"""Flash-attention kernel numerics vs the XLA reference implementation.

Mirrors the reference's kernel-vs-torch numerics tests (``tests/unit/ops/``,
SURVEY.md §4): same op, two implementations, tight tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import (choose_blocks,
                                                      flash_attention,
                                                      step_account)


def _rand_qkv(key, B, S, N, D, K=None, dtype=jnp.float32):
    K = K or N
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, N, D), dtype)
    k = jax.random.normal(kk, (B, S, K, D), dtype)
    v = jax.random.normal(kv, (B, S, K, D), dtype)
    return q, k, v


# (S, query heads a KV head, dtype, block_q, block_kv) with blocks small enough
# that a grid holds dead steps (above the diagonal), open steps (wholly under
# it: no mask), diagonal steps and, where S is no multiple of a block, steps
# on the edge of ``kv_len`` (and of ``q_len`` in dk/dv): the one-block cases
# above and below never run the skip, the clamped index maps or the open body.
# Tiles of 128 (``small_tiles``), so that a masked block is 1 x 2 or 2 x 2
# tiles with a dead one among them, as a 1,024-wide block is of 512-wide tiles
BLOCKED = [
    (512, 1, "float32", 128, 256),
    (512, 4, "bfloat16", 256, 256),
    (640, 4, "float32", 128, 256),      # keys padded to 768: a kv_len edge
    (640, 1, "bfloat16", 128, 256),
    (600, 4, "float32", 256, 256),      # both lengths end inside a block
]
_TOL = {"float32": 2e-5, "bfloat16": 3e-2}


@pytest.fixture
def small_tiles(monkeypatch):
    import importlib

    # the package exports the function under the module's own name
    monkeypatch.setattr(importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention"), "_TILE", 128)


def _blocked_qkv(seed, S, rep, dtype):
    q, k, v = _rand_qkv(jax.random.PRNGKey(seed), 1, S, 4, 32, K=4 // rep,
                        dtype=jnp.dtype(dtype))
    return (q, k, v), [x.astype(jnp.float32) for x in (q, k, v)]


def _kinds_met(S, causal, block_q, block_kv, rep):
    """Which kinds of step the three grids of a case hold."""
    met = set()
    for steps in step_account(S, S, causal, block_q, block_kv, rep).values():
        met |= {kind for kind in ("masked", "open") if steps[kind]}
        met |= {"dead"} if steps["live"] < steps["steps"] else set()
        assert steps["live"] == steps["masked"] + steps["open"]
        assert steps["fetched"] <= steps["steps"]
        if causal:      # a diagonal block's tiles above the diagonal are dead
            assert steps["computed"] < steps["live"] * block_q * block_kv
    return met


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [128, 256])
def test_forward_matches_reference(causal, S):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, S, 4, 64)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,rep,dtype,block_q,block_kv", BLOCKED)
def test_forward_over_many_blocks(small_tiles, causal, S, rep, dtype, block_q,
                                  block_kv):
    met = _kinds_met(S, causal, block_q, block_kv, rep)
    assert met >= ({"dead", "masked", "open"} if causal else {"open"})
    assert "masked" in met or S % block_kv == 0
    (q, k, v), f32 = _blocked_qkv(7, S, rep, dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_kv=block_kv)
    assert out.dtype == q.dtype
    ref = dot_product_attention(*f32, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=_TOL[dtype], rtol=_TOL[dtype])


def test_forward_unaligned_seq_len():
    # S=192 pads to 256 with block 128; padded kv cols must not leak in
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 192, 2, 64)
    for causal in (True, False):
        out = flash_attention(q, k, v, causal=causal)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_gqa_heads():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 8, 64, K=2)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,K", [(128, 2), (192, 2), (128, 1)])
def test_gradients_match_reference(causal, S, K):
    # S=192 exercises the padding masks in both backward kernels; K=1 with
    # N=2 exercises the GQA group-summed dk/dv path
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, S, 2, 64, K=K)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,rep,dtype,block_q,block_kv", BLOCKED)
def test_gradients_over_many_blocks(small_tiles, causal, S, rep, dtype, block_q,
                                    block_kv):
    (q, k, v), f32 = _blocked_qkv(8, S, rep, dtype)

    def loss(attn, **blocks):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=causal, **blocks).astype(jnp.float32) ** 2)

    g_flash = jax.grad(loss(flash_attention, block_q=block_q,
                            block_kv=block_kv), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(*f32)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        assert gf.dtype == q.dtype
        # against the float32 reference's largest entry: a bfloat16
        # gradient is rounded once on its way out
        top = float(np.abs(np.asarray(gr)).max())
        np.testing.assert_allclose(
            np.asarray(gf, np.float32) / top, np.asarray(gr) / top,
            atol=5e-6 if dtype == "float32" else 1e-2, err_msg=name)


# what ``choose_blocks`` gives the two training cells (a chip's share of a
# step: length, query heads a KV head; heads of 128, bfloat16) and what the
# three grids then do, for one index of each grid's leading axis: steps, live,
# masked, open, fetched, score elements computed over the causal need
# S * (S + 1) / 2 (a KV head's ``rep`` query heads in ``flash_dkv``)
GEOMETRY = {
    (4096, 4): ((1024, 1024),
                {"flash_fwd": (16, 10, 4, 6, 9), "flash_dq": (16, 10, 4, 6, 9),
                 "flash_dkv": (64, 40, 16, 24, 40)}, 36 * 512 * 512),
    (2048, 1): ((1024, 1024),
                {"flash_fwd": (4, 3, 2, 1, 2), "flash_dq": (4, 3, 2, 1, 2),
                 "flash_dkv": (4, 3, 2, 1, 2)}, 10 * 512 * 512),
}


@pytest.mark.parametrize("S,rep", sorted(GEOMETRY))
def test_chosen_blocks_and_step_account(S, rep):
    blocks, steps, computed = GEOMETRY[S, rep]
    assert choose_blocks(S, S) == blocks
    account = step_account(S, S, True, *blocks, rep)
    kinds = ("steps", "live", "masked", "open", "fetched")
    for kernel, want in steps.items():
        assert tuple(account[kernel][k] for k in kinds) == want, kernel
        group = rep if kernel == "flash_dkv" else 1
        assert account[kernel]["computed"] == group * computed
    # the parent's 512 x 1,024 blocks, whole under their masks, computed
    # 20 x 512 x 1,024 (4,096) and 6 x 512 x 1,024 (2,048) for this need
    assert computed * 2 / (S * (S + 1)) < 1.25
    # shorter than a block: capped
    assert choose_blocks(192, 100) == (128, 64)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S,Skv", [(512, 512), (600, 600), (256, 512),
                                   (512, 256)])
def test_moving_blocks_stay_inside_their_arrays(causal, S, Skv):
    # keys past the last query (Skv > S, causal) are dead for every row:
    # their steps of dk/dv must still name a block the arrays have
    from deepspeed_tpu.ops.pallas.flash_attention import _kv_block, _q_block

    shape = dict(causal=causal, block_q=128, block_kv=128)
    n_q, n_kv = -(-S // 128), -(-Skv // 128)
    for i in range(n_q):
        for j in range(n_kv):
            assert 0 <= _kv_block(i, j, **shape) < n_kv
            assert 0 <= _q_block(i, j, q_len=S, **shape) < n_q


def test_traced_kernels_set_their_gauges():
    from deepspeed_tpu import telemetry

    telemetry.reset()
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), 1, 256, 4, 32, K=1)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=64, block_kv=128)))(q)
    gauge = telemetry.gauge("flash_steps")
    for kernel, steps in step_account(256, 256, True, 64, 128, 4).items():
        for kind, n in steps.items():
            assert gauge.value(kernel=kernel, kind=kind) == n, (kernel, kind)
    assert gauge.value(kernel="flash_dkv", kind="steps") == 2 * 4 * 4


def test_bf16_forward():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 128, 2, 64,
                        dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_model_spec_flash_option():
    """attention='flash' threads the kernel through the model zoo."""
    import deepspeed_tpu as dst

    spec = dst.causal_lm_spec(
        "tiny", hidden_size=64, num_layers=1, num_heads=4,
        max_seq_len=128, dtype="float32", attention="flash")
    params = spec.init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 256)
    loss = spec.loss_fn(params, tokens)
    assert np.isfinite(float(loss))


# --------------------------------------------------------------------- #
# multi-device meshes: Mosaic refuses a pallas_call that GSPMD would have
# to partition ("Mosaic kernels cannot be automatically partitioned" — the
# first four-chip run of this kernel), and accepts it only inside a
# shard_map manual over EVERY mesh axis. Interpret mode cannot reproduce
# the refusal, so the rule is checked on the traced program.
# --------------------------------------------------------------------- #
def _unmapped_pallas_calls(jaxpr, manual=frozenset(), all_axes=None):
    """pallas_call equations NOT under full-manual shard_maps."""
    bad = []
    for eqn in jaxpr.eqns:
        inner_manual, inner_axes = manual, all_axes
        if eqn.primitive.name == "shard_map":
            inner_manual = manual | eqn.params["manual_axes"]
            inner_axes = frozenset(eqn.params["mesh"].axis_names)
        if eqn.primitive.name == "pallas_call" and (
                all_axes is None or manual != all_axes):
            bad.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            bad += _unmapped_pallas_calls(sub, inner_manual, inner_axes)
    return bad


@pytest.fixture
def data_mesh():
    from deepspeed_tpu.comm import mesh as M

    M.reset_mesh()
    yield M.initialize_mesh().mesh       # data = all 8 virtual devices
    M.reset_mesh()


@pytest.mark.parametrize("B", [8, 2])   # 8 shards over data; 2 replicates
def test_kernel_runs_per_shard_under_a_mesh(data_mesh, B):
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), B, 64, 2, 16, K=1)

    def grads(attn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2)))

    traced = grads(flash_attention).trace(q, k, v)
    assert "pallas_call" in str(traced.jaxpr)
    assert _unmapped_pallas_calls(traced.jaxpr.jaxpr) == []
    got = traced.lower().compile()(q, k, v)
    for g, w in zip(got, grads(dot_product_attention)(q, k, v)):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_nested_under_a_partly_manual_step(data_mesh):
    """Inside a shard_map that is manual over the data axis only (the
    compressed-wire step builders), the kernel still ends up with every
    axis manual."""
    from jax.sharding import PartitionSpec as P

    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 8, 64, 2, 16)
    spec = P("data")
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        mesh=data_mesh, in_specs=(spec,) * 3, out_specs=spec,
        axis_names={"data"}, check_vma=False))
    assert _unmapped_pallas_calls(jax.make_jaxpr(fn)(q, k, v).jaxpr) == []
    np.testing.assert_allclose(
        fn(q, k, v), dot_product_attention(q, k, v, causal=True),
        atol=2e-5, rtol=2e-5)


def test_zero3_train_step_maps_every_kernel(data_mesh):
    import deepspeed_tpu as dst

    engine, *_ = dst.initialize(
        model=dst.causal_lm_spec("tiny_llama", attention="flash",
                                 remat="full"),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3},
                "bf16": {"enabled": True}, "steps_per_print": 10 ** 9})
    batch = {"tokens": jnp.zeros((1, 8, 64), jnp.int32)}
    with engine.mesh:
        traced = jax.make_jaxpr(engine._select_step_builder(1))(
            engine.state, batch)
    assert "pallas_call" in str(traced)
    assert _unmapped_pallas_calls(traced.jaxpr) == []


# ------------------------------------------------------------------ #
# a window: a causal row sees its last ``window`` positions
# ------------------------------------------------------------------ #
# (S, query heads a KV head, block_q, block_kv, window): windows smaller
# than, equal to and larger than a block, one of a single position, one
# longer than the sequence, lengths that end inside a block; tiles of 128,
# so that a block on the window's edge has a dead tile among its four
WINDOWED = [
    (512, 1, 128, 256, 64),
    (512, 4, 256, 256, 256),
    (512, 2, 128, 128, 300),
    (640, 4, 128, 256, 128),
    (600, 1, 256, 256, 1),
    (600, 4, 256, 128, 200),
    (384, 2, 128, 128, 1000),
]


def _windowed_reference(q, k, v, window):
    from deepspeed_tpu.models.hybrid import windowed_attention

    return windowed_attention(q, k, v, q.shape[-1] ** -0.5, window)


@pytest.mark.parametrize("S,rep,block_q,block_kv,window", WINDOWED)
def test_window_forward_and_gradients(S, rep, block_q, block_kv, window,
                                      small_tiles):
    (q, k, v), _ = _blocked_qkv(11, S, rep, "float32")
    ct = jax.random.normal(jax.random.PRNGKey(12), q.shape)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_kv=block_kv, window=window)

    out, vjp = jax.vjp(kernel, q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda q, k, v: _windowed_reference(q, k, v, window), q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for name, got, want in zip(("dq", "dk", "dv"), vjp(ct), ref_vjp(ct)):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5,
                                   err_msg=name)
    # the plain attention takes the same argument
    np.testing.assert_allclose(
        dot_product_attention(q, k, v, causal=True, window=window), ref,
        atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("S,rep,block_q,block_kv", [b[:2] + b[3:]
                                                    for b in BLOCKED])
def test_no_window_is_todays_call(S, rep, block_q, block_kv):
    """``window=0`` is the call without the argument: equal to the bit,
    forward and backward, under the same names."""
    (q, k, v), _ = _blocked_qkv(13, S, rep, "float32")

    def run(**kw):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block_q, block_kv=block_kv, **kw),
            q, k, v)
        return (out,) + vjp(out)

    for a, b in zip(run(), run(window=0)):
        np.testing.assert_array_equal(a, b)


def test_window_needs_causal():
    q, k, v = _rand_qkv(jax.random.PRNGKey(14), 1, 128, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=16)


# (S, block, window, rep) -> live steps a head, counted by hand. At 8,192
# under 1,024 x 1,024 blocks a row block sees its own block and the one
# before it (15 live of 64; the causal triangle has 36), every one masked
# (the diagonal, or the window's edge), each as four 512-wide tiles of
# which one is dead: (7 x 6 + 3) tiles; ``dk/dv`` walks the same pairs from
# the columns, for each of the group's query heads
WINDOW_GEOMETRY = {
    (8192, 1024, 1024, 8): (15, 45 * 512 * 512),
    (8192, 1024, 0, 8): (36, (28 * 4 + 8 * 3) * 512 * 512),
    (4096, 512, 1024, 1): (8 + 7 + 6, (8 + 7 + 6) * 512 * 512),
    (2048, 1024, 4096, 1): (3, 10 * 512 * 512),
}


@pytest.mark.parametrize("S,block,window,rep", sorted(WINDOW_GEOMETRY))
def test_step_account_under_a_window(S, block, window, rep):
    live, computed = WINDOW_GEOMETRY[S, block, window, rep]
    account = step_account(S, S, True, block, block, rep, window)
    n = S // block
    for kernel in ("flash_fwd", "flash_dq"):
        assert account[kernel]["steps"] == n * n
        assert account[kernel]["live"] == live
        assert account[kernel]["computed"] == computed
    assert account["flash_dkv"]["live"] == rep * live
    assert account["flash_dkv"]["computed"] == rep * computed
    # by brute force: a block is live iff one of its scores counts
    rows, cols = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (cols <= rows) & ((cols > rows - window) if window else True)
    blocks = seen.reshape(n, block, n, block).any(axis=(1, 3))
    assert int(blocks.sum()) == live
    # the kernels' moving blocks stay inside their arrays, and a dead step
    # names a block that a live step of its row (column) also names
    from deepspeed_tpu.ops.pallas.flash_attention import _kv_block, _q_block

    shape = dict(causal=True, block_q=block, block_kv=block, window=window)
    for i in range(n):
        named = {_kv_block(i, j, **shape) for j in range(n)}
        assert named == set(np.flatnonzero(blocks[i]))
    for j in range(n):
        named = {_q_block(i, j, q_len=S, **shape) for i in range(n)}
        assert named == set(np.flatnonzero(blocks[:, j]))


def test_window_calls_set_their_own_gauges():
    from deepspeed_tpu import telemetry

    telemetry.reset()
    q, k, v = _rand_qkv(jax.random.PRNGKey(15), 1, 256, 4, 32, K=1)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=64, block_kv=64, window=64)))(q)
    gauge = telemetry.gauge("flash_steps")
    account = step_account(256, 256, True, 64, 64, 4, 64)
    for kernel, steps in account.items():
        for kind, n in steps.items():
            assert gauge.value(kernel=f"window_{kernel}", kind=kind) == n
    assert account["flash_fwd"]["live"] == 7
