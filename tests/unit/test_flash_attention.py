"""Flash-attention kernel numerics vs the XLA reference implementation.

Mirrors the reference's kernel-vs-torch numerics tests (``tests/unit/ops/``,
SURVEY.md §4): same op, two implementations, tight tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(key, B, S, N, D, K=None, dtype=jnp.float32):
    K = K or N
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, N, D), dtype)
    k = jax.random.normal(kk, (B, S, K, D), dtype)
    v = jax.random.normal(kv, (B, S, K, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [128, 256])
def test_forward_matches_reference(causal, S):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, S, 4, 64)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


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
