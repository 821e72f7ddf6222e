"""The movers of a share of an expert layer (``moe/layer.py``:
``held_rows_out``, ``held_pairs_in``, ``held_expert_act`` and their
transposes) against the plain forms they stand in for (a row a pair moved,
the absent masked): equal on the rows a held pair has, for every routing
from no pair here to every pair here, whatever the rows behind hold.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe import layer as MOE

T, K, H, INTER, HELD = 64, 8, 128, 64, 8
PAIRS = T * K
# (sorted rows a step, tokens a step): tiles the held pairs do not fill
TILES = (64, 16)
ULP = 2.0 ** -7              # of a bfloat16 in [1, 2)

SHARES = ("none", "one-pair", "quarter", "every-pair")


def _routing(share):
    """idx [T, K] over a router of 32 experts of which the first ``HELD``
    are here (``every-pair``: a router of the held experts alone)."""
    rng = np.random.default_rng(5)
    if share == "every-pair":
        idx = np.argsort(rng.random((T, HELD)), axis=1)[:, :K]
    else:
        idx = np.argsort(rng.random((T, 32)), axis=1)[:, :K]
        if share != "quarter":
            idx = HELD + idx % (32 - HELD)      # not one pair here
        if share == "one-pair":
            idx[17, 3] = 2
    return jnp.asarray(idx, jnp.int32)


def _operands(share, dtype=jnp.bfloat16):
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.normal(size=s), dtype)  # noqa: E731
    order, inv2d, sizes, here = MOE.held_group_sizes(_routing(share), HELD, 0)
    n = jnp.sum(sizes)
    want = {"none": 0, "one-pair": 1, "every-pair": PAIRS}.get(share)
    assert want is None or int(n) == want
    assert share != "quarter" or (0 < int(n) < PAIRS and int(n) % TILES[0])
    return dict(x=f(T, H), g=f(T, H), y_s=f(PAIRS, H), d_s=f(PAIRS, H),
                weights=jnp.asarray(rng.random((T, K)), dtype),
                up=f(PAIRS, INTER), gate=f(PAIRS, INTER),
                d_act=f(PAIRS, INTER), order=order, inv2d=inv2d, here=here,
                n=n)


def _poisoned(a, n):
    """Sorted arrays with the rows of the pairs that are not here NaN."""
    live = (jnp.arange(PAIRS) < n)[:, None]
    return {k: jnp.where(live, v, jnp.nan)
            if k in ("y_s", "d_s", "up", "gate", "d_act") else v
            for k, v in a.items()}


def _f32(v):
    return np.asarray(v, np.float32)


def _held_terms(a, src):
    """sum over a row's held pairs of |weight x row of ``src``|, float32:
    what one rounding of the sum is measured against."""
    picked = np.abs(_f32(src)[np.asarray(a["inv2d"])])
    return (np.where(np.asarray(a["here"])[..., None], picked, 0)
            * np.abs(_f32(a["weights"]))[..., None]).sum(1)


@pytest.mark.parametrize("share", SHARES)
def test_rows_out_is_the_plain_dispatch_on_the_held_rows(share):
    a = _operands(share)
    n = int(a["n"])
    got = MOE.held_rows_out(a["x"], a["order"], a["inv2d"], a["here"],
                            a["n"], TILES)
    want = MOE.held_dispatch_gather(a["x"], a["order"], a["inv2d"],
                                    a["here"])
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(_f32(got)[:n], _f32(want)[:n])


@pytest.mark.parametrize("share", SHARES)
def test_pairs_in_is_the_plain_combine_of_the_held_pairs(share):
    a = _operands(share)
    p = _poisoned(a, a["n"])
    got = MOE.held_pairs_in(p["y_s"], a["weights"], a["order"], a["inv2d"],
                            a["here"], a["n"], TILES)
    want = MOE.held_combine_gather(a["y_s"], a["weights"], a["order"],
                                   a["inv2d"], a["here"])
    assert bool(jnp.all(jnp.isfinite(got)))
    assert np.all(np.abs(_f32(got) - _f32(want))
                  <= ULP * _held_terms(a, a["y_s"]))
    if share == "none":
        assert not np.any(_f32(got))


@pytest.mark.parametrize("share", SHARES)
def test_transposes_are_the_plain_forms_on_the_held_rows(share):
    """``dx`` of the dispatch; ``dy`` and ``dw`` of the combine, with the
    rows behind the held pairs NaN: none of it reaches a gradient."""
    a = _operands(share)
    n = int(a["n"])
    p = _poisoned(a, a["n"])
    dx = jax.vjp(lambda x: MOE.held_rows_out(
        x, a["order"], a["inv2d"], a["here"], a["n"], TILES),
        a["x"])[1](p["d_s"])[0]
    dx_plain = jax.vjp(lambda x: MOE.held_dispatch_gather(
        x, a["order"], a["inv2d"], a["here"]), a["x"])[1](a["d_s"])[0]
    assert bool(jnp.all(jnp.isfinite(dx)))
    unweighted = dict(a, weights=jnp.ones_like(a["weights"]))
    assert np.all(np.abs(_f32(dx) - _f32(dx_plain))
                  <= ULP * _held_terms(unweighted, a["d_s"]))

    dy, dw = jax.vjp(lambda y, w: MOE.held_pairs_in(
        y, w, a["order"], a["inv2d"], a["here"], a["n"], TILES),
        p["y_s"], a["weights"])[1](a["g"])
    dy_plain, dw_plain = jax.vjp(lambda y, w: MOE.held_combine_gather(
        y, w, a["order"], a["inv2d"], a["here"]),
        a["y_s"], a["weights"])[1](a["g"])
    assert np.array_equal(_f32(dy)[:n], _f32(dy_plain)[:n])
    assert bool(jnp.all(jnp.isfinite(dw)))
    rows = np.abs(_f32(a["y_s"])[np.asarray(a["inv2d"])])
    scale = (rows * np.abs(_f32(a["g"]))[:, None]).sum(-1)
    assert np.all(np.abs(_f32(dw) - _f32(dw_plain)) <= ULP * scale)
    assert not np.any(_f32(dw)[~np.asarray(a["here"])])


@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("share", SHARES)
def test_activation_covers_the_held_rows(share, gated):
    a = _operands(share)
    n = int(a["n"])
    p = _poisoned(a, a["n"])
    name = "swiglu" if gated else "gelu"

    def forms(b):
        gate = b["gate"] if gated else None
        live = jax.vjp(lambda *o: MOE.held_expert_act(
            o[0], o[1] if gated else None, a["n"], name, TILES[0]),
            *((b["up"], gate) if gated else (b["up"],)))
        plain = jax.vjp(lambda *o: MOE._expert_act(
            o[0], o[1] if gated else None, name),
            *((b["up"], gate) if gated else (b["up"],)))
        return live, plain

    (act, vjp), _ = forms(p)
    _, (act_plain, vjp_plain) = forms(a)
    assert np.array_equal(_f32(act)[:n], _f32(act_plain)[:n])
    for got, want in zip(vjp(p["d_act"]), vjp_plain(a["d_act"])):
        assert np.array_equal(_f32(got)[:n], _f32(want)[:n])


@pytest.mark.parametrize("pairs,tokens,want", [
    (131072, 16384, (512, 512)), (16384, 2048, (512, 256)),
    (1024, 256, (512, 64)), (1024, 128, (512, 64)),
    (512, 64, (512, 64)), (256, 64, None), (1536 + 256, 224, None)])
def test_tiles_follow_the_static_shapes(pairs, tokens, want):
    assert MOE.held_tiles(pairs, tokens) == want


@pytest.mark.parametrize("share", SHARES)
def test_a_share_with_the_movers_is_the_share_with_the_plain_forms(
        share, monkeypatch):
    """The whole of ``_held_routed``, values and every gradient, float32:
    the movers engaged against the plain forms kept."""
    a = _operands(share, jnp.float32)
    rng = np.random.default_rng(9)
    experts = {k: jnp.asarray(rng.normal(size=s) / 8, jnp.float32)
               for k, s in (("w_up", (HELD, H, INTER)),
                            ("w_gate", (HELD, H, INTER)),
                            ("w_down", (HELD, INTER, H)))}
    idx = _routing(share)
    router = HELD if share == "every-pair" else 32

    def run(x, w, ex):
        y, rows = MOE._held_routed(x, w, idx, ex, "swiglu", 0, router)
        return jnp.sum(jnp.sin(y)), rows

    grad = jax.value_and_grad(run, (0, 1, 2), has_aux=True)
    assert MOE.held_tiles(PAIRS, T) == (PAIRS, T)
    (got, rows), got_g = grad(a["x"], a["weights"], experts)
    monkeypatch.setattr(MOE, "held_tiles", lambda pairs, tokens: None)
    assert MOE.held_tiles(PAIRS, T) is None
    (want, rows_plain), want_g = grad(a["x"], a["weights"], experts)
    assert int(rows.sum()) == int(a["n"]) == int(rows_plain.sum())
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def _share(seed=11):
    rng = np.random.default_rng(seed)
    gate_w = jnp.asarray(rng.normal(size=(H, 32)), jnp.float32)
    experts = {k: jnp.asarray(rng.normal(size=s) / 8, jnp.float32)
               for k, s in (("w_up", (HELD, H, INTER)),
                            ("w_gate", (HELD, H, INTER)),
                            ("w_down", (HELD, INTER, H)))}
    return rng, gate_w, experts


def test_meter_is_told_the_tile_the_movers_walk():
    """``moe_ffn`` of a share hands out, as an OUTPUT of the compiled
    program (no host callback: ``with_meter``), the rows of each held
    expert with the pairs the router chose and the rows a step of the
    movers takes, so the rows moved are ``ceil(n / tile) x tile`` of the
    pairs."""
    rng, gate_w, experts = _share()
    x = jnp.asarray(rng.normal(size=(2, 64, H)), jnp.float32)
    step = jax.jit(lambda x: MOE.moe_ffn(
        x, gate_w, experts, activation="swiglu", k=K, first_expert=0,
        with_meter=True))
    assert "callback" not in str(step.trace(x).jaxpr)
    assert "callback" not in step.lower(x).as_text()
    y, _, meter = step(x)
    rows, pairs, tile = MOE.read_held_meter(np.asarray(meter))
    assert rows.shape == (HELD,) and meter.dtype == jnp.int32
    assert pairs == 2 * 64 * K and tile == MOE.HELD_TILE_ROWS
    assert 0 < int(rows.sum()) < pairs
    # the rows are the router's own: the pairs that chose a held expert
    gate = MOE._gate_indices(x.reshape(-1, H), gate_w, None, K, "softmax",
                             True, 1, 1)
    want = np.bincount(np.asarray(gate.experts).reshape(-1),
                       minlength=32)[:HELD]
    assert np.array_equal(rows, want)
    # a call under a tile of pairs (the plain forms): no tile
    rows, pairs, tile = MOE.read_held_meter(np.asarray(
        MOE.held_meter(jnp.arange(3), 24, None)))
    assert (list(rows), pairs, tile) == ([0, 1, 2], 24, None)
    # every expert held, or no meter asked for: none comes back
    whole = {k: jnp.concatenate([v] * 4) for k, v in experts.items()}
    assert jax.eval_shape(lambda x: MOE.moe_ffn(
        x, gate_w, whole, activation="swiglu", k=K, with_meter=True),
        x)[2] is None
    assert len(jax.eval_shape(lambda x: MOE.moe_ffn(
        x, gate_w, experts, activation="swiglu", k=K), x)) == 2


# ------------------------------------------------------------------ #
# the meter rides the training step's outputs (runtime/engine.py)
# ------------------------------------------------------------------ #
LAYERS = 3


def _held_engine(gas=1):
    import deepspeed_tpu as dst
    from deepspeed_tpu import comm, telemetry
    from deepspeed_tpu.comm.mesh import MeshConfig, initialize_mesh
    from deepspeed_tpu.models import transformer as TR

    telemetry.reset()
    comm.init_distributed(verbose=False)
    # one device, as the one-chip cell's mesh (under a token-sharded mesh
    # a share's rows are not counted)
    mesh = initialize_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    cfg = TR.get_model_config(
        "tiny", n_experts=4, moe_router_experts=16, moe_top_k=4,
        num_layers=LAYERS, remat="full", moe_dispatch="ragged",
        dtype="float32")
    engine, *_ = dst.initialize(model=dst.causal_lm_spec(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas, "train_batch_size": 2 * gas,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}}},
        mesh_manager=mesh)
    return cfg, engine


def _batches(cfg, n, gas=1, seed=3):
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg.vocab_size, (2, 64),
                                    dtype=np.int32)}
            for _ in range(n * gas)]


@pytest.fixture
def one_device():
    """The global mesh and the registry as the next test expects them."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.comm.mesh import reset_mesh

    reset_mesh()
    yield
    reset_mesh()
    telemetry.reset()


def test_the_training_step_holds_no_host_callback(one_device):
    """The compiled step of a model that holds a share of its expert
    layers: no ``debug_callback`` in its jaxpr, no callback custom call in
    its lowered text (JAX writes no program that holds one to the
    persistent cache), and the meter among its outputs."""
    cfg, engine = _held_engine()
    batch = engine._shard_batch(jax.tree.map(
        lambda x: x[None], _batches(cfg, 1)[0]), leading=True)
    step = engine._select_step_builder(1)
    with engine.mesh:
        traced = step.trace(engine.state, batch)
        assert "callback" not in str(traced.jaxpr)
        assert "callback" not in traced.lower().as_text()
    meter = traced.out_info[1]["moe_held"]
    assert meter.shape == (1, LAYERS, 4 + 2) and meter.dtype == jnp.int32
    engine.shutdown_telemetry()


@pytest.mark.parametrize("gas", [1, 2])
def test_histograms_follow_the_steps_a_step_late(one_device, gas,
                                                 monkeypatch):
    """After N steps the four ``train_moe_*`` histograms hold ``layers x
    (N - 1)`` observations a micro-batch (step k's meter is observed when
    step k + 1 has been dispatched: no fence) and ``layers x N`` after the
    flush, with the means that the rows ``_held_routed`` returned give
    (the step's ``moe_held`` output is those rows:
    ``test_meter_is_told_the_tile_the_movers_walk``)."""
    from deepspeed_tpu import telemetry

    cfg, engine = _held_engine(gas)
    N = 3
    names = ("train_moe_held_expert_rows", "train_moe_load_imbalance",
             "train_moe_held_pair_share", "train_moe_moved_row_share")

    def counts():
        return [telemetry.get_registry().get(n).summary()["count"]
                for n in names]

    metrics = []
    after = engine._after_step
    monkeypatch.setattr(engine, "_after_step", lambda m, **kw: (
        metrics.append(np.asarray(m["moe_held"])), after(m, **kw)))
    batches = iter(_batches(cfg, N, gas))
    for step in range(N):
        engine.train_batch(batches)
        assert counts() == [LAYERS * gas * step] * 4
    engine.shutdown_telemetry()
    assert counts() == [LAYERS * gas * N] * 4
    engine.shutdown_telemetry()                 # nothing is observed twice
    assert counts() == [LAYERS * gas * N] * 4

    assert all(m.shape == (gas, LAYERS, 4 + 2) for m in metrics)
    meters = np.concatenate(metrics).reshape(-1, 4 + 2)
    pairs = 2 * 64 * cfg.moe_top_k
    assert (meters[:, -2] == pairs).all() and (meters[:, -1] == 512).all()
    rows = meters[:, :-2].astype(np.float64)
    summaries = [telemetry.get_registry().get(n).summary() for n in names]
    assert summaries[0]["mean"] == pytest.approx(rows.mean())
    assert summaries[1]["mean"] == pytest.approx(
        (rows.max(1) / rows.mean(1)).mean())
    assert summaries[2]["mean"] == pytest.approx(rows.sum(1).mean() / pairs)
    assert summaries[3]["mean"] == pytest.approx(
        (np.ceil(rows.sum(1) / 512) * 512).mean() / pairs)
    assert 0 < summaries[2]["mean"] < 1
