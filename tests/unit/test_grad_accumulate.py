"""One micro-batch has nothing to accumulate: at
``gradient_accumulation_steps`` 1 every step builder hands the backward's
gradients to the update and builds no accumulator (no zeros, no add, no
``grad_accumulate`` scope); at 2 and more the shared loop zero-fills,
scans and adds as it always did. The results keep their bits: ``0 + g``
is ``g`` in every accumulator dtype."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

import deepspeed_tpu as dst
from deepspeed_tpu.comm.mesh import reset_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine as DeepSpeedEngine

#: every builder ``_select_step_builder`` can pick on the CPU mesh, and
#: the host-step runner's gradient step
BUILDERS = {
    # stage 3, overlap scheduler on (the default): gradients constrained
    # bucket by bucket behind fences — the benchmark cells' program
    "exact": {"zero_optimization": {"stage": 3,
                                    "reduce_bucket_size": 4096}},
    "exact_unfenced": {"zero_optimization": {"stage": 2,
                                             "overlap_comm": False}},
    "bucketed_wire": {"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True,
        "reduce_bucket_size": 4096, "allgather_bucket_size": 8192}},
    "loco": {"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True,
        "loco_error_feedback": True}},
    "qz": {"zero_optimization": {
        "stage": 2, "zero_quantized_gradients": True,
        "overlap_comm": False}},
    "onebit": {"optimizer": {"type": "onebitadam",
                             "params": {"lr": 1e-3, "freeze_step": 2}},
               "zero_optimization": {"stage": 0}},
    "host_step": {"zero_optimization": {
        "stage": 0,
        "offload_optimizer": {"device": "cpu", "host_step": True}}},
}
BUILDER_METHOD = {
    "exact": "_build_train_step", "exact_unfenced": "_build_train_step",
    "bucketed_wire": "_build_train_step_bucketed_wire",
    "loco": "_build_train_step_bucketed_wire",
    "qz": "_build_train_step_qz", "onebit": "_build_train_step_onebit"}


def _engine(builder, gas, **overrides):
    reset_mesh()
    spec = dst.causal_lm_spec("tiny", dtype="float32", hidden_size=64,
                              num_layers=2, num_heads=4, max_seq_len=32,
                              vocab_size=512)
    cfg = {"train_batch_size": 8 * gas, "train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
           "steps_per_print": 10 ** 9}
    cfg.update(BUILDERS[builder])
    cfg.update(overrides)
    engine, *_ = dst.initialize(model=spec, config=cfg)
    return engine


#: engines of one micro-batch that ``test_accumulator_exists_only...`` built
#: and traced but never trained, by builder: the bits test trains each (its
#: run of the new loop) and so builds one engine fewer a builder
_UNTRAINED = {}


def _step_and_args(engine, builder, gas):
    batch = {"tokens": jnp.zeros((gas, 8, 32), jnp.int32)}
    if builder == "host_step":
        runner = engine._host_runner
        return runner._build_grad_step(gas), (runner.device_params, batch)
    picked = []
    name = BUILDER_METHOD[builder]
    orig = getattr(engine, name)
    setattr(engine, name, lambda g: picked.append(name) or orig(g))
    step = engine._select_step_builder(gas)
    assert picked == [name], "the configuration reaches another builder"
    return step, (engine.state, batch)


# --------------------------------------------------------------------- #
# (a) the program
# --------------------------------------------------------------------- #
#: value-preserving primitives a zero-filled array may pass through on
#: its way into an add (the gradient constraints and their fences)
PASS = {"sharding_constraint", "optimization_barrier", "convert_element_type",
        "copy"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _is_zero_literal(v):
    return isinstance(v, jex_core.Literal) and np.ndim(v.val) == 0 \
        and v.val == 0


def _survey(jaxpr, found):
    """Walk ``jaxpr`` and everything under it: ``found["scoped"]`` gets the
    primitive of every equation under the ``grad_accumulate`` scope,
    ``found["zero_adds"]`` the shape of every ``add`` one of whose operands
    is an array of zeros made in the same computation, and
    ``found["scoped_zeros"]`` the zero-fills under the scope."""
    zeros = set()
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        scoped = "grad_accumulate" in str(eqn.source_info.name_stack)
        if scoped:
            found["scoped"].append(prim)
        ins = [v for v in eqn.invars if not isinstance(v, jex_core.Literal)]
        if prim == "broadcast_in_dim" and _is_zero_literal(eqn.invars[0]) \
                and eqn.outvars[0].aval.ndim >= 1:
            zeros.add(eqn.outvars[0])
            if scoped:
                found["scoped_zeros"].append(eqn.outvars[0].aval.shape)
        elif prim in PASS and len(eqn.invars) == len(eqn.outvars):
            zeros.update(o for i, o in zip(eqn.invars, eqn.outvars)
                         if not isinstance(i, jex_core.Literal) and i in zeros)
        elif prim == "add" and any(v in zeros for v in ins):
            found["zero_adds"].append(eqn.outvars[0].aval.shape)
        for sub in _sub_jaxprs(eqn):
            _survey(sub, found)
    return found


@pytest.mark.parametrize("gas", [1, 2])
@pytest.mark.parametrize("builder", list(BUILDERS))
def test_accumulator_exists_only_beyond_one_microbatch(builder, gas):
    engine = _engine(builder, gas)
    step, args = _step_and_args(engine, builder, gas)
    with engine.mesh:
        traced = step.trace(*args)
        found = _survey(traced.jaxpr.jaxpr,
                        {"scoped": [], "zero_adds": [], "scoped_zeros": []})
        compiled = [n for n in re.findall(
            r'op_name="([^"]+)"', traced.lower().compile().as_text())
            if "grad_accumulate" in n]
    if gas == 1:
        # nothing under the scope, as traced and as compiled, and no
        # zero-filled array that an add then reads
        assert found["scoped"] == []
        assert found["zero_adds"] == []
        assert compiled == []
    else:
        # the carry of the scan: one zero-fill a parameter (the local
        # shard's shape inside a manual region), one add a parameter
        n = len(jax.tree.leaves(engine._shapes))
        assert len(found["scoped_zeros"]) == n
        assert found["scoped"].count("add") == n
        assert "scan" not in found["scoped"]   # the scan wraps the scope
        assert compiled
    if gas == 1:
        _UNTRAINED[builder] = engine
    else:
        engine.shutdown_telemetry()


# --------------------------------------------------------------------- #
# (b) the bits: against the loop as it was, zeros + g through a carry
# --------------------------------------------------------------------- #
def _old_loop(micro_fn, like, acc_dtype, batch, gas,
              constrain=lambda x: x, extra0=None):
    """``accumulate_microbatches`` as it stood before: a zeroed carry at
    every ``gas``, the one micro-batch of ``gas == 1`` added into it."""
    with_extra = extra0 is not None

    def micro(carry, mb):
        if with_extra:
            acc, extra = carry
            loss, grads, extra = micro_fn(mb, extra)
        else:
            acc = carry
            loss, grads = micro_fn(mb)
        acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc, grads)
        acc = constrain(acc)
        return ((acc, extra) if with_extra else acc), loss

    zeros = constrain(jax.tree.map(
        lambda s: jnp.zeros(s.shape, acc_dtype), like))
    carry0 = (zeros, extra0) if with_extra else zeros
    if gas == 1:
        carry, loss = micro(carry0, jax.tree.map(lambda x: x[0], batch))
    else:
        carry, losses = jax.lax.scan(micro, carry0, batch)
        loss = jnp.mean(losses)
    if with_extra:
        grads_sum, extra = carry
        return grads_sum, loss, extra
    return carry, loss


def _bits(tree):
    return [np.asarray(jax.device_get(x)).tobytes()
            for x in jax.tree.leaves(tree)]


def _three_steps(builder, overrides, gas=1, engine=None):
    engine = engine or _engine(builder, gas, **overrides)
    rng = np.random.default_rng(3)
    losses = []
    for _ in range(3):
        toks = rng.integers(0, 512, (8 * gas, 32)).astype(np.int32)
        losses.append(np.float32(engine.train_batch(iter([toks] * gas))))
    state = {k: engine.state[k] for k in ("master", "opt", "step")}
    if "loco_err" in engine.state:
        state["loco_err"] = engine.state["loco_err"]
    out = _bits(state), [x.tobytes() for x in losses], losses
    engine.shutdown_telemetry()
    return out


FP16 = {"fp16": {"enabled": True, "initial_scale_power": 8}}
BF16_ACC = {"data_types": {"grad_accum_dtype": "bfloat16"}}


@pytest.mark.parametrize("builder,overrides", [
    *[pytest.param(b, {}, id=f"{b}-fp32") for b in BUILDERS],
    *[pytest.param(b, BF16_ACC, id=f"{b}-bf16") for b in BUILDERS],
    pytest.param("exact", FP16, id="exact-fp16-scaled"),
    pytest.param("bucketed_wire", FP16, id="bucketed_wire-fp16-scaled"),
])
def test_one_microbatch_keeps_the_old_loops_bits(builder, overrides,
                                                 monkeypatch):
    new_state, new_loss, losses = _three_steps(
        builder, overrides,
        engine=None if overrides else _UNTRAINED.pop(builder, None))
    assert np.all(np.isfinite(losses)) and losses[-1] != losses[0]
    monkeypatch.setattr(DeepSpeedEngine, "accumulate_microbatches",
                        staticmethod(_old_loop))
    old_state, old_loss, _ = _three_steps(builder, overrides)
    assert new_loss == old_loss
    assert new_state == old_state


# --------------------------------------------------------------------- #
# (c), (d) the shared function alone
# --------------------------------------------------------------------- #
def _toy_micro(w):
    def micro_fn(mb):
        return jax.value_and_grad(
            lambda p: jnp.sum((mb @ p["w"] + p["b"]) ** 2))(w)
    return micro_fn


@pytest.fixture()
def toy():
    rng = np.random.default_rng(0)
    w = {"w": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32)}
    batch = jnp.asarray(rng.normal(size=(2, 4, 5)), jnp.float32)
    return w, batch


@pytest.mark.parametrize("acc_dtype", [jnp.float32, jnp.bfloat16])
def test_two_microbatches_sum_and_mean(toy, acc_dtype):
    w, batch = toy
    micro_fn = _toy_micro(w)
    grads, loss = jax.jit(
        lambda b: DeepSpeedEngine.accumulate_microbatches(
            micro_fn, w, acc_dtype, b, 2))(batch)
    (l0, g0), (l1, g1) = micro_fn(batch[0]), micro_fn(batch[1])
    np.testing.assert_allclose(loss, (l0 + l1) / 2, rtol=1e-6)
    for k in w:
        assert grads[k].dtype == acc_dtype
        want = (jnp.zeros_like(g0[k], acc_dtype) + g0[k].astype(acc_dtype)
                + g1[k].astype(acc_dtype))
        np.testing.assert_array_equal(np.asarray(grads[k], np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("acc_dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.float16])
def test_one_microbatch_is_the_gradient_itself(toy, acc_dtype):
    w, batch = toy
    seen = []

    def constrain(tree):
        seen.append(jax.tree.map(lambda x: x.dtype, tree))
        return tree

    grads, loss = DeepSpeedEngine.accumulate_microbatches(
        _toy_micro(w), None, acc_dtype, batch[:1], 1, constrain=constrain)
    want_loss, want = _toy_micro(w)(batch[0])
    assert np.float32(loss) == np.float32(want_loss)
    for k in w:
        assert grads[k].dtype == acc_dtype
        assert np.asarray(grads[k]).tobytes() == np.asarray(
            jnp.zeros_like(want[k], acc_dtype)
            + want[k].astype(acc_dtype)).tobytes()
    # constrained once, as the accumulated tree was: in the accumulator's
    # dtype, after the cast
    assert seen == [{k: jnp.dtype(acc_dtype) for k in w}]


@pytest.mark.parametrize("gas", [1, 2])
def test_extra_carry_passes_through_micro_fn(toy, gas):
    """LoCo's residuals: at one micro-batch the extra that comes back is
    the one ``micro_fn`` made from ``extra0``, and ``micro_fn`` ran once."""
    w, batch = toy
    calls = []

    def micro_fn(mb, extra):
        calls.append(1)
        loss, g = _toy_micro(w)(mb)
        return loss, g, jax.tree.map(lambda e, x: 0.5 * e + x, extra, g)

    extra0 = jax.tree.map(jnp.ones_like, w)
    grads, loss, extra = DeepSpeedEngine.accumulate_microbatches(
        micro_fn, w, jnp.float32, batch[:gas], gas, extra0=extra0)
    assert len(calls) == 1            # gas 2: traced once, by the scan
    want = extra0
    for i in range(gas):
        _, g = _toy_micro(w)(batch[i])
        want = jax.tree.map(lambda e, x: 0.5 * e + x, want, g)
    for k in w:
        np.testing.assert_allclose(extra[k], want[k], rtol=1e-6)
    if gas == 1:
        _, g = _toy_micro(w)(batch[0])
        for k in w:
            assert np.asarray(grads[k]).tobytes() == \
                np.asarray(g[k]).tobytes()
            assert np.asarray(extra[k]).tobytes() == \
                np.asarray(0.5 * extra0[k] + g[k]).tobytes()
