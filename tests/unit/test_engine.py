"""End-to-end engine tests over the virtual 8-device mesh: every ZeRO stage,
precision mode, GAS, eager fwd/bwd/step parity, checkpoint round-trip.
(Reference analogs: tests/unit/runtime/zero, half_precision, checkpoint.)"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.runtime.dataloader import synthetic_lm_data


def _make(config_overrides=None, model="tiny", **model_overrides):
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "bf16": {"enabled": False},
        "steps_per_print": 1,
    }
    cfg.update(config_overrides or {})
    spec = dst.causal_lm_spec(model, dtype="float32", **model_overrides)
    engine, *_ = dst.initialize(model=spec, config=cfg)
    return engine


def _data(engine, seed=0):
    return synthetic_lm_data(
        batch_size=engine.train_micro_batch_size() * engine.dp_world_size,
        seq_len=32, vocab_size=512, seed=seed)


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_train(stage):
    engine = _make({"zero_optimization": {"stage": stage}})
    data = _data(engine)
    losses = [float(jax.device_get(engine.train_batch(data))) for _ in range(3)]
    assert all(np.isfinite(losses))
    assert engine.global_steps == 3


def test_zero_stages_agree():
    """All ZeRO stages are resharding of the same math → identical losses."""
    losses = {}
    for stage in (0, 1, 2, 3):
        engine = _make({"zero_optimization": {"stage": stage}})
        data = _data(engine, seed=7)
        for _ in range(3):
            loss = engine.train_batch(data)
        losses[stage] = float(jax.device_get(loss))
    base = losses[0]
    for stage, val in losses.items():
        np.testing.assert_allclose(val, base, rtol=2e-4), (stage, losses)


def test_train_batches_matches_per_step():
    """The fused multi-step dispatch (lax.scan over fused steps) advances
    the exact same state as N train_batch calls: same losses, same step
    counters, LR schedule advanced inside the scan."""
    cfg = {"zero_optimization": {"stage": 2},
           "scheduler": {"type": "WarmupLR",
                         "params": {"warmup_min_lr": 0.0,
                                    "warmup_max_lr": 1e-3,
                                    "warmup_num_steps": 10}}}
    e1 = _make(cfg)
    e2 = _make(cfg)
    d1, d2 = _data(e1, seed=5), _data(e2, seed=5)
    per_step = [float(jax.device_get(e1.train_batch(d1))) for _ in range(4)]
    fused = float(jax.device_get(e2.train_batches(d2, 4)))
    np.testing.assert_allclose(fused, np.mean(per_step), rtol=1e-4)
    assert e2.global_steps == 4
    # states agree after the window → next step produces the same loss
    n1 = float(jax.device_get(e1.train_batch(d1)))
    n2 = float(jax.device_get(e2.train_batch(d2)))
    np.testing.assert_allclose(n2, n1, rtol=1e-4)


def test_train_batches_single_and_fallback():
    # n_steps=1 delegates to train_batch
    e = _make({"zero_optimization": {"stage": 1}})
    d = _data(e)
    loss = e.train_batches(d, 1)
    assert np.isfinite(float(jax.device_get(loss)))
    assert e.global_steps == 1


def test_train_batches_host_phase_fallback_mean_loss():
    """Configs with host-side per-step phases (optimizer offload here) take
    the per-step fallback — same counters and the same mean-loss contract
    as the fused path."""
    cfg = {"zero_optimization": {
        "stage": 2, "offload_optimizer": {"device": "cpu"}}}
    e1 = _make(cfg)
    e2 = _make(cfg)
    d1, d2 = _data(e1, seed=11), _data(e2, seed=11)
    per_step = [float(jax.device_get(e1.train_batch(d1))) for _ in range(3)]
    fused = float(jax.device_get(e2.train_batches(d2, 3)))
    np.testing.assert_allclose(fused, np.mean(per_step), rtol=1e-5)
    assert e2.global_steps == 3


def test_state_is_sharded_stage3():
    engine = _make({"zero_optimization": {"stage": 3}})
    w = engine.state["master"]["blocks"]["wq"]
    # some dim of some param should be sharded over 'data' (8-way)
    shards = {s.device for s in w.addressable_shards}
    assert len(shards) == 8


def test_gradient_accumulation():
    engine = _make({"train_batch_size": 16, "train_micro_batch_size_per_gpu": 1})
    assert engine.gradient_accumulation_steps() == 2
    data = _data(engine)
    loss = engine.train_batch(data)
    assert np.isfinite(float(jax.device_get(loss)))


def test_fused_vs_eager_api_parity():
    """forward/backward/step must produce the same params as train_batch."""
    cfg = {"train_batch_size": 16, "train_micro_batch_size_per_gpu": 1,
           "zero_optimization": {"stage": 2}}
    e1 = _make(cfg)
    e2 = _make(cfg)
    gas = e1.gradient_accumulation_steps()
    batches = [next(_data(e1, seed=3)) for _ in range(gas)]

    data_iter = iter(batches)
    loss_fused = e1.train_batch(data_iter)

    for b in batches:
        loss = e2.forward(b)
        e2.backward(loss)
    e2.step()

    w1 = np.asarray(jax.device_get(e1.get_fp32_params()["blocks"]["wq"]))
    w2 = np.asarray(jax.device_get(e2.get_fp32_params()["blocks"]["wq"]))
    np.testing.assert_allclose(w1, w2, rtol=1e-5, atol=1e-6)


def test_fp16_loss_scaling():
    engine = _make({"fp16": {"enabled": True, "initial_scale_power": 8},
                    "zero_optimization": {"stage": 2}})
    data = _data(engine)
    for _ in range(2):
        loss = engine.train_batch(data)
    assert np.isfinite(float(jax.device_get(loss)))
    assert engine.loss_scale == 2.0 ** 8  # no overflow in 2 steps


def test_bf16_training():
    engine = _make({"bf16": {"enabled": True}, "zero_optimization": {"stage": 1}})
    data = _data(engine)
    loss = engine.train_batch(data)
    assert np.isfinite(float(jax.device_get(loss)))


def test_gradient_clipping_applied():
    engine = _make({"gradient_clipping": 1e-6})
    data = _data(engine)
    w_before = np.asarray(jax.device_get(engine.get_fp32_params()["blocks"]["wq"]))
    engine.train_batch(data)
    w_after = np.asarray(jax.device_get(engine.get_fp32_params()["blocks"]["wq"]))
    # tiny clip bound keeps the update near zero
    assert np.max(np.abs(w_after - w_before)) < 1e-3


def test_lr_schedule_integration():
    engine = _make({"scheduler": {"type": "WarmupLR",
                                  "params": {"warmup_min_lr": 0.0,
                                             "warmup_max_lr": 1e-3,
                                             "warmup_num_steps": 10,
                                             "warmup_type": "linear"}}})
    data = _data(engine)
    engine.train_batch(data)
    lr1 = engine.get_lr()[0]
    engine.train_batch(data)
    lr2 = engine.get_lr()[0]
    assert lr2 > lr1 >= 0.0


def test_checkpoint_roundtrip(tmp_path):
    engine = _make({"zero_optimization": {"stage": 2}})
    data = _data(engine)
    engine.train_batch(data)
    engine.save_checkpoint(str(tmp_path))
    w_saved = np.asarray(jax.device_get(engine.get_fp32_params()["blocks"]["wq"]))

    engine2 = _make({"zero_optimization": {"stage": 2}})
    engine2.load_checkpoint(str(tmp_path))
    w_loaded = np.asarray(jax.device_get(engine2.get_fp32_params()["blocks"]["wq"]))
    np.testing.assert_allclose(w_saved, w_loaded)
    assert engine2.global_steps == 1


def test_checkpoint_cross_topology(tmp_path):
    """Save at stage 3 (sharded), load at stage 0 (replicated) — the universal
    checkpoint behavior (reference deepspeed/checkpoint/ds_to_universal.py)."""
    engine = _make({"zero_optimization": {"stage": 3}})
    data = _data(engine)
    engine.train_batch(data)
    engine.save_checkpoint(str(tmp_path))
    w_saved = np.asarray(jax.device_get(engine.get_fp32_params()["blocks"]["wq"]))

    engine2 = _make({"zero_optimization": {"stage": 0}})
    engine2.load_checkpoint(str(tmp_path))
    w_loaded = np.asarray(jax.device_get(engine2.get_fp32_params()["blocks"]["wq"]))
    np.testing.assert_allclose(w_saved, w_loaded)


def test_eval_and_predict():
    engine = _make()
    batch = next(_data(engine))
    loss = engine.eval_batch(batch)
    assert np.isfinite(float(jax.device_get(loss)))
    logits = engine.predict(batch)
    assert logits.shape[-1] == 512


class TestActivationCheckpointingConfig:
    def test_policy_reaches_the_model(self):
        """activation_checkpointing.policy rebuilds the spec with that remat
        policy (previously a silent config no-op; also what the autotuner's
        remat dimension tunes)."""
        from deepspeed_tpu.comm import mesh as mesh_mod

        mesh_mod.reset_mesh()
        spec = dst.causal_lm_spec("tiny", dtype="float32", max_seq_len=32)
        assert spec.config.remat == "none"
        config = {
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}, "mesh": {"data": 8},
            "activation_checkpointing": {"policy": "full"},
            "steps_per_print": 10 ** 9,
        }
        engine, *_ = dst.initialize(model=spec, config=config)
        assert engine.model_spec.config.remat == "full"
        batch = {"tokens": np.random.RandomState(0).randint(
            0, 256, size=(8, 32)).astype(np.int32)}
        loss = engine.train_batch(iter([batch]))
        assert np.isfinite(float(loss))

    def test_unknown_policy_raises(self):
        from deepspeed_tpu.comm import mesh as mesh_mod

        mesh_mod.reset_mesh()
        spec = dst.causal_lm_spec("tiny", dtype="float32", max_seq_len=32)
        config = {
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}, "mesh": {"data": 8},
            "activation_checkpointing": {"policy": "selectve"},  # typo
        }
        engine, *_ = dst.initialize(model=spec, config=config)
        batch = {"tokens": np.random.RandomState(0).randint(
            0, 256, size=(8, 32)).astype(np.int32)}
        with pytest.raises(ValueError, match="unknown remat"):
            engine.train_batch(iter([batch]))


def test_grad_accum_dtype_bf16():
    """data_types.grad_accum_dtype switches the GAS accumulator (at multi-B
    params the fp32 grad buffer is the HBM ceiling)."""
    import itertools

    import deepspeed_tpu as dst
    from deepspeed_tpu.runtime.dataloader import synthetic_lm_data

    spec = dst.causal_lm_spec("tiny", dtype="bfloat16", num_layers=2,
                              max_seq_len=64)
    dp = jax.device_count()
    config = {"train_batch_size": 4 * dp * 2,
              "train_micro_batch_size_per_gpu": 4,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 1},
              "bf16": {"enabled": True},
              "data_types": {"grad_accum_dtype": "bfloat16"},
              "steps_per_print": 10 ** 9}
    engine, *_ = dst.initialize(model=spec, config=config)
    # the wiring itself (not just convergence — fp32 accumulation would
    # also converge): the shared dtype helper must honor the section,
    # including the reference's short spellings
    assert engine._grad_accum_dtype() == jnp.bfloat16
    engine.config.data_types.grad_accum_dtype = "bf16"
    assert engine._grad_accum_dtype() == jnp.bfloat16
    engine.config.data_types.grad_accum_dtype = "bfloat16"
    data = itertools.repeat(next(synthetic_lm_data(4 * dp, 64, 512, seed=0)))
    l0 = float(engine.train_batch(data))
    for _ in range(40):
        loss = float(engine.train_batch(data))
    assert loss < l0 - 1.0, (l0, loss)
