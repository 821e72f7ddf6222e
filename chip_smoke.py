#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process drives the two entry points a user calls, at the published
widths of Mistral-7B-v0.1 (hidden 4096, 32 query / 8 KV heads of 128,
SwiGLU 14336, vocabulary 32,000) cut by depth only, with random weights
from a seed:

1. kernels — flash attention forward + backward and the paged decode
   kernel, once each at the model's shapes, against the XLA references;
2. train — ``dst.initialize`` (ZeRO-3, bf16 with fp32 master, Adam, full
   remat, flash attention) + ``engine.train_batch`` on one fixed batch;
3. serve — ``FastGenEngine`` (Pallas paged kernel) behind
   ``ServingFrontend``, two waves of requests so prefill and decode share
   ticks.

It refuses to start unless JAX's first device is a TPU the chip table
knows, never sets ``JAX_PLATFORMS``, and lets a failing phase raise.
Stdout is two JSON lines, written only after every phase ran: the report
(versions, compile cache, per-phase losses, timings, bytes; ends with
``"claim": null``), then, last, the verdict the driver reads — exactly
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it; ``"ok": true`` appears only after every phase passed on a
TPU. The report's timings are smoke timings (host clock, no warm-up
discipline), not benchmark metrics.

``--rehearse-cpu`` walks the same phases at ``tiny_llama`` size wherever
JAX runs (Pallas in interpret mode). It exists to debug the control flow
before spending chip time and can never print the pass line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import itertools
import json
import math
import sys
import time

SEED = 0

TRAIN_STEPS = 8
SERVE_LAYERS = 2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""
    train_seq: int
    lr: float
    block_size: int
    max_blocks_per_seq: int
    n_blocks: int
    token_budget: int
    prompt_lens: tuple      # two waves, first half then second half
    max_new: int
    paged_rows: int         # decode rows in the standalone kernel check


# lr: Adam's first steps move every parameter by ~lr whatever the
# gradient's scale, so the loss falls by ~lr * |g|_1, which grows with
# the parameter count: at 1e-4 the 698 M model memorises the fixed batch
# in two steps (11.2 -> 0.9 -> 0.0002, measured on the chip); 1e-5 gives
# a descent one can read
CHIP = Sizes(train_seq=2048, lr=1e-5,
             block_size=32, max_blocks_per_seq=64, n_blocks=1024,
             token_budget=512,
             prompt_lens=(200, 1500, 640, 977, 311, 1203, 1499, 450),
             max_new=64, paged_rows=16)
REHEARSAL = Sizes(train_seq=64, lr=1e-2,
                  block_size=8, max_blocks_per_seq=16, n_blocks=256,
                  token_budget=32,
                  prompt_lens=(20, 90, 41, 66, 25, 83, 57, 33),
                  max_new=8, paged_rows=4)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def model_config(layers: int, rehearsal: bool):
    """Mistral-7B-v0.1's config.json through ``hf_import``'s ``mistral``
    row; depth is the only key changed. The rehearsal uses the repo's
    ``tiny_llama`` preset (same block design, toy widths)."""
    from deepspeed_tpu.models import transformer as T

    if rehearsal:
        return T.get_model_config("tiny_llama", num_layers=layers,
                                  dtype="bfloat16", remat="full")
    from transformers import MistralConfig

    from deepspeed_tpu.models.hf_import import config_from_hf

    hf = MistralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=8, max_position_embeddings=32768,
        rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=4096,
        tie_word_embeddings=False)
    # sequences here stay <= 4096, where the model's window is inert
    return dataclasses.replace(config_from_hf(hf), dtype="bfloat16",
                               remat="full")


# --------------------------------------------------------------------- #
# phase 1: kernels against their references
# --------------------------------------------------------------------- #
def check_kernels(cfg, sz: Sizes) -> dict:
    """Flash forward + backward and paged decode at the model's head
    shapes; the tolerance and the reason for it are
    ``env_report.PROBE_TOL``'s."""
    from deepspeed_tpu import env_report

    N, K, D = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    t0 = time.perf_counter()
    errs = {**env_report.flash_rel_errors(sz.train_seq, N, K, D, SEED),
            **env_report.paged_rel_errors(
                sz.paged_rows, sz.max_blocks_per_seq, sz.block_size,
                N, K, D, SEED)}
    log(f"kernels: {errs}")
    env_report.check_rel_errors(errs)
    return {"tolerance": env_report.PROBE_TOL, "rel_err": errs,
            "seconds": round(time.perf_counter() - t0, 2)}


# --------------------------------------------------------------------- #
# phase 2: ZeRO-3 training
# --------------------------------------------------------------------- #
def peak_bytes_per_device() -> list:
    import jax

    from deepspeed_tpu.accelerator import get_accelerator

    acc = get_accelerator()
    return [int(acc.memory_stats(i)["peak_bytes_in_use"])
            for i in range(len(jax.local_devices()))]


def shard_bytes_per_device(tree) -> dict:
    """Bytes of ``tree``'s addressable shards, by device id."""
    import jax

    held = {d.id: 0 for d in jax.local_devices()}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return held


def has_mosaic_call(hlo_text: str) -> bool:
    return "tpu_custom_call" in hlo_text


def train_phase(sz: Sizes, rehearsal: bool) -> dict:
    import jax
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.profiling.observatory import ledger_for_engine

    n_dev = jax.device_count()
    # depth 2 x chips: 698 M parameters (~12.6 GB of ZeRO state) on one
    # chip, 2.0 B (~36 GB) on four — more than one chip holds, so a run
    # that put everything on the first chip dies instead of passing
    layers = 2 * n_dev
    cfg = model_config(layers, rehearsal)
    spec = dst.causal_lm_spec(cfg, attention="flash")
    engine, *_ = dst.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "train_batch_size": n_dev,
        "optimizer": {"type": "adam", "params": {"lr": sz.lr}},
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "mesh": {"data": n_dev},
        "steps_per_print": 10 ** 9,
    })
    log(f"train: {spec.num_params / 1e6:.0f} M parameters, depth {layers}, "
        f"mesh data={n_dev}")
    tokens = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (n_dev, sz.train_seq), dtype=np.int32)
    data = itertools.repeat({"tokens": tokens})

    losses, step_s = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(data)))
        step_s.append(time.perf_counter() - t0)
        log(f"train: step {len(losses)} loss {losses[-1]:.4f} "
            f"({step_s[-1]:.2f}s)")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    check(losses[-1] < math.log(cfg.vocab_size),
          f"last loss {losses[-1]} not below ln(vocab)")
    peaks = None
    if not rehearsal:   # the CPU reports host RAM, not a device allocator
        peaks = peak_bytes_per_device()
        check(all(p > 0 for p in peaks), f"a device reports no peak: {peaks}")
    held = {"params": shard_bytes_per_device(engine.state["master"]),
            "optimizer": shard_bytes_per_device(
                {m: engine.state["opt"][m]
                 for m in engine.optimizer.moment_names})}
    for what, by_dev in held.items():
        check(all(b > 0 for b in by_dev.values()),
              f"a device holds no shard of the {what}: {by_dev}")
        check(n_dev == 1 or max(by_dev.values()) < sum(by_dev.values()),
              f"{what} not sharded across devices: {by_dev}")

    ledger, _ = ledger_for_engine(engine, fold=False, seq_len=sz.train_seq)
    mosaic = has_mosaic_call(ledger.hlo_text)
    check(rehearsal or mosaic,
          "compiled train step contains no tpu_custom_call — the flash "
          "kernel did not go through Mosaic")
    out = {
        "model": "mistral-7b-v0.1 widths" if not rehearsal else "tiny_llama",
        "layers": layers, "params": int(spec.num_params),
        "mesh": {"data": n_dev}, "micro_batch_per_chip": 1,
        "seq_len": sz.train_seq, "steps": len(losses),
        "losses": [round(x, 4) for x in losses],
        "first_call_s": round(step_s[0], 2),
        "steady_step_s": round(float(np.median(step_s[1:])), 3),
        "peak_bytes_per_device": peaks,
        "shard_bytes_per_device": held,
        "mosaic_custom_call": mosaic,
    }
    engine.shutdown_telemetry()
    return out


# --------------------------------------------------------------------- #
# phase 3: FastGen behind the serving frontend
# --------------------------------------------------------------------- #
def serve_phase(sz: Sizes, rehearsal: bool) -> dict:
    import numpy as np

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.fastgen import FastGenEngine
    from deepspeed_tpu.serving import Admitted, ServingFrontend

    cfg = model_config(SERVE_LAYERS, rehearsal)
    engine = FastGenEngine(
        cfg, None, n_blocks=sz.n_blocks, block_size=sz.block_size,
        max_blocks_per_seq=sz.max_blocks_per_seq,
        token_budget=sz.token_budget, use_pallas_kernel=True, seed=SEED)
    free_at_start = engine.allocator.free_blocks
    fails = telemetry.counter("serving_tick_failures_total")
    fails_before = fails.total()
    rng = np.random.default_rng(SEED + 1)
    prompts = {uid: rng.integers(0, cfg.vocab_size, n).tolist()
               for uid, n in enumerate(sz.prompt_lens)}
    half = len(prompts) // 2
    tick_s = []

    def tick(fe) -> None:
        t0 = time.perf_counter()
        fe.run_tick()
        tick_s.append(time.perf_counter() - t0)

    def submit(fe, uids) -> None:
        for uid in uids:
            res = fe.submit(uid, prompts[uid], max_new_tokens=sz.max_new)
            check(isinstance(res, Admitted),
                  f"request {uid} not admitted: {res}")

    t_start = time.perf_counter()
    with ServingFrontend(engine) as fe:
        wave1, wave2 = list(prompts)[:half], list(prompts)[half:]
        submit(fe, wave1)
        # tick until every first-wave request is decoding, then let the
        # second wave's prefill share ticks with the first wave's decode
        while any(u in engine.seqs and engine.seqs[u].prefill_remaining
                  for u in wave1):
            check(len(tick_s) < 10_000, "first wave never reached decode")
            tick(fe)
        progress = [len(engine.seqs[u].generated)
                    for u in wave1 if u in engine.seqs]
        check(any(0 < n < sz.max_new for n in progress),
              f"no first-wave request mid-decode at second submit: "
              f"{progress}")
        submit(fe, wave2)
        while fe.active_count():
            check(len(tick_s) < 10_000, "server never drained")
            tick(fe)
        wall_s = time.perf_counter() - t_start

        results = {uid: fe.result(uid) for uid in prompts}
        for uid, res in results.items():
            check(res.state == "completed",
                  f"request {uid} ended {res.state} ({res.reason})")
            check(len(res.tokens) == sz.max_new,
                  f"request {uid}: {len(res.tokens)} tokens, "
                  f"wanted {sz.max_new}")
            check(all(0 <= t < cfg.vocab_size for t in res.tokens),
                  f"request {uid}: token out of range")
        tick_failures = fails.total() - fails_before
        check(tick_failures == 0,
              f"serving_tick_failures_total rose by {tick_failures}")
        check(fe.breaker.state == "closed",
              f"circuit breaker ended {fe.breaker.state}")
    check(engine.allocator.free_blocks == free_at_start,
          f"KV blocks leaked: {engine.allocator.free_blocks} free of "
          f"{free_at_start}")

    mosaic = has_mosaic_call(engine.collective_ledger(fold=False).hlo_text)
    check(rehearsal or mosaic,
          "compiled tick contains no tpu_custom_call — the paged kernel did "
          "not go through Mosaic")
    return {
        "layers": SERVE_LAYERS,
        # one replica on the default device; placing fleet replicas on
        # their own devices is ROADMAP R3's work
        "replicas": 1, "devices_used": 1,
        "requests": len(prompts), "prompt_tokens": sum(sz.prompt_lens),
        "new_tokens": sz.max_new * len(prompts), "ticks": len(tick_s),
        "tick_programs": len(engine._ticks),   # compiles among the ticks
        "first_call_s": round(tick_s[0], 2),
        "steady_tick_s": round(float(np.median(tick_s)), 4),
        "wall_s": round(wall_s, 2),
        "tick_failures": int(tick_failures), "breaker": "closed",
        "free_blocks": engine.allocator.free_blocks,
        "mosaic_custom_call": mosaic,
    }


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny_llama-size walk through every phase on "
                         "whatever backend JAX has; never prints the pass "
                         "line")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu

    import jax

    from deepspeed_tpu.utils.chip_specs import chip_peak_tflops
    from deepspeed_tpu.utils.compile_cache import ensure_compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not rehearsal:
        if dev.platform != "tpu":
            print(f"chip_smoke: JAX's first device is {dev.platform!r} "
                  f"({dev.device_kind}), not a TPU — refusing to run "
                  "(--rehearse-cpu walks the phases at a tiny size)",
                  file=sys.stderr)
            return 1
        chip_peak_tflops(dev.device_kind)   # raises for an unknown TPU
    else:
        # a rehearsal debugs this checkout's control flow: it compiles what
        # it runs instead of loading an earlier run's executables
        jax.config.update("jax_enable_compilation_cache", False)
    cache_dir = ensure_compile_cache()
    sz = REHEARSAL if rehearsal else CHIP
    log(f"device {device}, compile cache {cache_dir}")

    phases = {"kernels": check_kernels(model_config(SERVE_LAYERS,
                                                    rehearsal), sz)}
    phases["train"] = train_phase(sz, rehearsal)
    gc.collect()   # the engine is out of scope: its state leaves the chip
    phases["serve"] = serve_phase(sz, rehearsal)

    verdict = {"ok": not rehearsal, "device": device}
    print(json.dumps({
        **verdict,
        **({"rehearsal": True} if rehearsal else {}),
        "versions": {p: importlib.metadata.version(p)
                     for p in ("jax", "jaxlib", "libtpu")},
        "compile_cache_dir": cache_dir,
        "phases": phases,
        "wall_s": round(time.perf_counter() - T0, 1),
        "claim": None,
    }))
    # the driver reads the LAST line and wants these keys and no others
    print(json.dumps(verdict), flush=True)
    return 0


T0 = time.perf_counter()

if __name__ == "__main__":
    sys.exit(main())
