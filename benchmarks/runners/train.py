"""Runner ``train``: ``dst.initialize`` + ``engine.train_batch`` as a user's
training script drives them, on the cell's mesh.

Timing method: every step is fenced by reading its loss back to the host
(``float(loss)``), every step of the window counts, and the rate is the
tokens of the steps completed over the time those steps took: never a
best window. A fresh seeded batch is drawn inside the timed step (the
input pipeline is part of a step).
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, Iterator

import numpy as np

from benchmarks import device as devmod
from benchmarks import harness, model_config
from benchmarks.harness import check, log
from benchmarks.manifest import Cell, load_plugin

#: the first batch the reference comparison is made on: ones the feed
#: never reaches, each trained on once after the warm-up
CHECKED_BATCH = 10 ** 9


def run(cell: Cell, args, device: Dict[str, Any]) -> harness.RunRecord:
    import jax

    import deepspeed_tpu as dst

    reference = load_plugin("reference", cell.config["reference"])
    deploy, chips = cell.deploy, cell.chips
    cfg = model_config.build(cell.config, "train", remat=deploy["remat"],
                             rehearse=args.rehearse)
    traffic = dict(cell.traffic["params"])
    if args.rehearse:
        traffic.update(cell.traffic.get("rehearse", {}))
    batches = load_plugin("generators", cell.traffic["generator"]).build(
        traffic, args.seed, cfg.vocab_size)
    spec = dst.causal_lm_spec(cfg, attention=deploy["attention"])
    engine_cfg = dict(deploy["engine"])
    engine_cfg.update({
        "train_micro_batch_size_per_gpu": batches.micro_batch,
        "gradient_accumulation_steps": 1,
        "train_batch_size": batches.micro_batch * chips,
        "mesh": {"data": chips},
        "seed": args.seed,
        "steps_per_print": 10 ** 9,
    })
    engine, *_ = dst.initialize(model=spec, config=engine_cfg)
    n_params = int(spec.num_params)
    log(f"train: {n_params / 1e6:.0f} M parameters, depth {cfg.num_layers}, "
        f"mesh data={chips}, {batches.tokens_per_step(chips)} tokens a step")

    step_no = [0]

    def feed() -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield {"tokens": batches.batch(step_no[0], chips)}
            step_no[0] += 1

    data = feed()

    # ---- warm-up: the first call lowers the step (and compiles it, or
    # finds it in the cache); its text says whether flash attention went
    # through Mosaic, in untraced runs too ----
    with harness.LoweredText(cell.name) as lowered:
        loss0 = float(engine.train_batch(data))
    mosaic = lowered.contains("tpu_custom_call")
    for _ in range(int(deploy.get("warmup_steps", 2))):
        float(engine.train_batch(data))
    log("train: warm-up steps done")
    # every program of the window has run: the program's own peak
    peak_warm = devmod.memory_peak_bytes(chips)

    # ---- reference loss on batches the feed never reaches, each from the
    # weights its step will compute with (fp32 master rounded to the
    # compute type; the step then trains on it, as any step does); after
    # the warm-up, so that the peak above is the program's alone. How many
    # batches is the cell file's ``loss_check.batches`` (1 where absent):
    # the two means are compared ----
    hf = model_config.hf_kwargs(cell.config, "train")
    if args.rehearse:
        hf.update(cell.config["rehearse"])
    arch = reference.arch_from_config(cell.config, hf)
    pairs = []                                   # (engine loss, reference)
    for b in range(int(deploy["loss_check"].get("batches", 1))):
        as_computed = jax.tree.map(
            lambda x: x.astype(cfg.compute_dtype), engine.state["master"])
        checked = batches.batch(CHECKED_BATCH + b, chips)
        ref = reference.next_token_loss(as_computed, checked, arch)
        del as_computed
        peak_checked = devmod.memory_peak_bytes(chips)   # a running peak
        pairs.append((float(engine.train_batch(iter([{"tokens": checked}]))),
                      ref))
    loss_checked, ref_loss = (float(np.mean(x)) for x in zip(*pairs))
    tol = float(deploy["loss_check"]["rel_tol"])
    rel = abs(loss_checked - ref_loss) / abs(ref_loss)
    log(f"train: loss on the {len(pairs)} checked batch(es) "
        f"{loss_checked:.5f}, reference {ref_loss:.5f} (rel. diff {rel:.2e}, "
        f"tolerance {tol:.0e}); each, signed: "
        + " ".join(f"{(a - r) / abs(r):+.2e}" for a, r in pairs))
    # the compiled step's collectives and HLO: the only public way to them
    # lowers and compiles the step again (9.5 s warm on four chips), and
    # only per-layer metrics read it, so only a traced run pays for it
    ledger = engine.collective_ledger(
        fold=False, seq_len=batches.seq_len) if args.trace else None
    compiles = harness.CompileCounter()

    # ---- the window ----
    setup_s = time.perf_counter() - harness.T0
    tel0 = harness.telemetry_snapshot()
    steps = []                                   # (seconds, loss)
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < args.seconds:
        with jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            loss = float(engine.train_batch(data))
            steps.append((time.perf_counter() - t0, loss))
    tel1 = harness.telemetry_snapshot()
    compiles_in_window = compiles.count

    # ---- the traced stretch, after the window ----
    trace = None
    if args.trace:
        prof = harness.Profiler(cell.name)
        prof.start()
        t_tr = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            while time.perf_counter() - t_tr < deploy["trace_seconds"]:
                with jax.profiler.TraceAnnotation("bench.step"):
                    float(engine.train_batch(data))
        trace = prof.stop()

    # ---- correct ----
    losses = [l for _, l in steps]
    check(rel <= tol,
          f"loss {loss_checked} vs reference {ref_loss}: rel. diff "
          f"{rel:.3e} > {tol}")
    check(all(math.isfinite(l) for l in [loss0] + losses),
          f"non-finite loss in the window: {losses}")
    check(len(losses) >= 5, f"only {len(losses)} steps in the window")
    check(float(np.mean(losses[-5:])) < loss0,
          f"mean of the last five losses {np.mean(losses[-5:])} not below "
          f"the first {loss0}")
    check(compiles_in_window == 0,
          f"{compiles_in_window} program(s) compiled inside the window")
    check(args.rehearse or (mosaic and (
          ledger is None or "tpu_custom_call" in ledger.hlo_text)),
          "the step holds no tpu_custom_call: flash attention did not go "
          "through Mosaic")
    engine.shutdown_telemetry()

    return harness.RunRecord(
        cell=cell, seconds=args.seconds, chips=chips, device=device,
        peaks=None, model=cfg, setup_s=setup_s,
        client={"steps": steps,
                "tokens_per_step": batches.tokens_per_step(chips),
                "seq_len": batches.seq_len},
        telemetry=harness.Telemetry(tel0, tel1), trace=trace,
        extras={"n_params": n_params,
                "program_peak_bytes": devmod.program_peak_bytes(
                    peak_warm, peak_checked, devmod.memory_peak_bytes(chips)),
                "peak_bytes_after_warmup": peak_warm,
                "peak_bytes_after_reference": peak_checked,
                "collective_bytes_per_step":
                    ledger.total_bytes() if ledger else None,
                "collectives": {k: v["count"] for k, v in
                                ledger.totals_by_kind().items()}
                if ledger else None,
                "loss_first": loss0, "loss_checked": loss_checked,
                "loss_ref": ref_loss, "loss_checked_pairs": pairs,
                "loss_rel_diff": rel, "loss_last5": float(
                    np.mean(losses[-5:])) if losses else None,
                "steps": len(steps),
                "compiles_in_window": compiles_in_window},
        attempted=len(steps), failed=0)
