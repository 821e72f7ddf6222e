#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell by name from ``BENCHMARK.json`` and the files it points
to, sets the system up (weights from the seed, warm-up of the cell's own
shapes, correctness against the plain reference: all counted as
``setup_s``), measures for ``--seconds`` with nothing compiling, and
prints as its last line of standard output one JSON object with
``correct, attempted, failed, metrics, device`` (and ``breakdown`` with
``--trace 1``): the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``. Everything else worth keeping goes
on the line before it and into ``bench_out/``. Exits non-zero, printing
no result, unless JAX's devices are TPUs of a kind in ``peaks.json``.

``--rehearse`` walks the same control flow at a toy size on the CPU
(Pallas in interpret mode, virtual devices for a four-chip cell) to debug
the harness; it says ``"platform": "cpu"`` and never ``"correct": true``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402  (starts the set-up clock)
from benchmarks.manifest import (load_cell, load_manifest,  # noqa: E402
                                 load_plugin)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(load_manifest()["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    import deepspeed_tpu  # noqa: F401  (absent: not a checkout of the repo)
    import jax

    harness.log("imports done")

    from benchmarks import device as devmod

    try:
        device = devmod.describe(cell.chips, args.rehearse)
    except devmod.NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # every program of the run goes to the persistent cache, however
        # quickly it compiled: a second run must compile nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    harness.log(f"cell {cell.name}: runner {cell.runner}, config "
                f"{cell.config_name}, traffic {cell.traffic_name}, "
                f"device {device}")

    run = load_plugin("runners", cell.runner).run(cell, args, device)
    if not args.rehearse:
        run.peaks = devmod.peaks_for(device["kind"])
    # the most the process is known to have held at one time: its arrays'
    # peak (the reference comparison's included) or the program's own peak
    # with its temporaries, whichever is larger
    device["memory_peak_bytes"] = max(
        devmod.arrays_peak_bytes(cell.chips),
        run.extras.get("program_peak_bytes") or 0)
    run.extras["memory_stats_at_end"] = jax.devices()[0].memory_stats()

    metrics = {}
    group = "layer_metrics" if args.trace else "end_to_end"
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = load_plugin(group, m.reader).read(run)
        if value is not None:
            # a CPU number never goes under a device metric's name
            name = f"rehearsal.{m.name}" if args.rehearse else m.name
            metrics[name] = {"value": float(value), "unit": m.unit}
    line = {"correct": not harness.FAILURES and not args.rehearse,
            "attempted": run.attempted, "failed": run.failed,
            "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in run.trace.longest_gaps(
                harness.GAP_SPANS, 10)]}

    report = {"workload": cell.name, "seed": args.seed,
              "seconds": args.seconds, "trace": args.trace,
              "rehearsal": args.rehearse, "failures": harness.FAILURES,
              "extras": run.extras}
    os.makedirs(harness.OUT_DIR, exist_ok=True)
    with open(os.path.join(harness.OUT_DIR, f"last_{cell.name}.json"),
              "w") as f:
        json.dump({**report, **line}, f, indent=1, default=str)
    print(json.dumps(report, default=str))
    # the driver reads the LAST line and wants these keys and no others
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
