#!/usr/bin/env python3
"""Run one cell several times, as the driver does, and report the spread.

    chiprun [--chips 4] -- python benchmarks/tools/repeat.py --workload <cell> --sets 2 --runs 6

Each run is a new process of ``run.py`` with its own ``--seed`` (this
parent never touches JAX, so each child gets the chip); the sets use the
same seeds, ``--seed0`` onwards, as the driver's two sides do. For each
set and each end-to-end metric: median, spread (distance between the
quartiles over the median) and the spread without the run farthest from
the median (what the driver holds against half the bound); then the wider
of the sets' spreads and the shift of the second set's median against the
first. Lines are kept in
``chiprun_out/repeat/<cell>.jsonl``. The first run of a call may compile;
its ``setup_s`` is left out of the set-up median, as the driver does;
each run's line also keeps the runner's log of where its set-up went
(``stages``).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import stats  # noqa: E402  (numpy only: the parent stays off JAX)


#: of the report line's extras, what is kept beside each run
KEPT = ("loss_rel_diff", "logits_rel_diff", "program_peak_bytes",
        "kv_pool_peak", "degraded", "refused", "compiles_after_warmup")


#: a line of ``harness.log``
STAGE = re.compile(r"^\[bench \+\s*([0-9.]+)s\] (.*)$", re.M)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    seconds = args.seconds or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "repeat")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{args.workload}.jsonl")
    sets = []
    for s in range(args.sets):
        lines = []
        for r in range(args.runs):
            seed = args.seed0 + r
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(args.trace)],
                cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            tail = p.stdout.strip().splitlines()
            if p.returncode != 0 or not tail:
                print(f"set {s} run {r} seed {seed}: exit {p.returncode}",
                      flush=True)
                continue
            line = json.loads(tail[-1])
            line["seed"], line["set"] = seed, s
            extras = json.loads(tail[-2]).get("extras", {}) \
                if len(tail) > 1 else {}
            line["kept"] = {k: extras[k] for k in KEPT if k in extras}
            # where set-up went: the runner's own log, seconds from the start
            line["stages"] = [(float(t), what[:48]) for t, what in
                              STAGE.findall(p.stderr)]
            lines.append(line)
            with open(path, "a") as f:
                f.write(json.dumps(line) + "\n")
            print(f"set {s} run {r} seed {seed}: correct={line['correct']} "
                  f"failed={line['failed']}/{line['attempted']} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in line["metrics"].items())
                  + " " + " ".join(f"{k}={v}" if v is None else f"{k}={v:.4g}"
                                   for k, v in line["kept"].items()),
                  flush=True)
        sets.append(lines)
    names = sorted({k for ls in sets for l in ls for k in l["metrics"]})
    for name in names:
        meds, spreads, trimmed = [], [], []
        for s, ls in enumerate(sets):
            vals = [l["metrics"][name]["value"] for l in ls
                    if name in l["metrics"]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            if len(vals) < 2:
                continue
            meds.append(stats.percentile(vals, 50))
            spreads.append(stats.spread(vals))
            far = max(range(len(vals)), key=lambda i: abs(vals[i] - meds[-1]))
            rest = vals[:far] + vals[far + 1:]
            trimmed.append(stats.spread(rest) if len(rest) > 1 else 0.0)
        if not meds:
            continue
        shift = (meds[-1] - meds[0]) / meds[0] if len(meds) > 1 else 0.0
        print(f"{name}: medians {['%.6g' % m for m in meds]} spreads "
              f"{['%.4f' % s for s in spreads]} without the farthest run "
              f"{['%.4f' % s for s in trimmed]} widest {max(spreads):.4f} "
              f"second-vs-first {shift:+.4f} -> bound ~{5 * max(spreads):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
