#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, on the chip.

    chiprun -- python benchmarks/tools/sweep_knee.py --workload <cell> --rates 3,4,5,6,7,8

One engine, warmed once; for each rate the cell's own traffic mix at that
rate (pre-roll, then a window of ``--seconds``), then a drain. A rate is
*sustained* when the backlog (active requests) at the end of the window is
no larger than at its start and at least 90 % of the requests sent see
their first token within ``--ttft-limit`` seconds with a 95th-percentile
gap under ``--gap-limit`` seconds (a refused or degraded request misses).
The knee is the highest sustained rate; the cell's traffic file then gets
four fifths of it, written in as a number. Not part of any cell's run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import harness, readers, stats  # noqa: E402
from benchmarks.manifest import load_cell, load_plugin  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--ttft-limit", type=float, default=2.0)
    ap.add_argument("--gap-limit", type=float, default=0.25)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0

    cell = load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmarks import device as devmod
    from benchmarks.runners import serve

    device = devmod.describe(cell.chips, args.rehearse)
    if not args.rehearse:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    session = serve.Session(cell, args)
    session.warm()
    session.check_logits()
    compiles = harness.CompileCounter()
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic["params"])
        if args.rehearse:
            traffic.update(cell.traffic.get("rehearse", {}))
        traffic["rate_per_s"] = rate
        traffic.pop("schedule_seed", None)   # the law, not one draw of it
        preroll = float(traffic["preroll_s"])
        source = load_plugin("generators", cell.traffic["generator"]).build(
            traffic, args.seed + i, session.cfg.vocab_size,
            session.engine.max_len, start_s=-preroll, end_s=args.seconds)
        client = session.drive(source, preroll, args.seconds)
        run = harness.RunRecord(
            cell=cell, seconds=args.seconds, chips=1, device=device,
            peaks=None, model=session.cfg, setup_s=0.0, client=client)
        sent = readers.measured(run)
        met = 0
        for r in sent:
            if serve.failed(r) or not r["stamps"]:
                continue
            gaps95 = stats.percentile(stats.gaps(r["stamps"]), 95) or 0.0
            if r["stamps"][0] - r["due"] <= args.ttft_limit \
                    and gaps95 < args.gap_limit:
                met += 1
        ticks = readers.window_ticks(run)
        marks = client["marks"]
        row = {
            "rate_per_s": rate, "sent": len(sent),
            "failed": sum(1 for r in sent if serve.failed(r)),
            "met_both_pct": 100.0 * met / max(1, len(sent)),
            "backlog_open": marks["open"]["active"],
            "backlog_close": marks["close"]["active"],
            "ttft_p50_ms": readers.pct_ms(readers.ttfts_s(run), 50),
            "ttft_p90_ms": readers.pct_ms(readers.ttfts_s(run), 90),
            "itl_p50_ms": readers.pct_ms(readers.window_gaps_s(run), 50),
            "itl_p95_ms": readers.pct_ms(readers.window_gaps_s(run), 95),
            "out_tokens_per_s": readers.window_tokens(run) / args.seconds,
            "ticks": len(ticks),
            "mixed_tick_share_pct": 100.0 * sum(
                1 for t in ticks if t[2] > 0) / max(1, len(ticks)),
            "decode_rows_per_tick": sum(t[3] for t in ticks) / max(1, len(ticks)),
            "tick_p50_ms": readers.pct_ms([t[1] - t[0] for t in ticks], 50),
            "tick_max_ms": readers.pct_ms([t[1] - t[0] for t in ticks], 100),
            "gen_late_p99_ms": readers.pct_ms(readers.lateness_s(run), 99),
            "kv_peak": harness.Telemetry(
                marks["open"]["telemetry"], marks["close"]["telemetry"]
            ).gauge("fastgen_kv_pool_utilization_peak"),
            # programs lowered since the warm-up, all rates so far: a cell
            # at this rate would fail ``correct`` on any
            "compiles_after_warmup": compiles.count,
        }
        row["sustained"] = bool(
            row["backlog_close"] <= row["backlog_open"] * 1.25 + 5
            and row["met_both_pct"] >= 90.0)
        harness.log(json.dumps(row))
        rows.append(row)
    session.accounting(client)
    os.makedirs(os.path.join("chiprun_out", "sweep"), exist_ok=True)
    with open(os.path.join("chiprun_out", "sweep",
                           f"{cell.name}.json"), "w") as f:
        json.dump({"rows": rows, "failures": harness.FAILURES}, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
