"""What the program said of each tick of a traced stretch, beside the
device interval of the tick's run: the rows a roofline file takes a tick's
need from, so that need and time come from the same ticks.

The engine writes what a tick's attention has to compute on its
``decode_tick`` span (``prompt_attended``: cache positions its prompt rows
attend to) and what came back with its tokens on the ``tick_commit`` span
that follows (``experts_active``: experts with a row, summed over the
expert layers; both carry the engine's ``tick`` number). A span's
attributes are the stats of its host event in the profiler's file. A run
of the tick program finds its span as ``gap_chain`` does: through the
``run_id`` it shares with its enqueue, which lies inside the span. The
runner's tick log (``blocks``: cache blocks of the sequences with rows)
is joined by order: the profiler starts between two ticks of one thread,
so the first ``decode_tick`` event of the file is the log's row
``trace_from_tick``; a row whose prompt rows differ from the span's ends
the join. A program without the attributes (the parent's) gives no rows.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Tuple

MODULE = "jit_tick"


def events(path: str, chip: int):
    """(runs [(start, end, run_id)], enqueue {run_id: start}, ticks
    [(start, end, stats)] in time order, commits {tick: stats})."""
    import jax

    from benchmarks import gap_chain

    runs, enqueue, ticks, commits = [], {}, [], {}

    def lo(e) -> float:
        return e.start_ns * 1e-9

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == f"/device:TPU:{chip}":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs += [(lo(e), lo(e) + e.duration_ns * 1e-9,
                              dict(e.stats).get("run_id"))
                             for e in line.events
                             if e.name.startswith(MODULE)]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "decode_tick":
                        ticks.append((lo(e), lo(e) + e.duration_ns * 1e-9,
                                      dict(e.stats)))
                    elif e.name == "tick_commit":
                        stats = dict(e.stats)
                        if "tick" in stats:
                            commits[stats["tick"]] = stats
                    elif e.name == gap_chain.ENQUEUE:
                        stats = dict(e.stats)
                        if stats.get("device_ordinal", 0) == chip:
                            enqueue[stats.get("run_id")] = lo(e)
    ticks.sort(key=lambda t: t[0])
    return runs, enqueue, ticks, commits


def join(runs, enqueue, ticks, commits, log: List[tuple],
         window: Tuple[float, float]) -> List[Dict[str, Any]]:
    """One row per run that the window holds whole and whose span was
    found: ``start``/``end`` (the run's, on the device's clock), the
    span's attributes, the commit's, and ``blocks`` from the log row of
    the same place in the stretch."""
    starts = [t[0] for t in ticks]
    rows = []
    for a, b, run_id in sorted(runs):
        at = enqueue.get(run_id)
        if a < window[0] or b > window[1] or at is None:
            continue
        i = bisect.bisect_right(starts, at) - 1
        if i < 0 or ticks[i][1] < at or i >= len(log):
            continue
        stats = ticks[i][2]
        if "prompt_attended" not in stats \
                or stats.get("prefill_tokens") != log[i][2]:
            continue
        rows.append({"start": a, "end": b, "blocks": log[i][4], **stats,
                     **commits.get(stats.get("tick"), {})})
    return rows


def per_tick(run) -> List[Dict[str, Any]]:
    """``join`` over the run's trace file, made once a run."""
    from benchmarks import gap_chain, readers

    if "tick_attrs" not in run.cache:
        run.cache["tick_attrs"] = []
        path = gap_chain.trace_file(run) if run.trace is not None else None
        if path is not None and run.trace.chips:
            run.cache["tick_attrs"] = join(
                *events(path, run.trace.chips[0]),
                readers.traced_tick_log(run), run.trace.window)
            run.extras["tick_attrs"] = {
                "ticks_joined": len(run.cache["tick_attrs"])}
    return run.cache["tick_attrs"]


def calls_by_tick(rows: List[Dict[str, Any]], calls: List
                  ) -> List[Tuple[Dict[str, Any], List]]:
    """Each row with the calls that started inside its run. A call of a
    tick the stretch cut, or whose span was not found, belongs to no row:
    its time still counts in the kernel's total, so the share of the need
    met can only read lower for it."""
    calls = sorted(calls, key=lambda o: o.start)
    starts = [o.start for o in calls]
    return [(r, calls[bisect.bisect_left(starts, r["start"]):
                      bisect.bisect_right(starts, r["end"])])
            for r in rows]
