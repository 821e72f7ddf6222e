"""Arithmetic the metric readers share, over a run's record. A reader
(``end_to_end/<name>.py``, ``layer_metrics/<name>.py``) is a file with one
function ``read(run) -> float | None``; None (nothing to read: no trace in
this run, no such counter) leaves the metric out of the line.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks import stats
from benchmarks.runners import serve as _serve

# ---------------- serving: the client's log ---------------- #
def measured(run) -> List[Dict[str, Any]]:
    return _serve.measured(run.client)


def ttfts_s(run) -> List[float]:
    """Due instant to first visible token; a failed request counts as the
    largest value seen."""
    rows = measured(run)
    ok = [r["stamps"][0] - r["due"] for r in rows
          if not _serve.failed(r) and r["stamps"]]
    return stats.with_failures(ok, len(rows) - len(ok))


def window_gaps_s(run) -> List[float]:
    """Gaps between consecutive visible tokens of one request, every
    request pooled, whose later token fell inside the window."""
    s = run.client["seconds"]
    out = []
    for r in run.client["requests"].values():
        st = r["stamps"]
        out.extend(b - a for a, b in zip(st, st[1:]) if 0 <= b < s)
    return out


def window_tokens(run) -> int:
    """Tokens that became visible inside the window, of requests that did
    not fail."""
    s = run.client["seconds"]
    return sum(1 for r in run.client["requests"].values()
               if not (r["done_t"] is not None and _serve.failed(r))
               for t in r["stamps"] if 0 <= t < s)


def lateness_s(run) -> List[float]:
    return [r["submit_t"] - r["due"] for r in measured(run)]


def window_ticks(run) -> List[tuple]:
    s = run.client["seconds"]
    return [t for t in run.client["ticks"] if t[0] >= 0 and t[1] <= s]


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def pct_ms(values, q) -> Optional[float]:
    return ms(stats.percentile(values, q))


# ---------------- serving: ticks in the trace ---------------- #
def traced_ticks(run) -> Optional[List[Dict[str, Any]]]:
    """One row per tick that the traced stretch holds whole: the
    ``serving_tick`` span's wall time, the device time of the tick's
    program, and whether the tick held prompt rows (the benchmark's own
    per-tick mark). A run of the program finds its span through the
    ``run_id`` it shares with its enqueue (``gap_chain.ticks``): the clocks
    of host and device differ by a millisecond or two, so the window's edge
    can cut a tick on one clock and not on the other (231 spans against
    230 runs, PERF.md, PR 24), and counting the two would then match
    nothing. None without a trace or where the join found nothing."""
    from benchmarks import gap_chain

    if run.trace is None:
        return None
    gap_chain.analyse(run)
    return run.cache.get("ticks") or None


# ---------------- kernels ---------------- #
def kernel_share_pct(run, kernel: str) -> Optional[float]:
    """Device time of the kernel's calls over the device's busy time."""
    from benchmarks.manifest import load_plugin

    tr = run.trace
    if tr is None or tr.busy_s() <= 0:
        return None
    k = load_plugin("roofline", kernel)
    return 100.0 * tr.op_seconds(lambda o: k.classify(o) is not None) \
        / tr.busy_s()


def kernel_roofline_pct(run, kernel: str) -> Optional[float]:
    """Least time the chip could take for the kernel's calls of the traced
    stretch (``roofline/<kernel>.py`` says what they need; the peaks are
    ``peaks.json``'s) over the time they took."""
    from benchmarks.manifest import load_plugin

    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    k = load_plugin("roofline", kernel)
    calls = [o for c in tr.chips for o in tr.ops_in_window(c)
             if k.classify(o) is not None]
    took = sum(o.seconds for o in calls)
    least = k.least_seconds(run, calls)
    if took <= 0 or least is None:
        return None
    seconds, bound = least
    run.extras.setdefault("roofline_bound", {})[kernel] = bound
    return 100.0 * seconds / took


def traced_tick_log(run) -> List[tuple]:
    """The client's log rows of the ticks of the traced stretch."""
    marks = run.client["marks"]
    return run.client["ticks"][marks.get("trace_from_tick", 0):
                               marks.get("trace_to_tick", 0)]
