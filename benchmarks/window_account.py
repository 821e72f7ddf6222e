"""The measured window's own account: where every serving tick's period
went, by the program's own clock, read from the difference of the
program's registry between the window's opening and its close
(``run.telemetry``: the untraced window, also of a ``--trace 1`` run).

What the program keeps (``inference/fastgen.py::_account_tick``,
``serving/frontend.py::run_tick``, ``telemetry/host.py``):

* ``fastgen_tick_period_seconds{kind, bucket}``: end of one tick's
  ``tick_commit`` to the end of the next one's (after a stretch without a
  live sequence: from the tick's own ``schedule_tick``; the stretch is
  ``fastgen_engine_idle_seconds_total``), so window = periods + idle;
* ``fastgen_tick_phase_seconds_total{phase, kind}``: six consecutive
  phases of a tick, ``schedule_tick`` entry to ``tick_commit`` exit;
* ``serving_loop_seconds_total{part}``: ``tick`` (``run_tick`` entry to
  return) and ``caller`` (return to the next entry while a request is
  active: here the load generator), so periods = caller + tick and
  tick - phases = the frontend's own share;
* ``fastgen_slow_ticks_total`` / ``fastgen_slow_tick_excess_seconds_total``
  ``{phase, kind}`` and ``FastGenEngine.slow_ticks``: ticks over 1.25 x
  their program's typical period, under the part that grew most;
* ``span_seconds{span="gc_pause"}``, ``process_context_switches_total``.

``metric(run, name)`` gives one number a tick or a second of the window;
the whole table goes to ``run.extras["window_account"]``. Everything is
None on a program without the period histogram (nothing to read).
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from benchmarks import readers

PHASES = ("schedule", "pack", "dispatch", "overlap", "readback", "commit")
PERIODS = "fastgen_tick_period_seconds"


def _has_child(tel, group: str, name: str, **labels) -> bool:
    """Whether the program's registry holds a series of ``name`` carrying
    ``labels`` at the window's close (a difference alone cannot tell a
    counter that stood still from one that is not there)."""
    series = tel.end[group].get(name)
    if series is None:
        return False
    keys = series["children"] if group == "histograms" else series
    return any(labels.items() <= dict(k).items() for k in keys)


def _total_at(snap: Dict[str, Any], name: str) -> float:
    return sum(snap["counters"].get(name, {}).values())


def _slow_ticks_of(lo: float, hi: float) -> Optional[List[Dict[str, Any]]]:
    """The engine's slow-tick records with ``lo < tick <= hi``; None on a
    program that keeps none."""
    from deepspeed_tpu.inference.fastgen import FastGenEngine

    kept = getattr(FastGenEngine, "slow_ticks", None)
    if kept is None:
        return None
    return [dict(r) for r in kept if lo < r["tick"] <= hi]


def _traced_slow_ticks(run, first_tick: float) -> List[Dict[str, Any]]:
    """Each slow tick of the traced tail beside the device time of its
    program's run and the tail's median for ticks of its kind
    (``readers.traced_ticks``: joined to the device by ``run_id``). The
    tail's k-th whole tick is the engine's tick ``first_tick + k`` when
    the stretch cut no tick and every loop iteration ran one: otherwise
    nothing is said."""
    rows = readers.traced_ticks(run)
    log = readers.traced_tick_log(run)
    if not rows or len(rows) != len(log) or any(
            r["mixed"] != (t[2] > 0) for r, t in zip(rows, log)):
        return []
    slow = _slow_ticks_of(first_tick - 1, first_tick - 1 + len(rows)) or []
    out = []
    for rec in slow:
        row = rows[int(rec["tick"] - first_tick)]
        peers = [r["device"] for r in rows if r["mixed"] == row["mixed"]]
        out.append({"tick": rec["tick"], "kind": rec["kind"],
                    "phase": rec["phase"],
                    "period_ms": 1e3 * rec["period_s"],
                    "typical_period_ms": 1e3 * rec["typical_period_s"],
                    "device_ms": 1e3 * row["device"],
                    "tail_median_device_ms": 1e3 * statistics.median(peers),
                    "wall_ms": 1e3 * row["wall"]})
    return out


def analyse(run) -> Optional[Dict[str, Any]]:
    """The table, made once and kept in ``run.extras["window_account"]``;
    None where the program has no period histogram."""
    if "window_account" in run.extras:
        return run.extras["window_account"]
    tel = run.telemetry
    if tel is None or not _has_child(tel, "histograms", PERIODS):
        return None
    _, _, ticks, periods_s = tel.histogram(PERIODS)
    if ticks <= 0:
        return None
    marks = run.client.get("marks", {})
    window_s = marks["close"]["t"] - marks["open"]["t"] \
        if "open" in marks and "close" in marks else run.seconds

    programs = []
    for key in sorted(tel.end["histograms"][PERIODS]["children"]):
        labels = dict(key)
        _, _, n, total = tel.histogram(PERIODS, **labels)
        if n > 0:
            programs.append({
                **labels, "ticks": n, "mean_ms": 1e3 * total / n,
                "p50_ms": readers.ms(tel.quantile(PERIODS, 0.5, **labels)),
                "p99_ms": readers.ms(tel.quantile(PERIODS, 0.99, **labels))})
    by_kind = {}
    for kind in ("decode", "mixed"):
        _, _, n, total = tel.histogram(PERIODS, kind=kind)
        by_kind[kind] = {"ticks": n,
                         "mean_ms": 1e3 * total / n if n else None}

    phase_s = {p: tel.counter("fastgen_tick_phase_seconds_total", phase=p)
               for p in PHASES}
    engine_s = sum(phase_s.values())
    loop_tick_s = tel.counter("serving_loop_seconds_total", part="tick")
    caller_s = tel.counter("serving_loop_seconds_total", part="caller")
    idle_s = tel.counter("fastgen_engine_idle_seconds_total")
    per_tick = lambda s: 1e3 * s / ticks   # noqa: E731

    excess = "fastgen_slow_tick_excess_seconds_total"
    device_excess_s = tel.counter(excess, phase="readback")
    host_excess_s = tel.counter(excess) - device_excess_s
    metrics = {
        "win_ticks_per_s": ticks / window_s,
        "win_period_decode_ms": by_kind["decode"]["mean_ms"],
        "win_period_mixed_ms": by_kind["mixed"]["mean_ms"],
        "win_readback_ms": per_tick(phase_s["readback"]),
        "win_engine_host_ms": per_tick(engine_s - phase_s["readback"]),
        "win_frontend_ms": per_tick(loop_tick_s - engine_s),
        "win_caller_ms": per_tick(caller_s),
        "slow_excess_device_pct": 100.0 * device_excess_s / window_s,
        "slow_excess_host_pct": 100.0 * host_excess_s / window_s,
    }
    if _has_child(tel, "histograms", "span_seconds", span="gc_pause"):
        _, _, pauses, pause_s = tel.histogram("span_seconds",
                                              span="gc_pause")
        metrics["gc_pause_ms_per_s"] = 1e3 * pause_s / window_s
    else:
        pauses = None
    switches = "process_context_switches_total"
    if _has_child(tel, "counters", switches, kind="involuntary"):
        metrics["host_preempts_per_s"] = tel.counter(
            switches, kind="involuntary") / window_s

    parts_ms = sum(metrics[m] for m in (
        "win_readback_ms", "win_engine_host_ms", "win_frontend_ms",
        "win_caller_ms"))
    ticks_open = _total_at(tel.start, "fastgen_ticks_total")
    ticks_close = _total_at(tel.end, "fastgen_ticks_total")
    slow = _slow_ticks_of(ticks_open, ticks_close)
    out = {
        "metrics": metrics, "window_s": window_s, "ticks": ticks,
        "out_tokens_per_s": tel.counter(
            "fastgen_generated_tokens_total") / window_s,
        "programs": programs,
        "phase_ms_per_tick": {p: per_tick(s) for p, s in phase_s.items()},
        "engine_idle_s": idle_s,
        # window = periods + idle: what is left over, a tick (the two
        # snapshots lie between ticks, so only the caller's share of the
        # first and last period is cut)
        "identity_remainder_ms_per_tick": per_tick(
            window_s - periods_s - idle_s),
        # readback + engine host + frontend + caller against the mean
        # period (they differ by the frontend's share of a tick that
        # follows an idle stretch: its period starts at schedule_tick)
        "parts_minus_period_ms_per_tick": parts_ms - per_tick(periods_s),
        "gc_pauses": pauses,
        "cpu_s_per_s": tel.counter("process_cpu_seconds_total") / window_s,
        "voluntary_switches_per_s": tel.counter(
            switches, kind="voluntary") / window_s,
        "slow_ticks_counted": tel.counter("fastgen_slow_ticks_total"),
        "slow_ticks": slow,
    }
    if run.trace is not None and "close" in marks and ticks_close \
            - ticks_open == marks["close"]["ticks"] - marks["open"]["ticks"]:
        out["slow_ticks_traced"] = _traced_slow_ticks(run, ticks_close + 1)
    run.extras["window_account"] = out
    return out


def metric(run, name: str) -> Optional[float]:
    table = analyse(run)
    return None if table is None else table["metrics"].get(name)
