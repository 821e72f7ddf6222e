"""Set-up's own account: what the process spent, from its start to the
window's opening, under JAX's compile path (by program and phase) and in
the engine's constructor, read from the program's registry AS IT STOOD AT
THE WINDOW'S OPENING (``run.telemetry.start``: the registry is cumulative
from process start, so the opening snapshot IS set-up).

What the program keeps (``telemetry/host.py``, both engines' constructors):

* ``xla_program_seconds_total{program, phase}`` and
  ``xla_program_events_total{program, phase}``: ``phase`` one of ``trace``
  (jaxpr trace), ``lower`` (to MLIR), ``load`` (the persistent cache
  supplied the executable), ``compile`` (it did not); ``program`` is
  ``jit``'s function name, ``other`` every event under 50 ms. Seconds are
  an event's own (nested events' taken out), so their sum is wall time;
* ``span_seconds{span="engine_init"}`` and, inside it, ``device_attach``,
  ``params_init``, ``state_init`` (where a constructor has the part), and
  ``engine_init_compile_seconds_total``: the seconds of ``engine_init``
  that lay under the compile path and are in the account above already;
* ``xla_cache_seconds_saved_total``.

The five metrics are the table's sums; ``unaccounted_s`` is ``setup_s`` less
the pre-roll (serving) and the four sums of seconds: the benchmark's own
share (the weights it draws, warm-up's runs, the reference comparison) and
the runtime's start before any engine exists (``benchmarks/device.py``
reaches the device first, so ``device_attach`` reads ~0 here; an
operator's process pays it in the span). ``setup_engine_init_s`` is the
constructor's seconds WITHOUT what it spent under the compile path, so that
the four sums are disjoint. A traced training run calls
``engine.collective_ledger()`` before the window, which lowers and compiles
the step again: the step's second event is the traced run's alone.

Everything is None on a program without the account (nothing to read).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

SECONDS = "xla_program_seconds_total"
EVENTS = "xla_program_events_total"
PARTS = ("device_attach", "params_init", "state_init")


def _span_s(snap: Dict[str, Any], name: str) -> Optional[float]:
    """Seconds of ``span_seconds{span=name}``, every other label summed;
    None where the program recorded no such span."""
    hist = snap["histograms"].get("span_seconds")
    found = [c for key, c in (hist or {"children": {}})["children"].items()
             if dict(key).get("span") == name]
    return sum(c[2] for c in found) if found else None


def analyse(run) -> Optional[Dict[str, Any]]:
    """The table, made once and kept in ``run.extras["setup_account"]``;
    None where the program keeps no compile account."""
    if "setup_account" in run.extras:
        return run.extras["setup_account"]
    tel = run.telemetry
    if tel is None or SECONDS not in tel.start["counters"]:
        return None
    counters = tel.start["counters"]
    programs: Dict[str, Dict[str, Dict[str, float]]] = {}
    by_phase = {p: 0.0 for p in ("trace", "lower", "load", "compile")}
    lowered = 0.0
    for key, seconds in counters[SECONDS].items():
        labels = dict(key)
        events = counters.get(EVENTS, {}).get(key, 0.0)
        programs.setdefault(labels["program"], {})[labels["phase"]] = {
            "events": events, "seconds": seconds}
        by_phase[labels["phase"]] += seconds
        if labels["phase"] == "lower":
            lowered += events
    init_s = _span_s(tel.start, "engine_init")
    init_compile_s = sum(counters.get(
        "engine_init_compile_seconds_total", {}).values())
    traffic = dict(run.cell.traffic["params"])
    if run.peaks is None:                       # a rehearsal's own mix
        traffic.update(run.cell.traffic.get("rehearse", {}))
    preroll_s = float(traffic.get("preroll_s", 0.0))
    metrics = {
        "setup_engine_init_s": None if init_s is None
        else max(0.0, init_s - init_compile_s),
        "setup_trace_lower_s": by_phase["trace"] + by_phase["lower"],
        "setup_cache_load_s": by_phase["load"],
        "setup_compile_s": by_phase["compile"],
        "setup_programs": lowered,
    }
    out = {
        "metrics": metrics, "setup_s": run.setup_s, "preroll_s": preroll_s,
        "seconds_by_phase": by_phase,
        # by the compile path's seconds, largest first
        "programs": dict(sorted(
            programs.items(),
            key=lambda kv: -sum(p["seconds"] for p in kv[1].values()))),
        "engine_init": {
            "span_s": init_s, "under_compile_path_s": init_compile_s,
            **{part: _span_s(tel.start, part) for part in PARTS}},
        "cache_seconds_saved": sum(counters.get(
            "xla_cache_seconds_saved_total", {}).values()),
        # the benchmark's own share, and the runtime's start ahead of the
        # first engine
        "unaccounted_s": run.setup_s - preroll_s - sum(
            metrics[m] or 0.0 for m in (
                "setup_engine_init_s", "setup_trace_lower_s",
                "setup_cache_load_s", "setup_compile_s")),
    }
    run.extras["setup_account"] = out
    return out


def metric(run, name: str) -> Optional[float]:
    table = analyse(run)
    return None if table is None else table["metrics"].get(name)
