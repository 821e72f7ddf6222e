"""Span-based tracing + the stall watchdog.

``span("decode_tick")`` does four things at once:

* records the span's wall time into the ``span_seconds{span=...}`` histogram
  of the active registry (host-visible latency, scrapeable);
* emits a ``jax.profiler.TraceAnnotation`` so the span brackets the ops it
  dispatched in an XLA device trace (the compute/collective-overlap view
  that T3-style analyses need — a captured ``jax.profiler.trace`` shows
  these names on the host timeline aligned with device streams);
* feeds the structured tracer (``telemetry/tracing.py``) when tracing is
  enabled, so every already-instrumented site lands in the flight
  recorder's timeline for free (disabled: one attribute check);
* notes itself as the registry's *last completed span*, which is what the
  stall watchdog reports when a training step misses its deadline.

NOTE on async dispatch: the host wall time of a span that only *dispatches*
work is not device time. Spans measure what the host observed — for fenced
device timings use ``utils/timer.py``'s fenced timers (which also feed the
``train_phase_seconds`` histogram) or a profiler trace.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
from deepspeed_tpu.testing.chaos import sync_point
from deepspeed_tpu.telemetry import tracing as _tracing
from deepspeed_tpu.telemetry.registry import (
    DEFAULT_REGISTRY,
    MetricsRegistry,
    _label_key,
)


class _NoAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` where jax cannot be
    imported (the registry is dependency-free and must stay usable without
    a device runtime, e.g. from the HTTP scrape thread)."""

    __slots__ = ()

    def __init__(self, name: str, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


try:   # resolved ONCE: a per-call import costs more than the annotation
    from jax.profiler import TraceAnnotation as _Annotation
# a span must NEVER raise into the section it brackets, whatever the
# profiler backend is doing  # dslint: disable=silent-except
except Exception:
    _Annotation = _NoAnnotation

#: the default tracer: configured in place, never replaced
_TRACER = _tracing.get_tracer()


class span:
    """``with span(name, attrs={...}, **labels)``: one timed section
    (module docstring), recorded in ``registry`` (the process-wide one
    unless given). ``labels`` are LOW-cardinality: they key
    the ``span_seconds`` histogram beside ``span=name``. ``attrs`` are
    per-occurrence values (a uid, a tick count, a row count): they go to
    the ``TraceAnnotation`` as keyword arguments (stats of the host event
    in a profiler trace) and to the ``Tracer`` record when the flight
    recorder is on, and never reach a histogram.

    A slotted class, not a generator context manager; the histogram is
    held by the registry and the annotation class and the tracer by this
    module, so nothing is looked up by name on a call: a tick pays for
    eight of these.

    ``t0`` / ``t1`` are the span's own two readings of
    ``time.perf_counter()``, kept so that an account of consecutive
    sections (a serving tick's phases) reads no clock of its own: the
    boundary between two phases is one reading, used once."""

    __slots__ = ("_name", "_registry", "_key", "_attrs", "_labels",
                 "_ann", "_rec", "t0", "t1")

    def __init__(self, name: str,
                 registry: MetricsRegistry = DEFAULT_REGISTRY,
                 attrs: Optional[Dict[str, Any]] = None, **labels):
        self._name = name
        self._registry = registry
        self._attrs = attrs
        self._labels = labels
        # the histogram's key, as ``_label_key`` would build it
        self._key = _label_key({"span": name, **labels}) if labels \
            else (("span", name),)

    def __enter__(self):
        attrs = self._attrs
        ann = self._ann = _Annotation(self._name, **attrs) if attrs \
            else _Annotation(self._name)
        ann.__enter__()
        if _TRACER.enabled:
            rec = self._rec = _TRACER.span(self._name, **self._labels,
                                           **(attrs or {}))
            rec.__enter__()
        else:
            self._rec = None
        self.t0 = time.perf_counter()
        return self

    def note(self, **attrs) -> None:
        """Attributes learned inside the span (a tick's expert counts come
        back with its tokens): they reach the ``Tracer`` record; the
        profiler's annotation was written at entry and stays as it is."""
        if self._rec is not None and self._rec.rec is not None:
            self._rec.rec.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = self.t1 = time.perf_counter()
        if self._rec is not None:
            self._rec.__exit__(exc_type, exc, tb)
        self._ann.__exit__(exc_type, exc, tb)
        self._registry.observe_span(self._key, self._name, t1 - self.t0)
        return False


class StallWatchdog:
    """Logs a warning when no heartbeat lands within ``deadline_s``.

    The engine beats (``beat()``) once per completed optimizer step/window;
    a daemon thread checks at deadline/4 cadence and warns ONCE per stall
    episode, naming the last completed span — the first question anyone asks
    a wedged run is "what was it doing last". Recovery re-arms the warning.
    A ``telemetry_stalls_total`` counter makes stall history scrapeable.

    ``on_stall``: optional escalation callback fired (once per stall
    episode, on the watchdog thread) after the warning — the training
    engine hooks its emergency-checkpoint path here when
    ``fault_tolerance.on_stall == "checkpoint"``, turning detection into
    response. A raising callback is counted
    (``telemetry_stall_action_errors_total``) and never kills the thread.

    Clocks + threading: deadlines are measured on ``time.monotonic()`` —
    the wall clock steps under NTP slew and VM suspend/resume, and a 30s
    correction must not fake (or mask) a stall. ``beat()`` runs on the
    training thread while ``check()`` runs on the watchdog thread, so the
    beat/armed/stalled triple is updated under a small lock; the
    ``on_stall`` callback and all logging run OUTSIDE it (an emergency
    checkpoint must not block the training thread's next ``beat()``).

    The deadline ARMS at the first beat: the watchdog monitors steady-state
    training, and the first step's XLA compile routinely exceeds any sane
    step deadline — firing during legitimate compilation would put a false
    stall in every large-model run's metrics. (The cost: a run that never
    completes step 1 is not flagged — that failure mode presents as an
    obvious hang, not a mid-run stall.)
    """

    def __init__(self, deadline_s: float, registry: MetricsRegistry,
                 name: str = "train", logger=None, on_stall=None):
        if deadline_s <= 0:
            raise ValueError("StallWatchdog needs a positive deadline")
        self.deadline_s = float(deadline_s)
        self.registry = registry
        self.name = name
        if logger is None:
            from deepspeed_tpu.utils.logging import logger as _l

            logger = _l
        self.logger = logger
        self.on_stall = on_stall
        self._lock = make_lock("watchdog._lock")
        self._last_beat = time.monotonic()  # guarded-by: self._lock
        self._armed = False                 # guarded-by: self._lock
        self._stalled = False               # guarded-by: self._lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._stall_counter = registry.counter(
            "telemetry_stalls_total",
            "watchdog deadline misses (no step completed in time)")

    def beat(self) -> None:
        with self._lock:
            self._last_beat = time.monotonic()
            self._armed = True
            recovered, self._stalled = self._stalled, False
        if recovered:
            self.logger.warning(
                f"[watchdog:{self.name}] recovered — a step completed after "
                "the stall warning")

    def start(self) -> "StallWatchdog":
        if self._thread is None:
            # restartable: a prior stop() left the event set, and a new
            # thread would otherwise exit its wait-loop immediately
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name=f"telemetry-watchdog-{self.name}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        """Idempotent (thread popped before the join, so stacked
        teardown paths can't double-join); join-with-timeout; no lock
        held across the join (stop never takes self._lock)."""
        self._stop.set()
        thread, self._thread = self._thread, None
        sync_point("watchdog/stop/pre_join")
        if thread is not None:
            thread.join(timeout=2.0)

    def check(self, now: Optional[float] = None) -> bool:
        """One deadline check (the thread's body; callable directly in
        tests — ``now`` is a ``time.monotonic()`` reading). Returns True
        when a stall was (newly) reported."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if not self._armed or self._stalled \
                    or now - self._last_beat <= self.deadline_s:
                return False
            self._stalled = True
            last_beat = self._last_beat
        self._stall_counter.inc()
        last = self.registry.last_span
        where = (f"last completed span: {last[0]!r} "
                 f"{now - last[1]:.1f}s ago" if last
                 else "no span completed yet")
        self.logger.warning(
            f"[watchdog:{self.name}] no step finished in "
            f"{now - last_beat:.1f}s (deadline {self.deadline_s:.1f}s) "
            f"— {where}")
        if self.on_stall is not None:
            try:
                self.on_stall()
            except Exception as e:
                self.registry.counter(
                    "telemetry_stall_action_errors_total",
                    "on_stall escalation callbacks that raised"
                ).inc(error=type(e).__name__)
                self.logger.warning(
                    f"[watchdog:{self.name}] on_stall action failed: {e}")
        return True

    def _run(self) -> None:
        interval = max(self.deadline_s / 4.0, 0.05)
        while not self._stop.wait(interval):
            self.check()
