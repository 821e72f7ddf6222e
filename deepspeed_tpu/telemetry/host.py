"""What the host does to the process behind a loop's back: pauses of the
garbage collector, and the operating system taking the core away.

Both are the first suspects when a serving tick or a whole run is slow at
the same programs, and both are invisible to spans around the loop's own
phases. Two instruments, on the registry every other one uses:

* **``gc_pause``**, a span like any other: ``gc.callbacks`` opens it when a
  collection starts and closes it when the collection stops, so a pause
  lands in ``span_seconds{span="gc_pause", generation}`` (count and
  seconds), in the profiler's host timeline beside the tick spans (a
  ``TraceAnnotation``: one clock with the device trace) and in the flight
  recorder. A collection can begin at ANY allocation, also one made while
  this thread holds the registry's or the tracer's lock (a scrape copying
  a histogram's children, the tracer opening a request): recording from
  inside the callback would re-enter those sections, and the tracer's lock
  is not re-entrant. So the callback reads the clock, writes the profiler
  annotation and queues the pause; ``refresh()`` hands the queue to the
  histogram and the flight recorder, at the pauses' own instants.
* **``process_context_switches_total{kind}``** and
  **``process_cpu_seconds_total``** (``getrusage(RUSAGE_SELF)``,
  ``time.process_time()``): on a shared host an *involuntary* switch is
  the process losing its core, and CPU seconds short of wall seconds a
  thread that waited. The two calls are 1.2 us (sandbox CPU), carrying
  their differences into three series makes it 4.7: a training step pays
  that every step, a serving tick every sixteenth tick and at every slow
  one.

``refresh()`` is called where a loop's iteration ends (``FastGenEngine``'s
``tick_commit``, the end of ``train_step``), so every pause is in the
registry by then; between two iterations a scrape reads what the last one
left (no collector: that would be one more entry point on the scrape
thread).
"""
from __future__ import annotations

import collections
import gc
import resource
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
from deepspeed_tpu.telemetry import tracing as _tracing
from deepspeed_tpu.telemetry.registry import (
    DEFAULT_REGISTRY,
    MetricsRegistry,
    label_key,
)
from deepspeed_tpu.telemetry.spans import _Annotation

_TRACER = _tracing.get_tracer()
_GC_KEYS = {g: label_key(span="gc_pause", generation=g) for g in range(3)}
_SWITCH_KEYS = (label_key(kind="involuntary"), label_key(kind="voluntary"))
_NO_LABELS = (label_key(),)


class _GcPauses:
    """The ``gc.callbacks`` entry (module docstring). Collections do not
    nest, so one open pause at a time; ``seconds`` is the running sum a
    serving tick differences to say how much of its period was a pause."""

    def __init__(self):
        self.seconds = 0.0
        # (generation, start, end) on time.perf_counter(); bounded, so a
        # process that never refreshes keeps the newest
        self.pending: collections.deque = collections.deque(maxlen=4096)
        self._t0: Optional[float] = None
        self._ann: Any = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            ann = self._ann = _Annotation("gc_pause",
                                          generation=info["generation"])
            ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            self.pending.append((info["generation"], self._t0, t1))
            self.seconds += t1 - self._t0
            self._t0 = None


class _ProcessCounters:
    """The two ``process_*`` counters as differences of what the kernel
    has counted since the last refresh (the first refresh adds the
    process's whole life, as the Prometheus convention has them)."""

    def __init__(self, registry: MetricsRegistry):
        # two loops of one process (a trainer beside a server) may both
        # refresh: a difference taken twice would read negative
        self._lock = make_lock("host._lock")
        self._last = (0, 0, 0.0)            # guarded-by: self._lock
        self._switches = registry.counter(
            "process_context_switches_total",
            "context switches of this process by kind (involuntary: the "
            "scheduler took the core; voluntary: a thread waited)")
        self._cpu = registry.counter(
            "process_cpu_seconds_total",
            "user + system CPU time of this process, every thread")

    def refresh(self) -> None:
        with self._lock:
            ru = resource.getrusage(resource.RUSAGE_SELF)
            now = (ru.ru_nivcsw, ru.ru_nvcsw, time.process_time())
            was, self._last = self._last, now
        self._switches.inc_keys(_SWITCH_KEYS,
                                (now[0] - was[0], now[1] - was[1]))
        self._cpu.inc_keys(_NO_LABELS, (now[2] - was[2],))


_gc_pauses: Optional[_GcPauses] = None
_process: Optional[_ProcessCounters] = None


def install_gc_span() -> None:
    """Put the ``gc_pause`` span into ``gc.callbacks``; once a process,
    however many engines and frontends ask (``reset()`` takes it out)."""
    global _gc_pauses
    if _gc_pauses is None:
        _gc_pauses = _GcPauses()
        gc.callbacks.append(_gc_pauses)


def gc_pause_seconds() -> float:
    """Seconds of collections so far (0 while the span is not installed):
    a running sum for callers that difference it."""
    return _gc_pauses.seconds if _gc_pauses is not None else 0.0


def refresh(process: bool = True) -> None:
    """Bring the registry up to date: the pauses queued since the last
    call into ``span_seconds`` (and the flight recorder) and, unless
    ``process`` is false, the process's counters from the kernel's."""
    global _process
    pauses = _gc_pauses
    if pauses is not None and pauses.pending:
        hist = DEFAULT_REGISTRY.histogram(
            "span_seconds", "wall time of telemetry.span sections")
        while pauses.pending:
            generation, t0, t1 = pauses.pending.popleft()
            hist.observe_key(_GC_KEYS[generation], t1 - t0)
            if _TRACER.enabled:
                _TRACER.record_span("gc_pause", t1 - t0, end=t1,
                                    generation=generation)
    if not process:
        return
    if _process is None:
        _process = _ProcessCounters(DEFAULT_REGISTRY)
    _process.refresh()


def reset() -> None:
    """Tests only (``telemetry.reset()``): take the callback out of
    ``gc.callbacks``, which is then as it was found."""
    global _gc_pauses
    if _gc_pauses is not None:
        if _gc_pauses in gc.callbacks:
            gc.callbacks.remove(_gc_pauses)
        _gc_pauses = None
