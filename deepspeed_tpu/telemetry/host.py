"""What the host does to the process behind a loop's back: pauses of the
garbage collector, the operating system taking the core away, and JAX
tracing, lowering, loading and compiling programs.

The first two are the first suspects when a serving tick or a whole run is
slow at the same programs, the third is most of what set-up is made of
and the cause of a step or a tick that takes seconds where it took
milliseconds; all are invisible to spans around the loop's own phases.
Three instruments, on the registry every other one uses:

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

* **the compile account** (``install_compile_account()``): listeners on
  ``jax.monitoring``, which JAX fires around every jaxpr trace, every
  lowering to MLIR and every backend compile with the function's name,
  and at every hit of the persistent compilation cache. They feed
  ``xla_program_seconds_total{program, phase}`` and
  ``xla_program_events_total{program, phase}``, ``phase`` one of ``trace``,
  ``lower``, ``load`` (a backend-compile interval in which the persistent
  cache supplied the executable: ``/jax/compilation_cache/cache_hits``
  fired on its thread between its start and its end) and ``compile`` (one
  in which it did not, or there was no cache key; a program that holds a
  host callback is never written to the cache, so it reads ``compile`` in
  every process: ``jax/_src/compiler.py``, ``_cache_write``). ``program``
  is JAX's ``fun_name`` (the trace event says ``f`` and the other two
  ``jit(f)``: one label, ``f``); an event shorter than
  ``SMALL_PROGRAM_S`` goes to ``program="other"``, so the label set stays
  a few dozen names. Events nest (a step's trace holds its inner ``jit``s'
  traces, a lowering may trace): a series holds each event's seconds
  WITHOUT those of the events inside it, so the sum over every series is
  wall time spent under JAX's compile path, once. Also
  ``xla_cache_seconds_saved_total`` (JAX's ``compile_time_saved_sec``: what
  the cache was worth), each backend-compile interval as a span
  ``xla_compile`` (``program``, ``phase``) in the flight recorder, and
  ``compile_seconds()``, the running sum a serving tick and a training
  step difference to say "this one compiled". The counters are written
  from inside the listener (they fire on JAX's compile path alone, never
  under this package's locks, and the registry's lock is re-entrant), so a
  reader never sees the account behind the programs that ran;
  the flight recorder's lock is not re-entrant, so the spans are queued
  like the pauses. Nothing is paid between compiles.

``refresh()`` is called where a loop's iteration ends (``FastGenEngine``'s
``tick_commit``, the end of ``train_step``), so every pause is in the
registry by then; between two iterations a scrape reads what the last one
left (no collector: that would be one more entry point on the scrape
thread).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import re
import resource
import threading
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
from deepspeed_tpu.telemetry import tracing as _tracing
from deepspeed_tpu.telemetry.registry import (
    DEFAULT_REGISTRY,
    MetricsRegistry,
    label_key,
)
from deepspeed_tpu.telemetry.spans import _Annotation, span

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


#: an event shorter than this is accounted under ``program="other"``
SMALL_PROGRAM_S = 0.05
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_OTHER_KEYS = {phase: (label_key(program="other", phase=phase),)
               for phase in ("trace", "lower", "load", "compile")}


class _CompileAccount:
    """The ``jax.monitoring`` listeners (module docstring). JAX announces
    an interval's start with ``record_scalar(event, start, fun_name=)`` and
    its end with ``record_event_duration_secs(event, seconds, fun_name=)``,
    on the thread that does the work: a stack a thread says what an event
    is nested in and whether the cache was hit inside it."""

    def __init__(self, registry: MetricsRegistry):
        self._lock = make_lock("host._compile_lock")
        self.seconds = 0.0                  # guarded-by: self._lock
        # (program, phase, seconds, end) of backend-compile intervals, for
        # the flight recorder; bounded like the pauses
        self.pending: collections.deque = collections.deque(maxlen=4096)
        self._open = threading.local()
        self._seconds = registry.counter(
            "xla_program_seconds_total",
            "wall seconds under JAX's compile path by program (jit's "
            "fun_name; 'other': events under 50 ms) and phase (trace, "
            "lower, load: executable from the persistent cache, compile), "
            "nested events' seconds taken out")
        self._events = registry.counter(
            "xla_program_events_total",
            "traces, lowerings, cache loads and compilations by program "
            "and phase; rising after warm-up: something recompiles")
        self._saved = registry.counter(
            "xla_cache_seconds_saved_total",
            "compile seconds the persistent cache's hits stood for, less "
            "their retrieval (JAX's compile_time_saved_sec)")

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    # record_scalar: an interval opens
    def on_scalar(self, event: str, _value, **_kw) -> None:
        if event in _PHASE_OF_EVENT:
            # [event, seconds of the events closed inside it, cache hit]
            self._stack().append([event, 0.0, False])

    # record_event: the persistent cache supplied an executable
    def on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            for frame in reversed(self._stack()):
                if _PHASE_OF_EVENT[frame[0]] == "compile":
                    frame[2] = True
                    break

    # record_event_duration_secs: an interval closes
    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == _CACHE_SAVED_EVENT:
            self._saved.inc(max(0.0, seconds))
            return
        phase = _PHASE_OF_EVENT.get(event)
        if phase is None:
            return
        end = time.perf_counter()
        stack, inside, hit = self._stack(), 0.0, False
        # this interval's frame is the top one, unless an interval inside
        # it never announced its end (JAX skips them while the interpreter
        # exits); one that opened before the listeners has no frame
        at = next((i for i in range(len(stack) - 1, -1, -1)
                   if stack[i][0] == event), None)
        if at is not None:
            _, inside, hit = stack[at]
            del stack[at:]
        if stack:
            stack[-1][1] += seconds
        if phase == "compile" and hit:
            phase = "load"
        own = max(0.0, seconds - inside)
        if seconds < SMALL_PROGRAM_S:
            # thousands of these a set-up (every inner jit of every
            # program's trace): a key made once, no name read
            program, key = "other", _OTHER_KEYS[phase]
        else:
            name = str(kw.get("fun_name", "?"))
            wrapped = _WRAPPED.match(name)
            program = wrapped.group(1) if wrapped else name
            key = (label_key(program=program, phase=phase),)
        self._seconds.inc_keys(key, (own,))
        self._events.inc_keys(key, (1.0,))
        with self._lock:
            self.seconds += own
        if phase in ("load", "compile"):
            self.pending.append((program, phase, seconds, end))


_gc_pauses: Optional[_GcPauses] = None
_process: Optional[_ProcessCounters] = None
_compiles: Optional[_CompileAccount] = None


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


def install_compile_account() -> None:
    """Register the compile account's listeners with ``jax.monitoring``;
    once a process, however many engines ask (``reset()`` takes them
    out). Both engines call it where they place the compile cache, ahead
    of everything they compile."""
    global _compiles
    if _compiles is None:
        from jax import monitoring

        _compiles = acct = _CompileAccount(DEFAULT_REGISTRY)
        monitoring.register_scalar_listener(acct.on_scalar)
        monitoring.register_event_listener(acct.on_event)
        monitoring.register_event_duration_secs_listener(acct.on_duration)


@contextlib.contextmanager
def engine_init():
    """Around an engine's constructor (both engines; as a decorator of
    ``__init__``: a fresh one a call): the compile account
    installed ahead of everything the engine compiles, the span
    ``engine_init`` (its parts, where a constructor has them, are spans
    inside it: ``device_attach``, ``params_init``, ``state_init``), and
    ``engine_init_compile_seconds_total``: the seconds of the span that
    lay under JAX's compile path, which ``xla_program_seconds_total`` holds
    by program, so that a reader can tell the constructor's own seconds
    from what it compiled."""
    install_compile_account()
    under = -compile_seconds()
    try:
        with span("engine_init"):
            yield
    finally:
        DEFAULT_REGISTRY.counter(
            "engine_init_compile_seconds_total",
            "seconds of engine_init spans under JAX's compile path (they "
            "are in xla_program_seconds_total too)").inc(
                max(0.0, under + compile_seconds()))


def compile_seconds() -> float:
    """Seconds under JAX's compile path so far, every phase (0 while the
    account is not installed): a running sum for callers that difference
    it around a step or a tick."""
    return _compiles.seconds if _compiles is not None else 0.0


def refresh(process: bool = True) -> None:
    """Bring the registry up to date: the pauses queued since the last
    call into ``span_seconds`` (and the flight recorder), the backend
    compiles queued since then into the flight recorder (``xla_compile``)
    and, unless ``process`` is false, the process's counters from the
    kernel's."""
    global _process
    compiles = _compiles
    if compiles is not None and compiles.pending:
        while compiles.pending:
            program, phase, seconds, end = compiles.pending.popleft()
            if _TRACER.enabled:
                _TRACER.record_span("xla_compile", seconds, end=end,
                                    program=program, phase=phase)
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
    ``gc.callbacks`` and the compile account's listeners out of
    ``jax.monitoring``, which are then as they were found."""
    global _gc_pauses, _compiles
    if _gc_pauses is not None:
        if _gc_pauses in gc.callbacks:
            gc.callbacks.remove(_gc_pauses)
        _gc_pauses = None
    if _compiles is not None:
        from jax import monitoring

        # (bound methods compare equal by the instance they are bound to)
        monitoring.unregister_scalar_listener(_compiles.on_scalar)
        monitoring.unregister_event_listener(_compiles.on_event)
        monitoring.unregister_event_duration_listener(_compiles.on_duration)
        _compiles = None
