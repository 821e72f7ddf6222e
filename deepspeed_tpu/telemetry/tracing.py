"""Structured tracing + the flight recorder.

Aggregates (the metrics registry) answer "how slow on average"; this
module answers "why was THIS step/request slow" and "what was the loop
doing in the seconds before it died". Three pieces:

* **Tracer** — in-process structured spans: trace/span ids with parent
  links, monotonic durations, key/value attributes and point-in-time
  events. Span timing is ``time.perf_counter()`` throughout; ONE
  wall-clock anchor captured at tracer (re)configuration converts
  monotonic readings into real timestamps at export time, so exported
  traces line up with log timestamps without any interval ever being
  computed from the wall clock.
* **Flight recorder** — completed spans land in a bounded ring buffer
  (oldest evicted, counted by ``trace_events_dropped_total``). On a
  trigger — stall-watchdog escalation, circuit-breaker open, SIGTERM
  emergency checkpoint, an unhandled engine-step exception — the buffer
  is dumped to a JSON file (``flight_recorder_dumps_total`` by reason):
  the last N seconds of timeline, attached to the failure that needed it.
* **Chrome trace-event export** — the buffer (plus still-open request
  spans, marked ``in_flight``) serializes losslessly to the Chrome
  trace-event JSON format, loadable in Perfetto / ``chrome://tracing``;
  ``python -m deepspeed_tpu.telemetry.tracing <dump.json>`` (also
  ``tools/trace-dump``) prints a terminal summary (slowest spans,
  per-phase totals).

Request-scoped traces: the serving front-end opens one trace per uid
(``request_begin``/``request_event``/``request_end``) so a single slow
request's full timeline — admission verdict, queue wait, the ticks that
served it, terminal state — is reconstructable after the fact.

Config-gated (``"telemetry"`` section: ``tracing``,
``trace_buffer_events``, ``trace_sample_rate``, ``flight_dump_dir``)
and DISABLED by default: a disabled tracer's ``span()`` is one attribute
check returning a shared null context (measured in the tier-1 overhead
guard), so every instrumented site stays free until someone needs it.

Dependency-free (stdlib + the logger): recordable from watchdog / HTTP /
signal-handler adjacent paths without touching a device runtime.
"""
from __future__ import annotations

import collections
import json
import os
import random
import sys
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
from deepspeed_tpu.utils.logging import logger

#: schema tag written into every export/dump (consumers can gate on it)
TRACE_FORMAT_VERSION = 1

#: shared no-op context for the disabled path — allocated once so a
#: disabled span() costs an attribute check and nothing else
class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanRecord:
    """One span: ids, monotonic bounds, attrs, point events. ``t1`` is
    None while the span is open (request spans between begin and end)."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "cat", "tid",
                 "t0", "t1", "attrs", "points")

    def __init__(self, trace_id: int, span_id: int, parent_id: int,
                 name: str, cat: str, tid: int, t0: float,
                 attrs: Dict[str, Any]):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.tid = tid
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs = attrs
        # (monotonic t, name, attrs) instants inside this span. Appended
        # by the span's owning thread only (serving loop / traced thread)
        self.points: List[Tuple[float, str, Dict[str, Any]]] = []


class _SpanCtx:
    """Context manager for one stack span. Kept as a class (not a
    generator contextmanager) so enter/exit stay cheap and the exit can
    pop itself BY IDENTITY — a mid-span enable/disable toggle must not
    desync the per-thread stack."""

    __slots__ = ("_tracer", "rec")

    def __init__(self, tracer: "Tracer", rec: Optional[_SpanRecord]):
        self._tracer = tracer
        self.rec = rec   # None = trace unsampled (children skip too)

    def __enter__(self):
        self._tracer._stack().append(self)
        return self.rec

    def __exit__(self, *exc):
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:   # toggled mid-flight: remove wherever we are
            stack.remove(self)
        if self.rec is not None:
            self.rec.t1 = time.perf_counter()
            self._tracer._push(self.rec)
        return False


def _int_tid(uid: Any) -> int:
    """Stable integer tid for a request uid (Chrome trace tids are ints;
    uids in this repo are, but don't crash on a string one)."""
    if isinstance(uid, int):
        return uid
    return zlib.crc32(str(uid).encode())


class Tracer:
    """Structured tracer + flight recorder over one bounded ring buffer.

    Thread model: stack spans are per-thread (thread-local stack);
    request spans are keyed by uid and owned by the single-threaded
    serving loop; the ring buffer and open-request map are the shared
    state and sit under ``_lock`` (record path: one append under the
    lock). Exports copy under the lock and serialize outside it.
    """

    def __init__(self, enabled: bool = False, capacity: int = 4096,
                 sample_rate: float = 1.0,
                 dump_dir: str = "flight_dumps", keep_dumps: int = 20):
        self.enabled = enabled
        self.sample_rate = float(sample_rate)
        self.dump_dir = dump_dir
        # retention cap on dump FILES: a persistently-sick replica
        # re-opens its circuit once per backoff window forever, and each
        # dump serializes the full buffer — without a cap that fills the
        # disk of an unattended host (same bounding story as the ring
        # buffer itself). Oldest pruned first; 0 = keep everything.
        self.keep_dumps = keep_dumps
        self._lock = make_lock("tracer._lock")
        self._buf: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))       # guarded-by: self._lock
        self._open_reqs: Dict[Any, _SpanRecord] = {}  # guarded-by: self._lock
        self._next_id = 0                       # guarded-by: self._lock
        self._dump_seq = 0                      # guarded-by: self._lock
        self._tls = threading.local()
        self._rng = random.Random()
        self._set_anchor()

    def _set_anchor(self) -> None:
        """The ONE wall-clock read: pairs a monotonic reading with epoch
        time so exported timestamps are real without any interval ever
        being wall-clock-derived."""
        self._anchor_mono = time.perf_counter()
        # per-trace epoch anchor: exported Chrome `ts` values must be
        # real timestamps (they are compared against log lines, never
        # used as intervals)  # dslint: disable=wall-clock
        self._anchor_wall = time.time()

    # ------------------------------------------------------------------ #
    # internals
    # ------------------------------------------------------------------ #
    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _alloc_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    def _push(self, rec: _SpanRecord) -> None:
        with self._lock:
            dropped = len(self._buf) == self._buf.maxlen
            self._buf.append(rec)
        if dropped:
            # counter inc OUTSIDE the tracer lock (the registry has its
            # own lock; never hold both)
            self._tm_dropped().inc()

    def _tm_dropped(self):
        from deepspeed_tpu import telemetry

        return telemetry.counter(
            "trace_events_dropped_total",
            "trace events evicted from the flight-recorder ring buffer")

    def _tm_dumps(self):
        from deepspeed_tpu import telemetry

        return telemetry.counter(
            "flight_recorder_dumps_total",
            "flight-recorder dumps written, by trigger reason")

    def _ts_us(self, t_mono: float) -> float:
        """Monotonic reading → wall-clock microseconds via the anchor."""
        return (self._anchor_wall + (t_mono - self._anchor_mono)) * 1e6

    # ------------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------------ #
    def span(self, name: str, cat: str = "span", **attrs):
        """Context manager for one span. Child of the current thread's
        open span when one exists, else the root of a new trace (where
        the ``trace_sample_rate`` decision applies — an unsampled root
        silences its whole subtree)."""
        if not self.enabled:
            return _NULL_SPAN
        stack = self._stack()
        parent = stack[-1].rec if stack else None
        if stack and parent is None:
            return _SpanCtx(self, None)    # inside an unsampled trace
        if parent is None and self.sample_rate < 1.0 \
                and self._rng.random() >= self.sample_rate:
            return _SpanCtx(self, None)
        span_id = self._alloc_id()
        rec = _SpanRecord(
            trace_id=parent.trace_id if parent is not None else span_id,
            span_id=span_id,
            parent_id=parent.span_id if parent is not None else 0,
            name=name, cat=cat, tid=threading.get_ident(),
            t0=time.perf_counter(), attrs=dict(attrs))
        return _SpanCtx(self, rec)

    def event(self, name: str, cat: str = "event", **attrs) -> None:
        """Point-in-time event: attached to the current thread's open
        span when one exists, else recorded standalone (zero-duration)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        stack = self._stack()
        if stack:
            rec = stack[-1].rec
            if rec is not None:
                rec.points.append((now, name, dict(attrs)))
            return   # unsampled trace drops its events too
        span_id = self._alloc_id()
        rec = _SpanRecord(span_id, span_id, 0, name, cat,
                          threading.get_ident(), now, dict(attrs))
        rec.t1 = now
        self._push(rec)

    def record_span(self, name: str, duration_s: float, cat: str = "span",
                    end: Optional[float] = None, **attrs) -> None:
        """Record an already-measured section ending now (the compile-log
        path: the caller timed the work itself), or at ``end``, a
        ``time.perf_counter()`` reading (a garbage-collection pause is
        handed over after the fact: ``telemetry/host.py``)."""
        if not self.enabled:
            return
        now = time.perf_counter() if end is None else end
        span_id = self._alloc_id()
        rec = _SpanRecord(span_id, span_id, 0, name, cat,
                          threading.get_ident(), now - max(0.0, duration_s),
                          dict(attrs))
        rec.t1 = now
        self._push(rec)

    # ------------------------------------------------------------------ #
    # request-scoped traces (serving front-end)
    # ------------------------------------------------------------------ #
    def request_begin(self, uid: Any, **attrs) -> None:
        """Open a request trace for ``uid``. No-op when one is already
        open (a duplicate submission must not destroy the live request's
        timeline — the rejection lands as an event on it instead)."""
        if not self.enabled:
            return
        if self.sample_rate < 1.0 \
                and self._rng.random() >= self.sample_rate:
            return
        span_id = self._alloc_id()
        rec = _SpanRecord(span_id, span_id, 0, f"request/{uid}", "request",
                          _int_tid(uid), time.perf_counter(), dict(attrs))
        evicted = None
        with self._lock:
            if uid in self._open_reqs:
                return
            if len(self._open_reqs) >= self._buf.maxlen:
                # leak guard: a caller that never resolves uids must not
                # grow this map without bound — close out the oldest
                evicted = self._open_reqs.pop(next(iter(self._open_reqs)))
                # mutate while still under the lock (a concurrent export
                # snapshot may hold a reference); push after release
                evicted.t1 = time.perf_counter()
                evicted.attrs.setdefault("state", "abandoned")
            self._open_reqs[uid] = rec
        if evicted is not None:
            self._push(evicted)

    def request_event(self, uid: Any, name: str, **attrs) -> None:
        if not self.enabled:
            return
        now = time.perf_counter()
        # mutate rec UNDER the lock: export_chrome snapshots open request
        # records and iterates rec.points concurrently — an unlocked
        # append races that read (the scrape-vs-mutate class)
        with self._lock:
            rec = self._open_reqs.get(uid)
            if rec is not None:
                rec.points.append((now, name, dict(attrs)))

    def request_end(self, uid: Any, state: str, **attrs) -> None:
        """Close ``uid``'s trace with its terminal state; the completed
        span moves into the ring buffer. Unknown uids no-op (unsampled,
        or tracing enabled mid-request)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        # popping rec does NOT give this thread sole ownership: a
        # concurrent export_chrome may already hold a snapshot reference
        # and read rec.attrs (``dict(rec.attrs)`` raises if it changes
        # size mid-copy) — so the terminal-state mutation happens under
        # the lock too, and only the _push (which re-takes it) is outside
        with self._lock:
            rec = self._open_reqs.pop(uid, None)
            if rec is not None:
                rec.t1 = now
                rec.attrs["state"] = state
                for k, v in attrs.items():
                    if v not in (None, ""):
                        rec.attrs[k] = v
        if rec is not None:
            self._push(rec)

    # ------------------------------------------------------------------ #
    # export / flight dumps
    # ------------------------------------------------------------------ #
    def export_chrome(self) -> Dict[str, Any]:
        """The buffer (+ open request spans, marked ``in_flight``) as a
        Chrome trace-event JSON document: complete ``X`` events with
        real-timestamp ``ts`` (µs) and monotonic ``dur``, instant ``i``
        events for span points, ``pid``/``tid`` on every event, sorted
        by ``ts`` — loadable in Perfetto / ``chrome://tracing``."""
        now = time.perf_counter()
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        # render under the lock: a snapshot of the record LIST is not
        # enough — open request records' points/attrs keep mutating
        # (under this lock, see request_event/request_end), and
        # ``dict(rec.attrs)`` racing a writer is exactly the
        # scrape-vs-mutate bug this lock now covers end to end
        with self._lock:
            recs = list(self._buf) + list(self._open_reqs.values())
            for rec in recs:
                t1 = rec.t1 if rec.t1 is not None else now
                args = dict(rec.attrs)
                args["trace_id"] = rec.trace_id
                if rec.parent_id:
                    args["parent_span_id"] = rec.parent_id
                if rec.t1 is None:
                    args["in_flight"] = True
                events.append({
                    "name": rec.name, "cat": rec.cat, "ph": "X",
                    "ts": self._ts_us(rec.t0),
                    "dur": max(0.0, (t1 - rec.t0) * 1e6),
                    "pid": pid, "tid": rec.tid, "args": args,
                })
                for (t, name, attrs) in rec.points:
                    events.append({
                        "name": name, "cat": rec.cat, "ph": "i", "s": "t",
                        "ts": self._ts_us(t), "pid": pid, "tid": rec.tid,
                        "args": dict(attrs, trace_id=rec.trace_id),
                    })
        events.sort(key=lambda e: e["ts"])
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "format_version": TRACE_FORMAT_VERSION,
                "producer": "deepspeed_tpu.telemetry.tracing",
                "pid": pid,
                "export_unix_time": self._anchor_wall
                + (now - self._anchor_mono),
            },
        }

    def flight_status(self) -> Dict[str, Any]:
        """Live flight-recorder status (the ``/flight`` endpoint body)."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "buffered_events": len(self._buf),
                "capacity": self._buf.maxlen,
                "open_requests": len(self._open_reqs),
                "sample_rate": self.sample_rate,
                "dump_dir": self.dump_dir,
                "dumps_written": self._dump_seq,
            }

    def dump_flight(self, reason: str,
                    note: Optional[str] = None) -> Optional[str]:
        """Write the flight-recorder buffer to
        ``<dump_dir>/flight_<reason>_<pid>_<seq>.json`` and count it;
        dumps beyond ``keep_dumps`` are pruned oldest-first. Returns the
        path, or None when tracing is disabled or the dump failed — it
        runs INSIDE failure handlers (circuit-open, SIGTERM, step
        exceptions), so NOTHING here may take down the path that
        triggered it: every failure is logged and swallowed."""
        if not self.enabled:
            return None
        try:
            doc = self.export_chrome()
            doc["otherData"]["reason"] = reason
            if note:
                doc["otherData"]["note"] = note
            with self._lock:
                self._dump_seq += 1
                seq = self._dump_seq
            path = os.path.join(
                self.dump_dir, f"flight_{reason}_{os.getpid()}_{seq}.json")
            os.makedirs(self.dump_dir, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, default=str)   # exotic attr values
                # degrade to their repr rather than killing the dump
            os.replace(tmp, path)   # never leave a torn dump named .json
            self._prune_dumps()
            self._tm_dumps().inc(reason=reason)
            logger.warning(
                f"flight recorder: {len(doc['traceEvents'])} events -> "
                f"{path} (reason={reason}"
                + (f", note={note}" if note else "") + ")")
            return path
        except Exception as e:
            logger.warning(f"flight recorder: dump ({reason}) failed: "
                           f"{type(e).__name__}: {e}")
            return None

    def _prune_dumps(self) -> None:
        """Keep the newest ``keep_dumps`` flight files in ``dump_dir``
        (0 = unbounded); a sick replica re-dumping once per backoff
        window must not fill the disk. Best-effort: a racing unlink is
        someone else pruning the same dir."""
        if self.keep_dumps <= 0:
            return
        try:
            files = [os.path.join(self.dump_dir, f)
                     for f in os.listdir(self.dump_dir)
                     if f.startswith("flight_") and f.endswith(".json")]
            files.sort(key=os.path.getmtime)
            for stale in files[:-self.keep_dumps]:
                os.unlink(stale)
        except OSError as e:
            logger.warning(f"flight recorder: dump retention GC failed: {e}")

    # ------------------------------------------------------------------ #
    # aggregation (bench rows, CLI summary)
    # ------------------------------------------------------------------ #
    def phase_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name latency distribution over the buffered spans:
        ``{name: {count, total_s, p50_s, p95_s, p99_s}}`` — exact
        quantiles (the buffer is bounded), what ``bench.py`` embeds next
        to ``telemetry.snapshot()`` in each entry row."""
        with self._lock:
            recs = [(r.name, r.t1 - r.t0) for r in self._buf
                    if r.t1 is not None]
        by_name: Dict[str, List[float]] = {}
        for name, dur in recs:
            by_name.setdefault(name, []).append(dur)
        out: Dict[str, Dict[str, float]] = {}
        for name, durs in sorted(by_name.items()):
            durs.sort()
            n = len(durs)

            def q(frac: float) -> float:
                return durs[min(int(frac * n), n - 1)]

            out[name] = {
                "count": n,
                "total_s": round(sum(durs), 9),
                "p50_s": round(q(0.50), 9),
                "p95_s": round(q(0.95), 9),
                "p99_s": round(q(0.99), 9),
            }
        return out

    # ------------------------------------------------------------------ #
    def clear(self) -> None:
        """Tests only: drop buffered + open spans and the dump counter."""
        with self._lock:
            self._buf.clear()
            self._open_reqs.clear()
            self._dump_seq = 0


# --------------------------------------------------------------------- #
# module-level default tracer (what config wiring + instrumented sites use)
# --------------------------------------------------------------------- #
_default_tracer = Tracer()


def safe_dump_flight(reason: str, note: Optional[str] = None
                     ) -> Optional[str]:
    """Module-level convenience for failure handlers: dump the process
    tracer's flight recorder, never raising. ``Tracer.dump_flight``
    already swallows its own failures; this additionally guards the
    tracer lookup itself, so callers (guardian anomaly containment,
    elastic-agent give-up) need no boilerplate try/except."""
    try:
        return get_tracer().dump_flight(reason, note=note)
    except Exception as e:   # the caller's failure must win
        logger.warning(f"flight dump ({reason}) failed: {e}")
        return None


def get_tracer() -> Tracer:
    return _default_tracer


def configure(enabled: Optional[bool] = None,
              capacity: Optional[int] = None,
              sample_rate: Optional[float] = None,
              dump_dir: Optional[str] = None,
              keep_dumps: Optional[int] = None) -> Tracer:
    """(Re)configure the default tracer in place — process-wide, last
    caller wins (the same convention as the registry enabled gate).
    ``None`` leaves a setting unchanged; a capacity change rebuilds the
    ring buffer keeping the newest events; enabling refreshes the
    wall-clock anchor (a process may run for days before someone turns
    tracing on)."""
    tr = _default_tracer
    if capacity is not None and int(capacity) != tr._buf.maxlen:
        with tr._lock:
            tr._buf = collections.deque(tr._buf,
                                        maxlen=max(1, int(capacity)))
    if sample_rate is not None:
        tr.sample_rate = float(sample_rate)
    if dump_dir is not None:
        tr.dump_dir = dump_dir
    if keep_dumps is not None:
        tr.keep_dumps = int(keep_dumps)
    if enabled is not None:
        if enabled and not tr.enabled:
            tr._set_anchor()
        tr.enabled = bool(enabled)
    return tr


def reset() -> None:
    """Tests only: disable and clear the default tracer (defaults
    restored; ``telemetry.reset()`` calls this)."""
    tr = _default_tracer
    tr.enabled = False
    tr.sample_rate = 1.0
    tr.dump_dir = "flight_dumps"
    tr.keep_dumps = 20
    configure(capacity=4096)
    tr.clear()


# --------------------------------------------------------------------- #
# CLI: `python -m deepspeed_tpu.telemetry.tracing <dump.json>`
# (also `tools/trace-dump`) — terminal summary of a trace/flight dump
# --------------------------------------------------------------------- #
def _load_dump(path: str) -> Dict[str, Any]:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError(f"{path} is not a Chrome trace-event JSON dump "
                         "(no 'traceEvents' key)")
    return doc


def summarize(doc: Dict[str, Any], top: int = 10) -> str:
    """Human summary of one dump: header, per-phase totals, slowest
    spans. Pure function over the parsed JSON (tested directly)."""
    events = doc.get("traceEvents", [])
    spans = [e for e in events if e.get("ph") == "X"]
    other = doc.get("otherData", {})
    lines = []
    head = f"{len(events)} events ({len(spans)} spans)"
    if "reason" in other:
        head += f", dump reason: {other['reason']}"
        if "note" in other:
            head += f" (note: {other['note']})"
    lines.append(head)
    if spans:
        t_lo = min(e["ts"] for e in spans)
        t_hi = max(e["ts"] + e.get("dur", 0.0) for e in spans)
        lines.append(f"timeline: {(t_hi - t_lo) / 1e6:.3f}s "
                     f"across {len({e['tid'] for e in spans})} track(s)")
        by_name: Dict[str, List[float]] = {}
        for e in spans:
            by_name.setdefault(e["name"], []).append(e.get("dur", 0.0))
        lines.append("")
        lines.append(f"{'phase':<32} {'count':>6} {'total_ms':>10} "
                     f"{'p50_ms':>9} {'p95_ms':>9} {'p99_ms':>9}")
        for name, durs in sorted(by_name.items(),
                                 key=lambda kv: -sum(kv[1])):
            durs.sort()
            n = len(durs)

            def q(frac: float) -> float:
                return durs[min(int(frac * n), n - 1)]

            lines.append(
                f"{name[:32]:<32} {n:>6} {sum(durs) / 1e3:>10.3f} "
                f"{q(.5) / 1e3:>9.3f} {q(.95) / 1e3:>9.3f} "
                f"{q(.99) / 1e3:>9.3f}")
        lines.append("")
        lines.append(f"slowest {min(top, len(spans))} spans:")
        for e in sorted(spans, key=lambda e: -e.get("dur", 0.0))[:top]:
            state = e.get("args", {}).get("state", "")
            lines.append(
                f"  {e.get('dur', 0.0) / 1e3:>10.3f} ms  {e['name']}"
                + (f"  [{state}]" if state else ""))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m deepspeed_tpu.telemetry.tracing "
              "<dump.json> [--top N]\n"
              "Summarize a trace/flight-recorder dump: per-phase "
              "p50/p95/p99 and the slowest spans.\n"
              "Open the same file in https://ui.perfetto.dev for the "
              "full timeline.")
        return 0 if argv else 2
    top = 10
    if "--top" in argv:
        i = argv.index("--top")
        try:
            top = int(argv[i + 1])
        except (IndexError, ValueError):
            print("error: --top needs an integer value", file=sys.stderr)
            return 2
        argv = argv[:i] + argv[i + 2:]
    try:
        doc = _load_dump(argv[0])
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(summarize(doc, top=top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
