"""Dependency-free metrics registry: Counter / Gauge / Histogram with labels.

The unification point for the repo's four metric islands (``utils/timer``,
``monitor/monitor``, ``profiling/flops_profiler``, ``utils/comms_logging``):
everything records here, and the exposition layer (``telemetry/exposition``)
serves one Prometheus text endpoint + one JSON snapshot over it.

Design constraints:

* stdlib-only (no jax import on the record path — metrics must be writable
  from watchdog/HTTP threads without touching a device runtime);
* process-0 gated like ``monitor/monitor.py`` (SPMD: every host records the
  same values; one writer is the rank-0 analog). The gate is evaluated
  lazily on first record so importing telemetry never initializes jax;
* recording is O(dict lookup + float add) under an RLock — cheap enough for
  per-tick serving paths, but anything per-device-op still belongs in
  ``jax.profiler`` traces, not here.

Collectors: callables registered via :meth:`MetricsRegistry.add_collector`
run right before a snapshot/render — the hook for lazily-priced values
(device_get of the last step's metrics, allocator occupancy). A collector
that returns ``False`` is deregistered (the weakref-to-owner idiom); one
that raises is dropped into ``telemetry_collector_errors_total`` instead of
breaking the scrape.
"""
from __future__ import annotations

import bisect
import collections

from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

LabelKey = Tuple[Tuple[str, str], ...]

# Prometheus-style latency buckets (seconds), wide enough for both a ~100us
# CPU tick and a multi-second fused train window.
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)

# Sliding-window defaults: every histogram keeps a ring of per-interval
# snapshots alongside its lifetime state, so windowed quantiles reflect
# the last ``window_s`` seconds instead of the whole process lifetime
# (one slow startup tick must not skew a p99 gauge — or a hedge
# threshold — forever). Granularity is ``window_s / window_intervals``.
DEFAULT_WINDOW_S = 60.0
DEFAULT_WINDOW_INTERVALS = 6

_process_zero: Optional[bool] = None


def _is_process_zero() -> bool:
    """Rank-0 gate, resolved lazily (jax.process_index initializes the
    backend — must not happen at import time)."""
    global _process_zero
    if _process_zero is None:
        try:
            import jax

            _process_zero = jax.process_index() == 0
        # any failure (no jax, no backend, mid-init) means single-process:
        # record. The registry is dependency-free by contract, so no logger
        # here — and this resolves ONCE.  # dslint: disable=silent-except
        except Exception:
            _process_zero = True
    return _process_zero


def _label_key(labels: Dict[str, Any]) -> LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def label_key(**labels) -> LabelKey:
    """The key of one label set, for a caller that records the same few
    series every tick and builds their keys once (``Counter.inc_keys``,
    ``Histogram.observe_key``)."""
    return _label_key(labels)


def format_labels(key: LabelKey) -> str:
    if not key:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in key) + "}"


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, description: str, registry: "MetricsRegistry"):
        self.name = name
        self.description = description
        self._registry = registry
        self._lock = registry._lock
        self._children: Dict[LabelKey, Any] = {}

    def _enabled(self) -> bool:
        return self._registry.enabled and _is_process_zero()

    def labels_items(self):
        with self._lock:
            return list(self._children.items())


class Counter(_Metric):
    """Monotone counter; ``inc`` only accepts non-negative amounts."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not self._enabled():
            return
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = _label_key(labels)
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + amount

    def inc_keys(self, keys: Sequence[LabelKey],
                 amounts: Sequence[float]) -> None:
        """``inc`` of several series under one acquisition of the lock,
        by keys built once (``label_key``): a per-tick account pays for
        no sorting of label names and no ``str()``."""
        if not self._enabled():
            return
        if min(amounts) < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        children = self._children
        with self._lock:
            for key, amount in zip(keys, amounts):
                children[key] = children.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._children.get(_label_key(labels), 0.0)

    def total(self) -> float:
        """Sum across every label combination."""
        with self._lock:
            return sum(self._children.values())


class Gauge(_Metric):
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        if not self._enabled():
            return
        with self._lock:
            self._children[_label_key(labels)] = float(value)

    def set_max(self, value: float, **labels) -> None:
        """Monotone high-water mark (peak queue depth, peak occupancy)."""
        if not self._enabled():
            return
        key = _label_key(labels)
        with self._lock:
            self._children[key] = max(self._children.get(key, float("-inf")),
                                      float(value))

    def inc(self, amount: float = 1.0, **labels) -> None:
        if not self._enabled():
            return
        key = _label_key(labels)
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + amount

    def value(self, **labels) -> Optional[float]:
        with self._lock:
            return self._children.get(_label_key(labels))


class _HistogramChild:
    __slots__ = ("bucket_counts", "count", "sum", "min", "max")

    def __init__(self, n_buckets: int):
        self.bucket_counts = [0] * (n_buckets + 1)  # +1 = the +Inf bucket
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, idx: int, value: float, total: float, n: int) -> None:
        self.bucket_counts[idx] += n
        self.count += n
        self.sum += total
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def merge(self, other: "_HistogramChild") -> None:
        for i, n in enumerate(other.bucket_counts):
            self.bucket_counts[i] += n
        self.count += other.count
        self.sum += other.sum
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)


class Histogram(_Metric):
    """Cumulative-bucket histogram (Prometheus semantics). ``observe`` takes
    an optional ``n`` weight so a fused window can credit its per-item mean
    once per item without a Python loop.

    Alongside the lifetime state every child keeps a bounded ring of
    per-interval snapshots (``window_s`` seconds in ``window_intervals``
    slices): ``windowed_summary`` / ``windowed_quantile`` answer over
    the last N seconds only, while ``summary`` keeps its process-lifetime
    semantics for bench back-compat. ``set_window_clock`` injects a
    deterministic clock (the serving fleet points it at its own, so the
    chaos tests' seeded clocks drive window expiry too)."""

    kind = "histogram"

    def __init__(self, name: str, description: str, registry: "MetricsRegistry",
                 buckets: Optional[Sequence[float]] = None,
                 window_s: float = DEFAULT_WINDOW_S,
                 window_intervals: int = DEFAULT_WINDOW_INTERVALS):
        super().__init__(name, description, registry)
        self.buckets = tuple(sorted(buckets if buckets is not None
                                    else DEFAULT_BUCKETS))
        self.window_s = float(window_s)
        self.window_intervals = max(1, int(window_intervals))
        self._interval_s = self.window_s / self.window_intervals
        self._clock = time.monotonic
        # per-label ring of (interval_index, interval child), newest last
        self._win: Dict[LabelKey, collections.deque] = {}

    def set_window_clock(self, clock: Callable[[], float]) -> None:
        """Point the sliding window at an injectable clock (tests, the
        fleet's deterministic clock). Lifetime state is clock-free."""
        with self._lock:
            self._clock = clock

    def labels_items(self):
        """Consistent SNAPSHOTS of each child, copied under the registry
        lock — readers (exposition, bridge) iterate bucket lists outside
        the lock, and a live child mutating mid-scrape would emit a
        malformed histogram (count > +Inf bucket)."""
        with self._lock:
            out = []
            for key, c in self._children.items():
                cc = _HistogramChild.__new__(_HistogramChild)
                cc.bucket_counts = list(c.bucket_counts)
                cc.count, cc.sum = c.count, c.sum
                cc.min, cc.max = c.min, c.max
                out.append((key, cc))
            return out

    def observe(self, value: float, n: int = 1, **labels) -> None:
        if not self._enabled() or n < 1:
            return
        with self._lock:
            self._observe_locked(_label_key(labels), float(value), n)

    def observe_key(self, key: LabelKey, value: float) -> None:
        """``observe`` by a key built once (``label_key``)."""
        if self._enabled():
            with self._lock:
                self._observe_locked(key, float(value), 1)

    def _observe_locked(self, key: LabelKey, value: float, n: int) -> None:
        """The observation itself; the caller holds the lock and has
        checked ``_enabled()`` (``MetricsRegistry.observe_span`` brings a
        key it built once and shares the lock with its own bookkeeping)."""
        # first edge with value <= edge; past the last: the +Inf bucket
        idx = bisect.bisect_left(self.buckets, value) if value == value \
            else len(self.buckets)      # NaN compares False with every edge
        total = value * n
        child = self._children.get(key)
        if child is None:
            child = self._children[key] = _HistogramChild(len(self.buckets))
        # the windowed twin: same observation lands in the current
        # interval's snapshot; expired intervals fall off the ring
        child.observe(idx, value, total, n)
        self._win_child(key).observe(idx, value, total, n)

    def _win_child(self, key: LabelKey) -> _HistogramChild:
        """Current interval's child for ``key`` (caller holds the lock)."""
        now_idx = int(self._clock() // self._interval_s)
        ring = self._win.get(key)
        if ring is None:
            ring = self._win[key] = collections.deque()
        elif ring and ring[-1][0] == now_idx:
            # the common case; what had expired by this interval fell off
            # when the interval was opened
            return ring[-1][1]
        ring.append((now_idx, _HistogramChild(len(self.buckets))))
        while ring and ring[0][0] <= now_idx - self.window_intervals:
            ring.popleft()
        return ring[-1][1]

    def windowed_child(self, window_s: Optional[float] = None,
                       **labels) -> Optional[_HistogramChild]:
        """Merged snapshot of the intervals inside the last ``window_s``
        seconds (default: the full configured window; longer requests are
        clamped to what the ring retains). None when no observation
        landed inside the window."""
        if window_s is None:
            window_s = self.window_s
        span = max(1, int(round(window_s / self._interval_s)))
        span = min(span, self.window_intervals)
        with self._lock:
            ring = self._win.get(_label_key(labels))
            if not ring:
                return None
            now_idx = int(self._clock() // self._interval_s)
            merged = _HistogramChild(len(self.buckets))
            for idx, child in ring:
                if now_idx - span < idx <= now_idx:
                    merged.merge(child)
        return merged if merged.count else None

    def windowed_quantile(self, q: float,
                          window_s: Optional[float] = None,
                          **labels) -> Optional[float]:
        """Bucket-interpolated quantile over the sliding window, or None
        when the window is empty — callers fall back to their floor (the
        hedge threshold) or the lifetime view."""
        child = self.windowed_child(window_s=window_s, **labels)
        if child is None:
            return None
        return self._quantile(self.buckets, child, q)

    def windowed_summary(self, window_s: Optional[float] = None,
                         **labels) -> Dict[str, float]:
        """Like :meth:`summary` but over the sliding window only, with a
        p99 column (the SLO engine's quantile source)."""
        child = self.windowed_child(window_s=window_s, **labels)
        if child is None:
            return {"count": 0, "sum": 0.0}
        return {
            "count": child.count,
            "sum": round(child.sum, 9),
            "mean": round(child.sum / child.count, 9),
            "min": round(child.min, 9),
            "max": round(child.max, 9),
            "p50": round(self._quantile(self.buckets, child, 0.5), 9),
            "p95": round(self._quantile(self.buckets, child, 0.95), 9),
            "p99": round(self._quantile(self.buckets, child, 0.99), 9),
        }

    def windowed_bad_fraction(self, threshold: float,
                              window_s: Optional[float] = None,
                              **labels) -> Optional[Tuple[float, int]]:
        """``(bad_fraction, total)`` over the window, where *bad* means an
        observation above ``threshold`` — counted at bucket granularity
        (the smallest bucket edge >= threshold bounds the good side), so
        the verdict is deterministic and scrape-consistent. None when the
        window is empty."""
        child = self.windowed_child(window_s=window_s, **labels)
        if child is None or child.count == 0:
            return None
        good = 0
        for i, edge in enumerate(self.buckets):
            if edge > threshold:
                break
            good += child.bucket_counts[i]
        return (child.count - good) / child.count, child.count

    def child(self, **labels) -> Optional[_HistogramChild]:
        with self._lock:
            return self._children.get(_label_key(labels))

    @staticmethod
    def _quantile(buckets: Sequence[float], child: _HistogramChild,
                  q: float) -> float:
        """Bucket-interpolated quantile estimate (what the snapshot reports;
        exact samples are not retained)."""
        if child.count == 0:
            return 0.0
        target = q * child.count
        seen = 0
        lo = 0.0
        for i, edge in enumerate(buckets):
            n = child.bucket_counts[i]
            if seen + n >= target and n > 0:
                frac = (target - seen) / n
                return min(lo + (edge - lo) * frac, child.max)
            seen += n
            lo = edge
        return child.max

    def quantile(self, q: float, **labels) -> Optional[float]:
        """Lifetime-view quantile estimate (``windowed_quantile`` is the
        recency-bounded sibling); None before any observation."""
        with self._lock:
            live = self._children.get(_label_key(labels))
            if live is None or live.count == 0:
                return None
            child = _HistogramChild.__new__(_HistogramChild)
            child.bucket_counts = list(live.bucket_counts)
            child.count, child.sum = live.count, live.sum
            child.min, child.max = live.min, live.max
        return self._quantile(self.buckets, child, q)

    def summary(self, **labels) -> Dict[str, float]:
        with self._lock:   # copy, not live — same torn-read hazard as
            live = self._children.get(_label_key(labels))   # labels_items
            if live is None or live.count == 0:
                return {"count": 0, "sum": 0.0}
            child = _HistogramChild.__new__(_HistogramChild)
            child.bucket_counts = list(live.bucket_counts)
            child.count, child.sum = live.count, live.sum
            child.min, child.max = live.min, live.max
        return {
            "count": child.count,
            "sum": round(child.sum, 9),
            "mean": round(child.sum / child.count, 9),
            "min": round(child.min, 9),
            "max": round(child.max, 9),
            "p50": round(self._quantile(self.buckets, child, 0.5), 9),
            "p95": round(self._quantile(self.buckets, child, 0.95), 9),
        }


class MetricsRegistry:
    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._lock = make_lock("registry._lock", reentrant=True)
        self._metrics: Dict[str, _Metric] = {}          # guarded-by: self._lock
        self._collectors: List[Callable[[], Any]] = []  # guarded-by: self._lock
        # watchdog substrate: the last completed span as (name, monotonic
        # end time) — interval math only, never exported as a timestamp
        self.last_span: Optional[Tuple[str, float]] = None  # guarded-by: self._lock
        self._span_hist: Optional[Histogram] = None

    # -- metric construction (idempotent by name, kind-checked) ---------- #
    def _get_or_make(self, cls, name: str, description: str, **kw):
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if not isinstance(existing, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{existing.kind}, requested {cls.kind}")
                return existing
            metric = cls(name, description, self, **kw)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, description: str = "") -> Counter:
        return self._get_or_make(Counter, name, description)

    def gauge(self, name: str, description: str = "") -> Gauge:
        return self._get_or_make(Gauge, name, description)

    def histogram(self, name: str, description: str = "",
                  buckets: Optional[Sequence[float]] = None,
                  window_s: float = DEFAULT_WINDOW_S,
                  window_intervals: int = DEFAULT_WINDOW_INTERVALS,
                  ) -> Histogram:
        return self._get_or_make(Histogram, name, description,
                                 buckets=buckets, window_s=window_s,
                                 window_intervals=window_intervals)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def metrics(self) -> List[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    # -- collectors ------------------------------------------------------ #
    def add_collector(self, fn: Callable[[], Any]) -> None:
        """Register a pre-scrape callback. Return ``False`` from the callback
        to deregister it (weakref-owner idiom); exceptions are counted in
        ``telemetry_collector_errors_total`` and the scrape proceeds."""
        with self._lock:
            self._collectors.append(fn)

    def collect(self) -> None:
        """Run collectors (right before a snapshot, a render or a bridge
        publish)."""
        with self._lock:
            collectors = list(self._collectors)
        dead = []
        for fn in collectors:
            try:
                if fn() is False:
                    dead.append(fn)
            except Exception as e:  # broken collector must not kill scrapes
                self.counter(
                    "telemetry_collector_errors_total",
                    "collector callbacks that raised during a scrape",
                ).inc(error=type(e).__name__)
        if dead:
            with self._lock:
                self._collectors = [f for f in self._collectors
                                    if f not in dead]

    # -- span bookkeeping (see telemetry/spans.py) ----------------------- #
    def observe_span(self, key: LabelKey, name: str, seconds: float) -> None:
        """What the end of a ``telemetry.span`` records, under one
        acquisition of the lock: the wall time into ``span_seconds`` (made
        at the first span and then held: the metric object outlives
        ``reset()``) and the span as the last completed one."""
        hist = self._span_hist
        if hist is None:
            hist = self._span_hist = self.histogram(
                "span_seconds", "wall time of telemetry.span sections")
        record = hist._enabled()
        with self._lock:
            if record:
                hist._observe_locked(key, seconds, 1)
            self.last_span = (name, time.monotonic())

    def reset(self) -> None:
        """Tests only: zero every metric and drop collectors/span state.

        Children are cleared IN PLACE and the metric objects stay
        registered — engines (training or FastGen) cache their handles at
        construction, and dropping the dict would strand a long-lived
        engine's recordings in orphaned objects invisible to snapshots."""
        with self._lock:
            for m in self._metrics.values():
                m._children.clear()
                win = getattr(m, "_win", None)
                if win is not None:
                    win.clear()
            self._collectors.clear()
            self.last_span = None


#: the process-wide registry (``telemetry.get_registry()``)
DEFAULT_REGISTRY = MetricsRegistry()
