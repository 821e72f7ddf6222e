"""What both runners share: the record a run hands to the per-layer
readers, snapshots of the program's telemetry, the count of compilations,
and the profiler switch.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmarks.manifest import ROOT, Cell

T0 = time.perf_counter()      # process start, near enough: run.py imports
#                               this before anything heavy

#: where a run may write (listed in .gitignore)
OUT_DIR = os.path.join(ROOT, "bench_out")


#: host spans (the program's ``telemetry.span`` names and the runners' own
#: ``bench.*``) that may own a device gap in ``breakdown.idle_gaps``
GAP_SPANS = ("bench.submit", "bench.harvest", "bench.sleep", "bench.tick",
             "serving_tick", "schedule_tick", "decode_tick",
             "bench.step", "train_step")


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


#: what the checks of ``correct`` found wrong; empty means correct. A failed
#: check is recorded and the run goes on, so its numbers are still printed
#: beside ``correct: false``.
FAILURES: List[str] = []


def check(cond: bool, what: str) -> None:
    if not cond:
        FAILURES.append(what)
        log(f"CHECK FAILED: {what}")


# ------------------------------------------------------------------ #
class CompileCounter:
    """Counts programs lowered (a new shape or function reached ``jit``),
    whether the persistent cache then had them or not."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name == self.EVENT:
            self.count += 1


# ------------------------------------------------------------------ #
class LoweredText:
    """The text of every program JAX lowers while this is open
    (``jax_dump_ir_to``: written at lowering, so a program the persistent
    cache then supplies is seen too, and nothing is compiled twice). The
    runners use it to ask whether the program's own step reached Mosaic,
    in untraced runs as well."""

    def __init__(self, tag: str):
        self.dir = os.path.join(OUT_DIR, "lowered", tag)

    def __enter__(self) -> "LoweredText":
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.config.update("jax_dump_ir_to", self.dir)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.config.update("jax_dump_ir_to", None)

    def contains(self, needle: str) -> bool:
        for name in sorted(os.listdir(self.dir)):
            with open(os.path.join(self.dir, name), errors="replace") as f:
                if needle in f.read():
                    return True
        return False


# ------------------------------------------------------------------ #
def telemetry_snapshot() -> Dict[str, Any]:
    """Counters, gauges and histogram buckets of the program's registry,
    copied (the registry is cumulative; readers take differences)."""
    from deepspeed_tpu import telemetry

    snap: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
    for m in telemetry.get_registry().metrics():
        if m.kind == "histogram":
            snap["histograms"][m.name] = {
                "buckets": list(m.buckets),
                "children": {key: (list(c.bucket_counts), c.count, c.sum)
                             for key, c in m.labels_items()}}
        elif m.kind in ("counter", "gauge"):
            snap[m.kind + "s"][m.name] = dict(m.labels_items())
    return snap


class Telemetry:
    """The window's share of the program's telemetry: end minus start."""

    def __init__(self, start: Dict[str, Any], end: Dict[str, Any]):
        self.start, self.end = start, end

    @staticmethod
    def _match(key, labels: Dict[str, str]) -> bool:
        have = dict(key)
        return all(have.get(k) == v for k, v in labels.items())

    def counter(self, name: str, **labels) -> float:
        """Increase over the window, summed over the label sets that
        carry ``labels``."""
        def tot(snap):
            return sum(v for k, v in snap["counters"].get(name, {}).items()
                       if self._match(k, labels))
        return tot(self.end) - tot(self.start)

    def gauge(self, name: str, **labels) -> Optional[float]:
        for k, v in self.end["gauges"].get(name, {}).items():
            if self._match(k, labels):
                return v
        return None

    def histogram(self, name: str, **labels
                  ) -> Optional[Tuple[List[float], List[int], int, float]]:
        """(bucket edges, counts per bucket, count, sum) over the window."""
        h1 = self.end["histograms"].get(name)
        if h1 is None:
            return None
        h0 = self.start["histograms"].get(name, {"children": {}})
        n = len(h1["buckets"]) + 1
        counts, count, total = [0] * n, 0, 0.0
        for key, (bc, c, s) in h1["children"].items():
            if not self._match(key, labels):
                continue
            bc0, c0, s0 = h0["children"].get(key, ([0] * n, 0, 0.0))
            counts = [a + b - b0 for a, b, b0 in zip(counts, bc, bc0)]
            count += c - c0
            total += s - s0
        return h1["buckets"], counts, count, total

    def quantile(self, name: str, q: float, **labels) -> Optional[float]:
        """Quantile interpolated inside the program's histogram buckets
        (exact samples are not kept by the program)."""
        h = self.histogram(name, **labels)
        if h is None or h[2] <= 0:
            return None
        edges, counts, count, _ = h
        target, seen, lo = q * count, 0, 0.0
        for edge, n in zip(edges, counts):
            if n > 0 and seen + n >= target:
                return lo + (edge - lo) * (target - seen) / n
            seen += n
            lo = edge
        return edges[-1] if edges else None


# ------------------------------------------------------------------ #
class Profiler:
    """``jax.profiler`` around a stretch of the run; Python-frame tracing
    off (it multiplies the host's work and the trace's size), host
    ``TraceAnnotation`` spans on."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(OUT_DIR, "trace", cell_name)
        self.on = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on = True

    def stop(self):
        import jax

        from benchmarks.trace_reduce import ReducedTrace

        jax.profiler.stop_trace()
        self.on = False
        return ReducedTrace.from_dir(self.dir)


# ------------------------------------------------------------------ #
@dataclasses.dataclass
class RunRecord:
    """What a run hands to the metric readers."""
    cell: Cell
    seconds: float                   # length of the measured window
    chips: int
    device: Dict[str, Any]
    peaks: Optional[Dict[str, float]]    # None in a rehearsal
    model: Any                       # the program's model config
    setup_s: float
    client: Dict[str, Any]           # the runner's own log of the window
    telemetry: Optional[Telemetry] = None
    trace: Any = None                # ReducedTrace of the traced stretch
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: what one reader worked out for the others; never printed
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
