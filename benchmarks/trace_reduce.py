"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Written against what a v5e trace holds (looked at by hand,
see ``testdata/``):

* one plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` has one
  event per executed HLO instruction, named by the instruction's text
  (``%name = type opkind(operands), ...``), with container instructions
  (``while``, ``conditional``, ``call``) enclosing the events of their
  bodies; line ``Async XLA Ops`` has one event from each ``*-start`` to
  its ``*-done``; line ``XLA Modules`` has one event per executed program
  (``jit_<fn>(<fingerprint>)``);
* plane ``/host:CPU`` has a line per thread; ``TraceAnnotation`` spans
  (the program's ``telemetry.span`` names and the benchmark's ``bench.*``)
  are events named by the span. Device and host clocks agree to about a
  millisecond, so whatever must be exact is matched by order, not by time.

Busy time is the union of the *leaf* events of ``XLA Ops`` (an operation
ran); a container's own time is loop control and counts as idle. The
interval arithmetic is ``profiling/observatory/overlap.py``'s
``overlap_from_intervals`` (union, intersection), copied.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_OP_NAME = re.compile(r"^%?([^\s=]+)\s*=")
_OP_KIND = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start|-done)?$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.trace_window"


# ------------------------------------------------------------------ #
# interval arithmetic (copied from observatory/overlap.py)
# ------------------------------------------------------------------ #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(merged: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in merged)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Overlap of two merged, sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def complement(merged: Sequence[Interval], lo: float, hi: float
               ) -> List[Interval]:
    gaps, at = [], lo
    for a, b in merged:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


# ------------------------------------------------------------------ #
@dataclasses.dataclass(frozen=True)
class Op:
    """One leaf event of ``XLA Ops`` (times in seconds on the trace's clock)."""
    name: str        # the instruction's name, e.g. ``fusion.12``
    kind: str        # its opcode, e.g. ``fusion``, ``custom-call``
    text: str        # the whole instruction text
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def is_collective(self) -> bool:
        return bool(_COLLECTIVE.match(self.kind))

    @property
    def is_mosaic(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.text


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def parse_instruction(text: str) -> Tuple[str, str]:
    """(name, opcode) of an ``XLA Ops`` event name."""
    m = _OP_NAME.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0].lstrip("%")
    rest = text[m.end():] if m else text
    k = _OP_KIND.search(rest)
    return name, (k.group(1) if k else "unknown")


def _leaves(events: List[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """Events that enclose no other event of the line."""
    events = sorted(events, key=lambda e: (e[0], -(e[1] - e[0])))
    out = []
    for i, (lo, hi, text) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        # the next event (by start, longest first) lies inside this one
        # exactly when this one is a container
        if nxt is not None and nxt[0] >= lo and nxt[1] <= hi \
                and (nxt[1] - nxt[0]) < (hi - lo):
            continue
        out.append((lo, hi, text))
    return out


class ReducedTrace:
    """The trace, cut down to what the readers ask for."""

    def __init__(self, ops: Dict[int, List[Op]],
                 async_ops: Dict[int, List[Op]],
                 modules: Dict[int, List[Span]], host: List[Span],
                 window_span: str = WINDOW_SPAN):
        self.ops = ops                # chip -> leaf ops, by start
        self.async_ops = async_ops    # chip -> start..done spans
        self.modules = modules        # chip -> executed programs, by start
        self.host = sorted(host, key=lambda s: s.start)
        win = [s for s in self.host if s.name == window_span]
        if win:
            self.window = (win[0].start, win[-1].end)
        else:
            every = [o for v in ops.values() for o in v]
            self.window = (min(o.start for o in every),
                           max(o.end for o in every)) if every else (0.0, 0.0)

    # ---------------- construction ---------------- #
    @classmethod
    def from_file(cls, path: str, window_span: str = WINDOW_SPAN
                  ) -> "ReducedTrace":
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        ops: Dict[int, List[Op]] = {}
        async_ops: Dict[int, List[Op]] = {}
        modules: Dict[int, List[Span]] = {}
        host: List[Span] = []
        for plane in data.planes:
            dev = _DEVICE_PLANE.match(plane.name)
            if dev:
                chip = int(dev.group(1))
                for line in plane.lines:
                    evs = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        ops[chip] = [Op(*parse_instruction(t), t, lo, hi)
                                     for lo, hi, t in _leaves(evs)]
                    elif line.name == "Async XLA Ops":
                        async_ops[chip] = [Op(*parse_instruction(t), t, lo, hi)
                                           for lo, hi, t in sorted(evs)]
                    elif line.name == "XLA Modules":
                        modules[chip] = [Span(t, lo, hi)
                                         for lo, hi, t in sorted(evs)]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        # python-tracer frames start with '$'; runtime
                        # internals contain '::' or spaces
                        if e.name.startswith("$"):
                            continue
                        host.append(Span(
                            e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9))
        return cls(ops, async_ops, modules, host, window_span)

    @classmethod
    def from_dir(cls, logdir: str) -> Optional["ReducedTrace"]:
        found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                                 recursive=True))
        return cls.from_file(found[-1]) if found else None

    # ---------------- device ---------------- #
    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, chip: int) -> List[Interval]:
        return union(clip(((o.start, o.end) for o in self.ops[chip]),
                          *self.window))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy_intervals(c)) for c in self.chips) \
            / len(self.chips)

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def ops_in_window(self, chip: int) -> List[Op]:
        lo, hi = self.window
        return [o for o in self.ops[chip] if o.end > lo and o.start < hi]

    def op_seconds(self, pred) -> float:
        """Device seconds of the leaf ops ``pred`` accepts, per chip."""
        if not self.ops:
            return 0.0
        return sum(o.seconds for c in self.chips
                   for o in self.ops_in_window(c) if pred(o)) / len(self.chips)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The operations with most device time (seconds per chip), under
        the instruction names the trace gives them, with the opcode."""
        acc: Dict[str, float] = collections.defaultdict(float)
        for c in self.chips:
            for o in self.ops_in_window(c):
                acc[f"{o.name} [{o.kind}]"] += o.seconds / len(self.chips)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:n]

    def collective_exposed_s(self) -> float:
        """Seconds per chip inside collective events (synchronous ones on
        ``XLA Ops``, start-to-done spans on ``Async XLA Ops``) during which
        no other operation ran on that chip."""
        if not self.ops:
            return 0.0
        exposed = 0.0
        for c in self.chips:
            comm = [(o.start, o.end) for o in self.ops_in_window(c)
                    if o.is_collective]
            comm += [(o.start, o.end) for o in self.async_ops.get(c, [])
                     if o.is_collective]
            comm_u = union(clip(comm, *self.window))
            compute_u = union(clip(
                ((o.start, o.end) for o in self.ops[c]
                 if not o.is_collective), *self.window))
            exposed += total(comm_u) - intersect(comm_u, compute_u)
        return exposed / len(self.chips)

    def module_runs(self, prefix: str, chip: Optional[int] = None
                    ) -> List[Span]:
        """Executions of the programs whose name starts with ``prefix``
        on one chip (the first by default), whole ones inside the window."""
        if not self.modules:
            return []
        chip = self.chips[0] if chip is None else chip
        lo, hi = self.window
        return [m for m in self.modules.get(chip, [])
                if m.name.startswith(prefix) and m.start >= lo and m.end <= hi]

    # ---------------- host ---------------- #
    def spans(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [s for s in self.host
                if s.name == name and s.start >= lo and s.end <= hi]

    def innermost_span(self, at: float, names: Sequence[str]) -> str:
        best = None
        for s in self.host:
            if s.start > at:
                break
            if s.end >= at and s.name in names and (
                    best is None or s.end - s.start < best.end - best.start):
                best = s
        return best.name if best else "(no span)"

    def longest_gaps(self, names: Sequence[str], n: int = 10
                     ) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first chip, each with the innermost
        of the named host spans that covered its middle."""
        if not self.ops:
            return []
        gaps = complement(self.busy_intervals(self.chips[0]), *self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.innermost_span((lo + hi) / 2, names), hi - lo)
                for lo, hi in gaps[:n]]
