"""Who owns the device's idle time between two ticks (steps), and which
phase of a training step the device's busy time belongs to.

Read from the run's ``.xplane.pb`` (``bench_out/trace/<cell>/``), beside
``trace_reduce.py`` and not in it. What the trace holds (looked at by hand
in ``testdata/``):

* every ``XLA Modules`` event on a device plane carries a ``run_id`` stat,
  and the host's ``DoEnqueueProgram`` event that launched that run carries
  the same ``run_id`` (with ``device_ordinal``: on four chips the run ids
  differ per device, so the chain follows ordinal 0 and chip 0). That is an
  exact join of a run to its enqueue, and through the enqueue, by time on
  the one host clock, to the program span that caused it: the last
  ``tick_dispatch`` (``train_step``) that began before it. The enqueue
  runs on a runtime thread and, as often as not, after the jitted call
  has returned (my chip run, PR 23, call 1: 145 of 190 ticks), so the
  span need not enclose it;
* ``telemetry.span`` names are host events (``TraceAnnotation``);
* each ``XLA Ops`` event's *metadata* has a ``tf_op`` stat: the operation's
  name stack as the program wrote it (``jit(train_step)/loss_and_grads/
  jvp(attn)/...``). ``jax.profiler.ProfileData`` hands out event stats and
  no metadata stats, and the only protobuf module for the format here
  imports all of TensorFlow (14 s), so the few metadata fields are decoded
  from the wire format directly (``_fields``).

The chain, for consecutive runs k, k+1 of the tick (step) program on chip
0: the device gap ``G = start(k+1) - end(k)`` on the device clock; on the
host clock the interval ``I`` from the end of ``tick_readback(k)``
(training: of ``bench.step(k)``, the loss read being the fence) to the
enqueue of k+1, or to the end of the span that dispatched k+1 where the
runtime enqueued later than that; ``I`` is split among the innermost
program spans that cover it; what no program span covers is the
*client*'s; ``G - |I|`` is the *runtime*'s share (completion notice and
read-back after the device finished, the launch after the jitted call
returned or after the enqueue). Only durations are compared across the two clocks.
Every metric is None unless runs, enqueues and spans match one to one.

The same join gives ``readers.traced_ticks`` its rows (``ticks``): the
device time of a tick's run beside the wall time of the ``serving_tick``
span around the span that dispatched it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import statistics
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmarks import trace_reduce as tr

ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"

#: cell runner -> program prefix on ``XLA Modules``, the span that encloses
#: the enqueue, the span whose end opens the host interval, span -> owner
CHAINS = {
    "serve": {
        "module": "jit_tick",
        "dispatch": "tick_dispatch",
        # the read-back follows the dispatch of its tick
        "fence": "tick_readback", "fence_encloses_dispatch": False,
        # the frontend's span around a whole tick, and the runner's mark
        # after it that says whether the tick held prompt rows
        "tick": "serving_tick",
        "marks": {"bench.tick.mixed": True, "bench.tick.decode": False},
        "owners": {"schedule_tick": "schedule", "tick_dispatch": "dispatch",
                   "decode_tick": "dispatch", "tick_readback": "dispatch",
                   "tick_commit": "commit", "serving_harvest": "frontend",
                   "serving_submit": "frontend", "serving_tick": "frontend"},
    },
    "train": {
        "module": "jit_train_step",
        "dispatch": "train_step",
        # the benchmark's span around the step and its loss read
        "fence": "bench.step", "fence_encloses_dispatch": True,
        # the caller's iterator is the client's, not the engine's
        "owners": {"train_batch_fetch": "client",
                   "train_batch_input": "input", "train_step": "dispatch"},
    },
}
#: markers in an operation's ``tf_op`` name stack, first match wins: the
#: recompute of a checkpointed block runs inside the backward pass, so it
#: is asked for first. ``transpose(`` is JAX's mark of a transposed
#: (backward) computation, ``transpose(jvp(..))`` or, behind a custom
#: derivative, ``transpose(loss_and_grads)/jvp(..)``; the array operation
#: of that name is a leaf ``.../transpose`` with no parenthesis
PHASES = (("recompute", ("rematted_computation",)),
          ("bwd", ("transpose(",)),
          ("optimizer", ("/optimizer/", "zero_param_update", "grad_reduce",
                         "grad_accumulate")),
          ("fwd", ("loss_and_grads",)))


# ------------------------------------------------------------------ #
# the wire format, as far as the metadata needs it
# ------------------------------------------------------------------ #
def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, a slice of the buffer for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield tag >> 3, wire, val


def op_scopes(path: str, chip: int = 0) -> Dict[Tuple[int, str], str]:
    """(program id, instruction text) -> ``tf_op`` of every operation in
    the event metadata of ``/device:TPU:<chip>``. A fusion's ``tf_op`` is
    the one XLA kept for it: that of its root. ``XSpace.planes = 1``;
    ``XPlane``: ``name = 2, event_metadata = 4, stat_metadata = 5`` (maps:
    ``value = 2``); ``XEventMetadata``: ``name = 2, stats = 5``;
    ``XStatMetadata``: ``id = 1, name = 2``; ``XStat``: ``metadata_id = 1,
    uint64_value = 3, str_value = 5``. The planes' lines are skipped
    unread, so this costs the metadata's size, not the trace's."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    want = f"/device:TPU:{chip}".encode()
    for field, wire, plane in _fields(space):
        if field != 1 or wire != 2:
            continue
        parts = [(f2, v) for f2, w2, v in _fields(plane) if f2 in (2, 4, 5)]
        if not any(f2 == 2 and bytes(v) == want for f2, v in parts):
            continue
        stat_names: Dict[int, str] = {}
        for f2, entry in parts:
            if f2 != 5:
                continue
            for f3, _, meta in _fields(entry):
                if f3 == 2:
                    got = {f4: v for f4, _, v in _fields(meta)}
                    stat_names[got.get(1)] = bytes(got.get(2, b"")).decode()
        out: Dict[Tuple[int, str], str] = {}
        for f2, entry in parts:
            if f2 != 4:
                continue
            for f3, _, meta in _fields(entry):
                if f3 != 2:
                    continue
                name, program, scope = "", None, None
                for f4, _, v in _fields(meta):
                    if f4 == 2:
                        name = bytes(v).decode(errors="replace")
                    elif f4 == 5:
                        stat = {f5: v5 for f5, _, v5 in _fields(v)}
                        kind = stat_names.get(stat.get(1))
                        if kind == "tf_op" and 5 in stat:
                            scope = bytes(stat[5]).decode(errors="replace")
                        elif kind == "program_id" and 3 in stat:
                            program = stat[3]
                if scope is not None:
                    out[(program, name)] = scope
        return out
    return {}


def phase_of(scope: Optional[str]) -> Optional[str]:
    if not scope:
        return None
    for phase, marks in PHASES:
        if any(m in scope for m in marks):
            return phase
    return None


# ------------------------------------------------------------------ #
# the trace's events with the stats the join needs
# ------------------------------------------------------------------ #
Run = collections.namedtuple("Run", "name start end run_id")
Enq = collections.namedtuple("Enq", "start end")


class HostDevice:
    """Program runs of chip 0 with their ``run_id``, the enqueue and the
    completion callback of each run id on device ordinal 0, and the host
    spans by name. Times in seconds, each on its own clock."""

    def __init__(self, path: str, span_names, chip: int = 0):
        import jax

        self.runs: List[Run] = []
        self.enqueue: Dict[int, Enq] = {}
        self.complete: Dict[int, Enq] = {}
        self.spans: Dict[str, List[tr.Interval]] = {n: [] for n in span_names}
        def bounds(e) -> tr.Interval:
            lo = e.start_ns * 1e-9
            return lo, lo + e.duration_ns * 1e-9

        device = f"/device:TPU:{chip}"
        # (a second reading of the file: the reducer keeps no event stats)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == device:
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        self.runs += [
                            Run(e.name, *bounds(e),
                                dict(e.stats).get("run_id"))
                            for e in line.events]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name in self.spans:
                            self.spans[e.name].append(bounds(e))
                        elif e.name in (ENQUEUE, COMPLETE):
                            stats = dict(e.stats)
                            if stats.get("device_ordinal", 0) == chip:
                                into = self.enqueue if e.name == ENQUEUE \
                                    else self.complete
                                into[stats.get("run_id")] = Enq(*bounds(e))
        self.runs.sort(key=lambda r: r.start)
        for v in self.spans.values():
            v.sort()

    def last_started(self, name: str, at: float) -> Optional[int]:
        """Index of the last span ``name`` that began by the host instant
        ``at`` (it may have ended by then)."""
        i = bisect.bisect_right(self.spans[name], (at, float("inf"))) - 1
        return i if i >= 0 else None


def trace_file(run) -> Optional[str]:
    from benchmarks import harness

    found = sorted(glob.glob(os.path.join(
        harness.OUT_DIR, "trace", run.cell.name, "**", "*.xplane.pb"),
        recursive=True))
    return found[-1] if found else None


# ------------------------------------------------------------------ #
# the split of one host interval among the spans that cover it
# ------------------------------------------------------------------ #
def split_interval(lo: float, hi: float,
                   spans: Dict[str, List[tr.Interval]]
                   ) -> Dict[Optional[str], float]:
    """Seconds of [lo, hi] under each span name, every instant given to
    the innermost span that covers it (the one that started last);
    ``None`` keys what no span covers. ``spans``: sorted, by name."""
    inside = []
    for name, ivs in spans.items():
        # a span that covers ``lo`` started before it: no span is 60 s long
        for a, b in ivs[bisect.bisect_left(ivs, (lo - 60.0,)):]:
            if a >= hi:
                break
            if b > lo:
                inside.append((a, b, name))
    cuts = sorted({lo, hi, *(min(max(x, lo), hi)
                             for a, b, _ in inside for x in (a, b))})
    out: Dict[Optional[str], float] = collections.defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, n) for s, e, n in inside if s <= a and e >= b]
        out[max(cover)[1] if cover else None] += b - a
    return out


# ------------------------------------------------------------------ #
def chain(hd: HostDevice, spec: Dict[str, Any], window: tr.Interval
          ) -> Tuple[Dict[str, Any], List[Dict[str, Any]], List[Run]]:
    """The gap chain of one traced stretch: a report for ``extras`` and,
    where runs, enqueues and spans matched one to one, one row per pair of
    consecutive runs (the device gap and each owner's seconds) with the
    runs that were joined; no rows otherwise. ``spec``: an entry of
    ``CHAINS``."""
    dispatch, fences = hd.spans[spec["dispatch"]], hd.spans[spec["fence"]]
    runs = [r for r in hd.runs if r.name.startswith(spec["module"])
            and r.start >= window[0] and r.end <= window[1]]
    report: Dict[str, Any] = {"runs": len(runs), "spans": len(dispatch),
                              "unmatched": {}}
    if not runs or not dispatch:
        report["unmatched"] = {"reason": (
            f"no {spec['module']} run or no {spec['dispatch']} span in the "
            "traced stretch (a program without the spans: nothing to "
            "chain)")}
        return report, [], []
    no_enqueue = [r.run_id for r in runs if r.run_id not in hd.enqueue]
    joined, homeless, seen = [], [], collections.Counter()
    for r in runs:
        enq = hd.enqueue.get(r.run_id)
        i = None if enq is None else hd.last_started(spec["dispatch"],
                                                     enq.start)
        if enq is not None and i is None:
            homeless.append(r.run_id)
        if i is not None:
            seen[i] += 1
            joined.append((r, enq, i))
    shared = [i for i, n in seen.items() if n > 1]
    report["matched"] = len(joined)
    # the runtime enqueues on a thread of its own: where the jitted call
    # returned first, the enqueue lies after its span (counted, not an error)
    report["enqueued_after_dispatch_returned"] = sum(
        1 for _, enq, i in joined if enq.start > dispatch[i][1])
    if no_enqueue or homeless or shared:
        report["unmatched"] = {
            "runs_without_enqueue": no_enqueue[:10],
            "enqueues_before_any_span": homeless[:10],
            "spans_with_several_runs": shared[:10]}
        return report, [], []

    # the two clocks: host = device + offset. A run starts after its
    # enqueue began, and its completion callback runs after it ended
    highs = [hd.complete[r.run_id].start - r.end for r, _, _ in joined
             if r.run_id in hd.complete]
    report["host_minus_device_ms"] = {
        "at_least": 1e3 * max(enq.start - r.start for r, enq, _ in joined),
        "at_most": 1e3 * min(highs) if highs else None}

    rows = []
    for (r0, _, i0), (r1, enq1, i1) in zip(joined, joined[1:]):
        # the fence of run k: the first fence span that starts after the
        # dispatch span of k did, or the one that encloses it
        j = bisect.bisect_left(fences, (dispatch[i0][0],))
        if spec["fence_encloses_dispatch"]:
            j -= 1
        # the host's part ends at the enqueue, or where the dispatching
        # span returned if the runtime had not enqueued by then: what
        # follows is the runtime's own launch
        start, end = fences[j][1] if 0 <= j < len(fences) else None, \
            min(enq1.start, dispatch[i1][1])
        if start is None or start > end:
            report["unmatched"] = {"no_fence_before_enqueue": r1.run_id}
            return report, [], []
        row: Dict[str, Any] = collections.defaultdict(float)
        row["by_span"] = {}
        for name, secs in split_interval(start, end, hd.spans).items():
            row[spec["owners"].get(name, "client")] += secs
            row["by_span"][str(name)] = secs
        row["gap"] = r1.start - r0.end
        row["runtime"] = row["gap"] - (end - start)
        rows.append(row)
    report["negative_runtime"] = sum(1 for r in rows if r["runtime"] < 0)
    return report, rows, [r for r, _, _ in joined]


def ticks(hd: HostDevice, spec: Dict[str, Any], joined: List[Run],
          window: tr.Interval) -> List[Dict[str, Any]]:
    """One row per joined run of the tick program (``chain``'s) whose tick
    the stretch holds whole on both clocks (the run on the device's, as
    ``chain`` saw to; span and mark on the host's): the wall time of the
    ``serving_tick`` span around its dispatching span, the device time of
    the run, and whether the tick held prompt rows (the runner's mark
    that follows the span, before the next tick begins). Run and span are
    joined by ``run_id`` and by enclosure on the host's clock, never by
    order or across the two clocks, so a stretch that cuts a tick on one
    clock and not on the other loses that tick and no other."""
    whole = hd.spans[spec["tick"]]
    marks = sorted((a, mixed) for name, mixed in spec["marks"].items()
                   for a, _ in hd.spans[name])
    rows = []
    for r in joined:
        lo, hi = hd.spans[spec["dispatch"]][hd.last_started(
            spec["dispatch"], hd.enqueue[r.run_id].start)]
        t = hd.last_started(spec["tick"], lo)
        if t is None or whole[t][1] < hi or whole[t][0] < window[0]:
            continue                  # the stretch began inside this tick
        m = bisect.bisect_left(marks, (whole[t][1],))
        if m == len(marks) or marks[m][0] > window[1] or (
                t + 1 < len(whole) and marks[m][0] > whole[t + 1][0]):
            continue                  # it ended before the tick's mark
        rows.append({"wall": whole[t][1] - whole[t][0],
                     "device": r.end - r.start, "mixed": marks[m][1]})
    return rows


def idle_check(rows, joined: List[Run], reduced, chip: int
               ) -> Dict[str, Any]:
    """Gaps plus the idle time inside the program's runs, against the idle
    time the reducer reads (1 - union of leaf operations) over the same
    stretch: they differ when something else ran between two runs."""
    lo, hi = joined[0].start, joined[-1].end
    busy = tr.union(tr.clip(((o.start, o.end) for o in reduced.ops[chip]),
                            lo, hi))
    in_runs = tr.union((r.start, r.end) for r in joined)
    in_program = tr.total(in_runs) - tr.intersect(in_runs, busy)
    gaps = sum(r["gap"] for r in rows)
    reducer_idle = (hi - lo) - tr.total(busy)
    return {"gaps_s": gaps, "in_program_idle_s": in_program,
            "reducer_idle_s": reducer_idle, "stretch_s": hi - lo,
            "rel_diff": abs(gaps + in_program - reducer_idle)
            / reducer_idle if reducer_idle > 0 else 0.0}


def device_phases(joined: List[Run], reduced, scopes, chip: int
                  ) -> Dict[str, Any]:
    """Device seconds of the leaf operations of each joined run by phase
    (``PHASES``, from the operation's ``tf_op``). A collective under a
    phase's scope counts in that phase (``collectives_by_phase`` says how
    much of each phase they are); collectives and other operations that
    carry none of the marks are counted apart."""
    ops = reduced.ops[chip]
    starts = [o.start for o in ops]
    per_run, by_scope = [], collections.Counter()
    collectives = collections.Counter()
    for r in joined:
        # ``jit_train_step(<program id>)``
        program = int(r.name[r.name.index("(") + 1:-1]) \
            if r.name.endswith(")") else None
        row = collections.Counter()
        for o in ops[bisect.bisect_left(starts, r.start):
                     bisect.bisect_right(starts, r.end)]:
            scope = scopes.get((program, o.text))
            phase = phase_of(scope)
            if phase is None:
                phase = "collective" if o.is_collective else "unscoped"
            row[phase] += o.seconds
            by_scope[leaf_scope(scope)] += o.seconds
            if o.is_collective:
                collectives[phase] += o.seconds
        per_run.append(row)
    busy = sum(sum(r.values()) for r in per_run)
    return {"per_run": per_run,
            "collectives_by_phase": dict(collectives),
            "unscoped_share": sum(r["unscoped"] for r in per_run) / busy
            if busy else None,
            "seconds_by_scope": dict(by_scope.most_common(12))}


def leaf_scope(scope: Optional[str]) -> str:
    """The model's own scope in a name stack (``attn``, ``mlp``, ...)."""
    for part in reversed((scope or "").split("/")):
        for known in ("attn", "mlp", "embed", "lm_head_loss", "lm_head",
                      "sample", "optimizer", "grad_reduce",
                      "grad_accumulate"):
            if part == known or part == f"jvp({known})":
                return known
    return "(none)"


# ------------------------------------------------------------------ #
# what the readers call
# ------------------------------------------------------------------ #
def span_means(run, hd: HostDevice, spec) -> Dict[str, Dict[str, float]]:
    """What the profiler adds to a span: each program span's mean over the
    untraced window (the program's ``span_seconds`` histogram) beside its
    mean in the traced stretch (the host events themselves)."""
    out = {}
    for name in sorted(spec["owners"]):
        row = {}
        hist = run.telemetry.histogram("span_seconds", span=name) \
            if run.telemetry is not None else None
        if hist is not None and hist[2] > 0:
            row["window"] = 1e6 * hist[3] / hist[2]
        if hd.spans[name]:
            row["traced"] = 1e6 * statistics.fmean(
                b - a for a, b in hd.spans[name])
        if row:
            out[name] = row
    return out


def analyse(run) -> Dict[str, Any]:
    """The run's chain, made once and kept in ``run.extras["gap_chain"]``
    (``metrics``: name -> per-tick or per-step median in ms, empty when
    nothing could be chained)."""
    if "gap_chain" in run.extras:
        return run.extras["gap_chain"]
    out: Dict[str, Any] = {"metrics": {}}
    run.extras["gap_chain"] = out
    kind = run.cell.runner
    path = trace_file(run) if run.trace is not None else None
    if path is None or kind not in CHAINS or not run.trace.ops:
        out["unmatched"] = {"reason": "no trace of this run"}
        return out
    spec, chip = CHAINS[kind], run.trace.chips[0]
    hd = HostDevice(path, set(spec["owners"]) | {spec["fence"]}
                    | set(spec.get("marks", ())), chip)
    report, rows, joined = chain(hd, spec, run.trace.window)
    out.update(report)
    if "marks" in spec:
        # rows for ``readers.traced_ticks``; too many for the report
        run.cache["ticks"] = ticks(hd, spec, joined, run.trace.window)
        out["ticks_whole"] = len(run.cache["ticks"])
    out["span_mean_us"] = span_means(run, hd, spec)
    if not rows:
        return out
    med = lambda xs: 1e3 * statistics.median(xs)   # noqa: E731
    for owner in sorted({*spec["owners"].values(), "client", "runtime"}):
        out["metrics"][f"gap_{owner}_ms"] = med([r[owner] for r in rows])
    out["gap_ms"] = {"median": med([r["gap"] for r in rows]),
                     "mean": 1e3 * statistics.fmean(r["gap"] for r in rows),
                     "pairs": len(rows)}
    out["span_ms_median"] = {
        n: med([r["by_span"].get(n, 0.0) for r in rows])
        for n in sorted({n for r in rows for n in r["by_span"]})}
    out["idle_check"] = idle_check(rows, joined, run.trace, chip)
    if kind == "train":
        phases = device_phases(joined, run.trace, op_scopes(path, chip),
                               chip)
        for phase in ("fwd", "recompute", "bwd", "optimizer"):
            out["metrics"][f"train_dev_{phase}_ms"] = med(
                [r[phase] for r in phases["per_run"]])
        out["device_ms_per_step"] = {
            p: med([r[p] for r in phases["per_run"]])
            for p in ("collective", "unscoped")}
        out["unscoped_share"] = phases["unscoped_share"]
        out["collective_ms_per_step_by_phase"] = {
            p: 1e3 * v / len(joined)
            for p, v in phases["collectives_by_phase"].items()}
        out["device_seconds_by_scope"] = phases["seconds_by_scope"]
    return out


def metric(run, name: str) -> Optional[float]:
    """One metric of the chain, None where nothing could be chained (no
    trace in this run, a program that has no such spans, a join that did
    not match one to one)."""
    if run.trace is None:
        return None
    return analyse(run)["metrics"].get(name)
