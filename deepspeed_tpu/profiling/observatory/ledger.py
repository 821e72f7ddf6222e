"""The compiled-collective ledger: every wire byte of a compiled program.

``build_ledger`` turns compiled-HLO text into per-kind / per-subsystem
totals with predicted bandwidths per the shared busbw convention
(``comm/bandwidth.py``); ``ledger_for_engine`` / ``ledger_for_fastgen``
lower the LIVE train step / FastGen tick (same builders the hot path
dispatches) and cross-check against ``compiled.cost_analysis()``.

Attribution: XLA preserves the jax call path in each op's
``metadata.op_name`` (e.g. ``jit(train_step)/.../transpose(...)/psum``).
The subsystem rules are substring heuristics over that path plus the
engine's ZeRO stage — documented, testable, and honest about being
heuristics (anything unmatched lands in ``"other"``, never dropped):

* ``zero_param_update`` (checked FIRST — outermost scope): collectives
  traced under the ``zero_param_update`` name scope — the step-phase
  overlap's bucketed weight update and its DEFERRED post-update param
  publish (engine ``_apply_update`` /
  ``compressed.publish_gather_tree_fn``); the deferred qwZ gather nests
  its ``qwz_wire`` mark inside this scope and bills to the update
  phase, not the forward;
* quantized wire (next — most specific of the rest): the ZeRO++ wire
  kernels trace under ``qgz_wire`` / ``qwz_wire`` name scopes
  (``parallel/compressed.py``; the wire step's exact-branch parameter
  gather marks ``zpp_gather``), so the int8 blocks AND their fp32
  scale companions attribute to ``zero_grad_sync`` /
  ``zero_param_gather``; an int8 (s8/u8) payload without the scope
  still routes by dtype — all-to-all/reduce-scatter →
  ``zero_grad_sync``, all-gather → ``zero_param_gather`` (nothing else
  in the step moves int8);
* ``moe_dispatch`` — path mentions moe/expert/router/dispatch/combine
  (an all-to-all WITHOUT those marks and not on the quantized wire is
  partitioner resharding → ``other``);
* ``pipeline_handoff`` — collective-permute, or path mentions
  ppermute/pipeline;
* ``zero_grad_sync`` — reduce-scatter / all-reduce on the backward path
  (jax marks the transpose) or in the update;
* ``zero_param_gather`` — all-gather at ZeRO-3 (per-use parameter
  gathers; at stage <3 an all-gather is batch/TP plumbing → ``other``).

Telemetry fold (metric catalog: README "Execution observatory"):
``comm_ledger_bytes_per_step`` / ``comm_ledger_collectives_per_step``
gauges labeled (program, kind, subsystem), the
``comm_ledger_unparsed_total`` counter, and
``comm_ledger_predicted_comm_seconds`` per program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from deepspeed_tpu.comm import bandwidth as BW
from deepspeed_tpu.profiling.observatory.hlo import (
    CollectiveOp,
    parse_hlo_collectives,
)

SUBSYSTEMS = ("zero_grad_sync", "zero_param_gather", "zero_param_update",
              "moe_dispatch", "pipeline_handoff", "other")

_MOE_MARKS = ("moe", "expert", "router", "dispatch", "combine")
_PIPE_MARKS = ("ppermute", "pipeline", "pipe_stage")
_BWD_MARKS = ("transpose(", "/vjp", "backward", "grad")
#: the ZeRO++ wire kernels' name scopes (parallel/compressed.py) — the
#: deliberate attribution channel for the quantized transport, covering
#: the fp32 scale companions dtype sniffing would miss
_WIRE_GRAD_MARK = "qgz_wire"
#: qwz_wire = quantized parameter gather; zpp_gather = the wire step's
#: exact-branch parameter gather (same collective, uncompressed wire)
_WIRE_PARAM_MARKS = ("qwz_wire", "zpp_gather")
#: the step-phase overlap scope (engine ``_apply_update`` /
#: ``compressed.publish_gather_tree_fn``): the bucketed weight update's
#: fenced applies and the DEFERRED post-update param publish. Checked
#: before the wire marks — the deferred qwZ gather nests qwz_wire
#: INSIDE this scope, and it must price as the update phase, not the
#: forward's.
_UPDATE_MARK = "zero_param_update"
#: the step function's own phase scopes (``runtime/engine.py``): every
#: op of the step sits under one of them, so they say nothing about
#: which subsystem issued a collective and must not trip the "grad" mark
_STEP_SCOPES = ("loss_and_grads", "grad_reduce")
_INT8_DTYPES = ("s8", "u8")


def attribute_subsystem(op: CollectiveOp, zero_stage: int = 0) -> str:
    """Heuristic issuing-subsystem attribution (module docstring has the
    rule table). Pure function of the op + ZeRO stage so fixtures test it
    without an engine."""
    path = f"{op.op_name or ''} {op.source_file or ''}".lower()
    for scope in _STEP_SCOPES:
        path = path.replace(scope, "")
    # update phase first — outermost scope: the deferred publish nests
    # the qwZ/zpp gather kernels inside zero_param_update, and those
    # collectives bill to the step phase (the fence-chained post-update
    # publish), not the forward
    if _UPDATE_MARK in path:
        return "zero_param_update"
    # quantized wire next — most specific of the rest. The qgZ mark
    # outranks qwZ (the hpZ replica hop reuses the quantized gather for
    # GRADIENTS, under an outer qgz_wire scope).
    if _WIRE_GRAD_MARK in path:
        return "zero_grad_sync"
    if any(m in path for m in _WIRE_PARAM_MARKS):
        return "zero_param_gather"
    if any(m in path for m in _MOE_MARKS):
        return "moe_dispatch"
    # dtype fallback only at stage >= 1, where qgZ/qwZ can be active —
    # at stage 0 the only int8 mover is the 1-bit transport's packed-sign
    # all-gather (no ZeRO partitioning to attribute to; honest "other")
    wire_int8 = op.dtype in _INT8_DTYPES and zero_stage >= 1
    if op.kind == BW.ALL_TO_ALL:
        if wire_int8:
            # nothing else in a ZeRO step moves int8: a scope-less s8
            # all-to-all is the qgZ chunk exchange, not resharding
            return "zero_grad_sync"
        # an all-to-all with no MoE/wire mark is partitioner resharding —
        # honest bucket is "other"
        return "other"
    if op.kind == BW.COLLECTIVE_PERMUTE or any(m in path for m in _PIPE_MARKS):
        return "pipeline_handoff"
    if op.kind in (BW.REDUCE_SCATTER, BW.ALL_REDUCE):
        return "zero_grad_sync"
    if op.kind == BW.ALL_GATHER:
        if wire_int8:
            return "zero_param_gather"       # qwZ int8 parameter blocks
        if zero_stage >= 3 or any(m in path for m in _BWD_MARKS):
            return "zero_param_gather"
    return "other"


@dataclasses.dataclass
class CollectiveLedger:
    """Parsed + attributed collectives of ONE compiled program."""

    program: str                      # "train_step" / "fastgen_tick" / ...
    ops: List[CollectiveOp]
    unparsed: int
    world: int                        # participants hint used for parsing
    zero_stage: int = 0
    #: matched -start/-done pairs (async-collective pass evidence; 0 on
    #: sync-only backends like the CPU tier — see hlo.count_async_pairs)
    async_pairs: int = 0
    #: cost_analysis cross-check (None = unavailable on this build)
    cost_flops: Optional[float] = None
    cost_bytes_accessed: Optional[float] = None
    #: the raw HLO text this ledger was parsed from ("" when the caller
    #: didn't keep it). hlolint's text-level rules (host-transfer,
    #: resharding-thrash) re-scan it so a live lint never pays a second
    #: lowering; deliberately NOT in ``to_dict`` — reports stay small.
    hlo_text: str = ""

    # ---------------- aggregations ---------------- #
    def totals_by_kind(self) -> Dict[str, Dict[str, float]]:
        """{kind: {count, bytes, bus_bytes}} — counts are per single
        execution of the program (one optimizer step / one tick)."""
        out: Dict[str, Dict[str, float]] = {}
        for op in self.ops:
            row = out.setdefault(op.kind,
                                 {"count": 0, "bytes": 0, "bus_bytes": 0.0})
            row["count"] += 1
            row["bytes"] += op.size_bytes
            row["bus_bytes"] += op.size_bytes * BW.busbw_factor(
                op.kind, op.group_size)
        return out

    def totals_by_subsystem(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for op in self.ops:
            sub = op.subsystem or "other"
            row = out.setdefault(sub, {"count": 0, "bytes": 0})
            row["count"] += 1
            row["bytes"] += op.size_bytes
        return out

    def total_bytes(self) -> int:
        return sum(op.size_bytes for op in self.ops)

    def predicted_comm_seconds(self, link_gbps: float) -> float:
        """Serialized wire-time prediction at ``link_gbps`` per chip —
        the roofline's comm leg (an upper bound: real schedules overlap)."""
        return sum(BW.predicted_seconds(op.kind, op.size_bytes,
                                        op.group_size, link_gbps)
                   for op in self.ops)

    def dominant_kind(self) -> Optional[str]:
        """The kind moving the most bus bytes (None when no collectives)."""
        totals = self.totals_by_kind()
        if not totals:
            return None
        return max(totals.items(), key=lambda kv: kv[1]["bus_bytes"])[0]

    def to_dict(self, link_gbps: Optional[float] = None,
                max_ops: int = 64) -> Dict[str, Any]:
        """JSON-ready view (the step report's ``ledger`` block)."""
        by_kind = {
            kind: {
                "count": int(row["count"]),
                "bytes": int(row["bytes"]),
                "bus_bytes": round(row["bus_bytes"], 1),
                **({"predicted_busbw_gbps": round(link_gbps, 2)}
                   if link_gbps else {}),
            }
            for kind, row in sorted(self.totals_by_kind().items())}
        out: Dict[str, Any] = {
            "program": self.program,
            "world": self.world,
            "zero_stage": self.zero_stage,
            "total_bytes": self.total_bytes(),
            "unparsed": self.unparsed,
            "async_pairs": self.async_pairs,
            "by_kind": by_kind,
            "by_subsystem": {
                k: {"count": int(v["count"]), "bytes": int(v["bytes"])}
                for k, v in sorted(self.totals_by_subsystem().items())},
            "ops": [
                {"kind": op.kind, "hlo_opcode": op.hlo_opcode,
                 "dtype": op.dtype, "shape": list(op.shape),
                 "size_bytes": op.size_bytes,
                 "group_size": op.group_size, "n_groups": op.n_groups,
                 "subsystem": op.subsystem, "op_name": op.op_name[:160]}
                for op in self.ops[:max_ops]],
        }
        if len(self.ops) > max_ops:
            out["ops_truncated"] = len(self.ops) - max_ops
        if link_gbps:
            out["link_gbps"] = link_gbps
            out["predicted_comm_seconds"] = round(
                self.predicted_comm_seconds(link_gbps), 6)
        if self.cost_flops is not None:
            out["cost_analysis"] = {
                "flops": self.cost_flops,
                "bytes_accessed": self.cost_bytes_accessed,
            }
        return out

    # ---------------- telemetry fold ---------------- #
    def fold_into_telemetry(self, link_gbps: Optional[float] = None) -> None:
        """Publish this program's ledger into the unified registry. Gauges
        are per-program absolutes (a re-fold after a re-compile overwrites,
        it never double-counts); only the unparsed counter accumulates.
        ``link_gbps`` prices the predicted-comm gauge (default: the chip's
        datasheet rate) — callers with an override pass it so the gauge and
        their report agree."""
        from deepspeed_tpu import telemetry

        bytes_g = telemetry.gauge(
            "comm_ledger_bytes_per_step",
            "full-tensor bytes each compiled collective moves per program "
            "execution (HLO ledger)")
        count_g = telemetry.gauge(
            "comm_ledger_collectives_per_step",
            "compiled collective ops per program execution (HLO ledger)")
        by: Dict[tuple, Dict[str, float]] = {}
        for op in self.ops:
            key = (op.kind, op.subsystem or "other")
            row = by.setdefault(key, {"count": 0, "bytes": 0})
            row["count"] += 1
            row["bytes"] += op.size_bytes
        for (kind, sub), row in by.items():
            bytes_g.set(row["bytes"], program=self.program, kind=kind,
                        subsystem=sub)
            count_g.set(row["count"], program=self.program, kind=kind,
                        subsystem=sub)
        if self.unparsed:
            telemetry.counter(
                "comm_ledger_unparsed_total",
                "collective-family HLO ops the ledger could not map to a "
                "known kind").inc(self.unparsed, program=self.program)
        telemetry.gauge(
            "comm_ledger_async_pairs_per_step",
            "matched async collective start/done pairs in the compiled "
            "program (0 = every collective lowered synchronous, e.g. the "
            "CPU backend)").set(self.async_pairs, program=self.program)
        link = link_gbps or BW.chip_link_gbps(_device_kind())
        telemetry.gauge(
            "comm_ledger_predicted_comm_seconds",
            "serialized wire-time prediction of one program execution at "
            "the chip's datasheet link bandwidth").set(
                self.predicted_comm_seconds(link), program=self.program)


def _device_kind() -> str:
    try:
        import jax

        return getattr(jax.devices()[0], "device_kind", "")
    except (ImportError, RuntimeError, IndexError):
        return ""   # no backend in stdlib-only contexts


def build_ledger(hlo_text: str, program: str = "program",
                 world: int = 1, zero_stage: int = 0,
                 cost_flops: Optional[float] = None,
                 cost_bytes_accessed: Optional[float] = None,
                 ) -> CollectiveLedger:
    """Parse + attribute: the pure-text entry point (fixtures, offline
    dumps, ``step-report --hlo-file``)."""
    from deepspeed_tpu.profiling.observatory.hlo import count_async_pairs

    ops, unparsed = parse_hlo_collectives(hlo_text, world_hint=world)
    for op in ops:
        op.subsystem = attribute_subsystem(op, zero_stage)
    return CollectiveLedger(program=program, ops=ops, unparsed=unparsed,
                            world=world, zero_stage=zero_stage,
                            async_pairs=count_async_pairs(hlo_text),
                            cost_flops=cost_flops,
                            cost_bytes_accessed=cost_bytes_accessed,
                            hlo_text=hlo_text)


# ------------------------------------------------------------------ #
# live-program lowering (engine / fastgen front ends)
# ------------------------------------------------------------------ #
def _lower_compiled(jitted, *abstract_args):
    """lower → compile → (hlo_text, costs, memory_stats). The compile is
    the price of ground truth; callers cache the resulting ledger."""
    from deepspeed_tpu.profiling.flops_profiler import normalize_costs

    lowered = jitted.lower(*abstract_args)
    compiled = lowered.compile()
    try:
        costs = normalize_costs(compiled.cost_analysis())
    except (RuntimeError, NotImplementedError, TypeError):
        costs = {}
    try:
        mem = compiled.memory_analysis()
    except (RuntimeError, NotImplementedError, AttributeError):
        mem = None
    return compiled.as_text(), costs, mem


def memory_stats_dict(mem: Any) -> Optional[Dict[str, float]]:
    """``CompiledMemoryStats`` → plain dict (None passes through)."""
    if mem is None:
        return None
    out = {}
    for key in ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes"):
        val = getattr(mem, key, None)
        if val is not None:
            out[key] = float(val)
    return out or None


#: opcodes hlolint's text-level rules scan (host-transfer vocabulary +
#: the collective families resharding-thrash pairs up). The engine cache
#: below trims the retained ``hlo_text`` to these lines — a real model's
#: full dump is tens of MB and the observatory cache lives as long as
#: the engine. Cross-reference: ``analysis/hlolint/rules.py``
#: (_HOST_OPCODES / _THRASH_FAMILIES).
_LINT_TEXT_OPCODES = ("infeed", "outfeed", "send", "recv", "send-done",
                      "recv-done", "custom-call")
_LINT_TEXT_PREFIXES = ("all-", "reduce-scatter", "collective-")


def _trim_lint_text(hlo_text: str) -> str:
    """The subset of op lines hlolint's text rules read, with every
    dropped line replaced by an EMPTY line: line numbers in lint
    findings must still point at the real dump (an operator re-dumping
    the step and jumping to the cited line has to land on the cited
    op). Memory stays bounded — the blanks cost one newline each."""
    from deepspeed_tpu.profiling.observatory.hlo import _OP_LINE

    keep = []
    for i, line in enumerate(hlo_text.splitlines()):
        if i == 0 or line.startswith("HloModule") \
                or "input_output_alias=" in line \
                or "entry_computation_layout=" in line:
            # the module header identifies the program AND carries the
            # entry's donation directives + parameter/output layout —
            # memlint's text tier reads both from this cached text
            keep.append(line)
            continue
        m = _OP_LINE.match(line)
        op = m.group("opcode") if m else ""
        keep.append(line if op in _LINT_TEXT_OPCODES
                    or op.startswith(_LINT_TEXT_PREFIXES) else "")
    return "\n".join(keep)


def ledger_for_engine(engine, fold: bool = True,
                      seq_len: Optional[int] = None,
                      link_gbps: Optional[float] = None):
    """Ledger of the engine's LIVE fused train step (the same builder
    ``_dispatch_train_step`` would pick — onebit / compressed wire
    variants included), plus memory stats for the report.

    ``seq_len``: the sequence length the engine actually trains at —
    activation-dependent collectives (MoE dispatch, TP gathers) scale
    with it, so callers that know their data shape (bench, the CLI) pass
    it; the fallback is the model spec's max. Returns ``(ledger,
    memory_stats_dict_or_None)``. Cached per (gas, batch, seq) on the
    engine — one lowering each; ``fold=True`` publishes the
    ``comm_ledger_*`` metrics (priced at ``link_gbps`` when given).
    """
    gas = engine.gradient_accumulation_steps()
    mb = engine.train_micro_batch_size() * engine.dp_world_size
    seq = seq_len or getattr(engine.model_spec, "seq_len", None) or 128
    cache = getattr(engine, "_observatory_cache", None)
    if cache is None:
        cache = engine._observatory_cache = {}
    cached = cache.get((gas, mb, seq))
    if cached is None:
        import jax.numpy as jnp

        key = ("train_step", gas)
        fn = engine._compiled.get(key)
        if fn is None:
            # the engine's ONE builder-selection point (wire format ×
            # overlap compose inside it): the ledgered program is always
            # the program _dispatch_train_step runs — ledgering the plain
            # step for a wire variant would report the reduction away
            fn = engine._select_step_builder(gas)
        batch = {"tokens": jnp.zeros((gas, mb, seq), jnp.int32)}
        with engine.mesh:
            hlo_text, costs, mem = _lower_compiled(fn, engine.state, batch)
        ledger = build_ledger(
            hlo_text, program="train_step",
            world=engine.dp_world_size, zero_stage=engine.zero_stage,
            cost_flops=(float(costs["flops"]) if "flops" in costs else None),
            cost_bytes_accessed=(float(costs["bytes accessed"])
                                 if "bytes accessed" in costs else None))
        # the cache outlives this call by the engine's lifetime: keep
        # only the lines hlolint's text rules scan, not the full dump
        ledger.hlo_text = _trim_lint_text(hlo_text)
        cached = cache[(gas, mb, seq)] = (ledger, memory_stats_dict(mem))
    if fold:
        cached[0].fold_into_telemetry(link_gbps)
    return cached


def ledger_for_fastgen(engine, n_tokens: Optional[int] = None,
                       fold: bool = True):
    """Ledger of one FastGen mixed tick at the given token-budget bucket
    (default: the engine's full ``token_budget`` tier). Under TP the tick
    program carries the row/col-parallel collectives GSPMD inserted;
    single-replica serving legitimately ledgers empty.

    Cached per bucket (same ``(Tn, mb)`` key as the tick programs); a
    non-default bucket folds under ``program="fastgen_tick_t<N>"`` so the
    two tiers' gauges don't overwrite each other. Returns ``(ledger,
    memory_stats_dict_or_None)``.
    """
    import numpy as np

    tn = engine._bucket(n_tokens or engine.token_budget)
    key = (tn, engine.max_blocks_per_seq)
    cache = getattr(engine, "_observatory_cache", None)
    if cache is None:
        cache = engine._observatory_cache = {}
    cached = cache.get(key)
    if cached is None:
        tick = engine._ticks.get(key)
        if tick is None:
            tick = engine._build_tick(*key)
        # the tick's one operand, laid out by the engine
        rows = np.zeros((tn,), np.int32)
        packed = engine._pack_tick(
            rows, rows, np.zeros(key, np.int32), np.zeros((2,), np.uint32))
        hlo_text, costs, mem = _lower_compiled(
            tick, engine.params, engine.pool, packed)
        world = 1
        if engine.mesh is not None:
            from deepspeed_tpu.comm.mesh import TENSOR_AXIS

            world = engine.mesh.shape.get(TENSOR_AXIS, 1)
        program = ("fastgen_tick"
                   if tn == engine._bucket(engine.token_budget)
                   else f"fastgen_tick_t{tn}")
        ledger = build_ledger(
            hlo_text, program=program, world=world, zero_stage=0,
            cost_flops=(float(costs["flops"]) if "flops" in costs else None),
            cost_bytes_accessed=(float(costs["bytes accessed"])
                                 if "bytes accessed" in costs else None))
        ledger.hlo_text = _trim_lint_text(hlo_text)   # cache-lifetime bound
        cached = cache[key] = (ledger, memory_stats_dict(mem))
    if fold:
        cached[0].fold_into_telemetry()
    return cached
