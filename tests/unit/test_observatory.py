"""XLA execution-observatory tests (``deepspeed_tpu/profiling/observatory``).

The ledger-parser tests run over COMMITTED HLO-text fixtures
(``observatory_fixtures/``: the real zero2 / zero3 / MoE tiny-model step
dumps, trimmed to the module header + every collective-bearing line,
generated once under JAX_PLATFORMS=cpu with 8 forced host devices) so op
extraction, byte math, and replica-group attribution are pinned without
recompiling anything. The live e2e tests lower the real train step /
step report on the 8-device virtual mesh — the same path tier-1's
acceptance criterion exercises through ``tools/step-report``.
"""
import json
import math
import os
import subprocess
import sys

import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.comm import bandwidth as BW
from deepspeed_tpu.profiling.observatory import (
    build_ledger,
    estimate_overlap,
    overlap_from_intervals,
    parse_hlo_collectives,
)
from deepspeed_tpu.profiling.observatory.ledger import attribute_subsystem
from deepspeed_tpu.profiling.observatory.report import validate_report

pytestmark = pytest.mark.observatory

FIXTURES = os.path.join(os.path.dirname(__file__), "observatory_fixtures")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fixture_text(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


# --------------------------------------------------------------------- #
# HLO parser: op extraction / byte math / replica groups
# --------------------------------------------------------------------- #
class TestHloParser:
    def test_zero3_fixture_kinds_and_counts(self):
        ops, unparsed = parse_hlo_collectives(
            fixture_text("zero3_tiny_step.hlo.txt"), world_hint=8)
        assert unparsed == 0
        kinds = {op.kind for op in ops}
        # the zero3 step carries at least grad-sync reductions AND
        # param gathers — the two kinds the acceptance criterion names
        assert BW.ALL_REDUCE in kinds and BW.ALL_GATHER in kinds
        assert all(op.size_bytes > 0 for op in ops)

    def test_zero2_vs_zero3_fixtures_both_parse(self):
        for name in ("zero2_tiny_step.hlo.txt", "zero3_tiny_step.hlo.txt",
                     "moe_tiny_step.hlo.txt"):
            ops, unparsed = parse_hlo_collectives(fixture_text(name),
                                                  world_hint=8)
            assert ops, f"{name}: no collectives parsed"
            assert unparsed == 0, f"{name}: {unparsed} unparsed"

    def test_byte_math_all_gather_takes_full_tensor(self):
        # all-gather: shard in, full out — size must be the GATHERED side
        line = ('  %all-gather.1 = f32[8,32,64]{1,0,2} all-gather('
                'f32[8,32,8]{1,0,2} %x), channel_id=1, '
                'replica_groups=[1,8]<=[8], dimensions={2}')
        ops, unparsed = parse_hlo_collectives(line, world_hint=8)
        assert len(ops) == 1 and unparsed == 0
        assert ops[0].kind == BW.ALL_GATHER
        assert ops[0].size_bytes == 8 * 32 * 64 * 4
        assert ops[0].shape == (8, 32, 64)

    def test_byte_math_reduce_scatter_takes_full_tensor(self):
        # reduce-scatter: full in, shard out — size is the OPERAND side
        line = ('  %reduce-scatter.2 = f32[8,8]{1,0} reduce-scatter('
                'f32[64,8]{1,0} %g), channel_id=2, '
                'replica_groups=[1,8]<=[8], dimensions={0}, '
                'to_apply=%add.1')
        ops, _ = parse_hlo_collectives(line, world_hint=8)
        assert ops[0].kind == BW.REDUCE_SCATTER
        assert ops[0].size_bytes == 64 * 8 * 4

    def test_byte_math_tuple_all_to_all_sums_operands(self):
        # the moe fixture's tuple-form all-to-all: one chunk per
        # destination, each a separate operand — bytes are the SUM
        ops, _ = parse_hlo_collectives(
            fixture_text("moe_tiny_step.hlo.txt"), world_hint=8)
        a2a = [op for op in ops if op.kind == BW.ALL_TO_ALL]
        assert a2a
        f32_chunks = [op for op in a2a if op.dtype == "f32"
                      and op.shape == (1, 64, 64)]
        assert f32_chunks
        assert f32_chunks[0].size_bytes == 4 * (1 * 64 * 64) * 4

    def test_bf16_dtype_width(self):
        line = ('  %all-reduce.9 = bf16[16,4]{1,0} all-reduce('
                'bf16[16,4]{1,0} %x), replica_groups={{0,1,2,3}}, '
                'to_apply=%add')
        ops, _ = parse_hlo_collectives(line)
        assert ops[0].dtype == "bf16"
        assert ops[0].size_bytes == 16 * 4 * 2

    def test_replica_groups_explicit_and_iota(self):
        explicit = ('  %all-reduce.3 = f32[4]{0} all-reduce(f32[4]{0} %x), '
                    'replica_groups={{0,1},{2,3},{4,5},{6,7}}, '
                    'to_apply=%add')
        iota = ('  %all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %x), '
                'replica_groups=[2,4]<=[8], to_apply=%add')
        absent = ('  %all-reduce.5 = f32[4]{0} all-reduce(f32[4]{0} %x), '
                  'to_apply=%add')
        (op_e,), _ = parse_hlo_collectives(explicit)
        assert (op_e.group_size, op_e.n_groups) == (2, 4)
        (op_i,), _ = parse_hlo_collectives(iota)
        assert (op_i.group_size, op_i.n_groups) == (4, 2)
        (op_a,), _ = parse_hlo_collectives(absent, world_hint=8)
        assert (op_a.group_size, op_a.n_groups) == (8, 1)

    def test_async_start_done_counted_once(self):
        text = "\n".join([
            '  %all-gather-start.1 = (f32[8,8]{1,0}, f32[64,8]{1,0}) '
            'all-gather-start(f32[8,8]{1,0} %p), channel_id=1, '
            'replica_groups=[1,8]<=[8], dimensions={0}',
            '  %all-gather-done.1 = f32[64,8]{1,0} all-gather-done('
            '(f32[8,8]{1,0}, f32[64,8]{1,0}) %all-gather-start.1)',
        ])
        ops, unparsed = parse_hlo_collectives(text, world_hint=8)
        assert len(ops) == 1 and unparsed == 0
        assert ops[0].hlo_opcode == "all-gather-start"
        assert ops[0].kind == BW.ALL_GATHER
        # the async tuple is (shard_in, full_out): the byte convention
        # wants the FULL gathered tensor, not the input shard
        assert ops[0].size_bytes == 64 * 8 * 4

    def test_tpu_tiled_layout_operand_scan(self):
        # TPU dumps print tiled layouts with NESTED PARENS — the operand
        # scan must not stop at the ')' inside T(8,128), or reduce-scatter
        # falls back to its shard-sized result (1/world undercount)
        line = ('  %reduce-scatter.7 = f32[512]{0:T(256)} reduce-scatter('
                'f32[4096]{0:T(8,128)} %grad), channel_id=3, '
                'replica_groups=[1,8]<=[8], dimensions={0}, '
                'to_apply=%add.2')
        ops, unparsed = parse_hlo_collectives(line, world_hint=8)
        assert len(ops) == 1 and unparsed == 0
        assert ops[0].size_bytes == 4096 * 4

    def test_op_name_metadata_extracted(self):
        ops, _ = parse_hlo_collectives(
            fixture_text("zero3_tiny_step.hlo.txt"), world_hint=8)
        named = [op for op in ops if op.op_name]
        assert named, "fixture metadata op_name not extracted"
        assert any("train_step" in op.op_name for op in named)

    def test_non_collective_lines_ignored(self):
        text = ('  %add.905 = f32[] add(f32[] %a, f32[] %b)\n'
                '  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop\n')
        ops, unparsed = parse_hlo_collectives(text)
        assert ops == [] and unparsed == 0


class TestUnknownOpGuard:
    def test_unknown_collective_degrades_not_raises(self):
        # a novel XLA opcode in the collective family must parse with
        # kind="unknown" and count as unparsed — never raise
        line = ('  %all-frobnicate.1 = f32[64]{0} all-frobnicate('
                'f32[64]{0} %x), replica_groups={{0,1,2,3}}')
        ops, unparsed = parse_hlo_collectives(line)
        assert len(ops) == 1
        assert ops[0].kind == BW.UNKNOWN
        assert unparsed == 1

    def test_known_family_variants_map(self):
        line = ('  %collective-broadcast.1 = f32[64]{0} '
                'collective-broadcast(f32[64]{0} %x), '
                'replica_groups={{0,1,2,3}}')
        ops, unparsed = parse_hlo_collectives(line)
        assert ops[0].kind == BW.BROADCAST and unparsed == 0

    def test_unknown_feeds_unparsed_counter_on_fold(self):
        from deepspeed_tpu import telemetry

        line = ('  %all-frobnicate.2 = f32[64]{0} all-frobnicate('
                'f32[64]{0} %x), replica_groups={{0,1}}')
        ledger = build_ledger(line, program="guard_test", world=2)
        assert ledger.unparsed == 1
        ledger.fold_into_telemetry()
        ctr = telemetry.counter(
            "comm_ledger_unparsed_total",
            "collective-family HLO ops the ledger could not map to a "
            "known kind")
        assert ctr.value(program="guard_test") >= 1


# --------------------------------------------------------------------- #
# subsystem attribution
# --------------------------------------------------------------------- #
def _op(kind, op_name="", hlo_opcode=None):
    from deepspeed_tpu.profiling.observatory.hlo import CollectiveOp

    return CollectiveOp(kind=kind, hlo_opcode=hlo_opcode or kind,
                        result="r", dtype="f32", shape=(4,), size_bytes=16,
                        group_size=8, n_groups=1, channel_id=None,
                        op_name=op_name)


class TestAttribution:
    def test_moe_marks_win_over_kind(self):
        op = _op(BW.ALL_TO_ALL, "jit(train_step)/.../moe/all_to_all")
        assert attribute_subsystem(op) == "moe_dispatch"

    def test_plain_all_to_all_is_other(self):
        assert attribute_subsystem(_op(BW.ALL_TO_ALL)) == "other"

    def test_collective_permute_is_pipeline(self):
        assert attribute_subsystem(
            _op(BW.COLLECTIVE_PERMUTE)) == "pipeline_handoff"

    def test_reduce_ops_are_grad_sync(self):
        assert attribute_subsystem(_op(BW.REDUCE_SCATTER)) == "zero_grad_sync"
        assert attribute_subsystem(_op(BW.ALL_REDUCE)) == "zero_grad_sync"

    def test_all_gather_stage_dependent(self):
        assert attribute_subsystem(
            _op(BW.ALL_GATHER), zero_stage=3) == "zero_param_gather"
        assert attribute_subsystem(
            _op(BW.ALL_GATHER), zero_stage=2) == "other"
        # stage-2 gather on the backward path still bills to params
        bwd = _op(BW.ALL_GATHER, "jit(train_step)/transpose(jvp)/dot")
        assert attribute_subsystem(bwd, zero_stage=2) == "zero_param_gather"

    def test_moe_fixture_attributes_dispatch(self):
        ledger = build_ledger(fixture_text("moe_tiny_step.hlo.txt"),
                              program="moe", world=8, zero_stage=2)
        subs = ledger.totals_by_subsystem()
        assert "moe_dispatch" in subs
        assert subs["moe_dispatch"]["bytes"] > 0


# --------------------------------------------------------------------- #
# ledger aggregation + telemetry fold
# --------------------------------------------------------------------- #
class TestLedger:
    def test_totals_and_dominant(self):
        ledger = build_ledger(fixture_text("zero3_tiny_step.hlo.txt"),
                              program="zero3", world=8, zero_stage=3)
        by_kind = ledger.totals_by_kind()
        assert len(by_kind) >= 2
        assert ledger.total_bytes() == sum(
            r["bytes"] for r in by_kind.values())
        assert ledger.dominant_kind() in by_kind
        for row in by_kind.values():
            assert row["bus_bytes"] <= row["bytes"] * 2  # factor <= 2

    def test_predicted_comm_seconds_scales_with_link(self):
        ledger = build_ledger(fixture_text("zero3_tiny_step.hlo.txt"),
                              program="zero3", world=8, zero_stage=3)
        slow = ledger.predicted_comm_seconds(10.0)
        fast = ledger.predicted_comm_seconds(100.0)
        assert slow > 0
        assert math.isclose(slow / fast, 10.0, rel_tol=1e-9)

    def test_to_dict_shape(self):
        ledger = build_ledger(fixture_text("zero2_tiny_step.hlo.txt"),
                              program="zero2", world=8, zero_stage=2)
        d = ledger.to_dict(link_gbps=10.0)
        assert d["program"] == "zero2"
        assert isinstance(d["total_bytes"], int) and d["total_bytes"] > 0
        assert set(d["by_kind"]) == set(ledger.totals_by_kind())
        assert d["predicted_comm_seconds"] > 0
        assert all(isinstance(r["bytes"], int) and isinstance(r["count"], int)
                   for r in d["by_kind"].values())

    def test_to_dict_truncates_ops(self):
        ledger = build_ledger(fixture_text("zero3_tiny_step.hlo.txt"),
                              program="zero3", world=8, zero_stage=3)
        d = ledger.to_dict(max_ops=5)
        assert len(d["ops"]) == 5
        assert d["ops_truncated"] == len(ledger.ops) - 5

    def test_fold_publishes_gauges(self):
        from deepspeed_tpu import telemetry

        ledger = build_ledger(fixture_text("zero3_tiny_step.hlo.txt"),
                              program="fold_test", world=8, zero_stage=3)
        ledger.fold_into_telemetry()
        snap = telemetry.snapshot()
        rows = {k: v for k, v in snap["gauges"].items()
                if k.startswith("comm_ledger_bytes_per_step")
                and 'program="fold_test"' in k}
        assert rows
        assert sum(rows.values()) == ledger.total_bytes()
        pred = [v for k, v in snap["gauges"].items()
                if k.startswith("comm_ledger_predicted_comm_seconds")
                and 'program="fold_test"' in k]
        assert pred and pred[0] > 0

    def test_refold_overwrites_not_double_counts(self):
        from deepspeed_tpu import telemetry

        ledger = build_ledger(fixture_text("zero2_tiny_step.hlo.txt"),
                              program="refold_test", world=8, zero_stage=2)
        ledger.fold_into_telemetry()
        ledger.fold_into_telemetry()
        snap = telemetry.snapshot()
        rows = {k: v for k, v in snap["gauges"].items()
                if k.startswith("comm_ledger_bytes_per_step")
                and 'program="refold_test"' in k}
        assert sum(rows.values()) == ledger.total_bytes()


# --------------------------------------------------------------------- #
# shared busbw convention (satellite: ONE formula, pinned values)
# --------------------------------------------------------------------- #
class TestBusbwUnification:
    # NCCL-tests convention at n = 2 / 4 / 8
    PINNED = {
        ("all_reduce", 2): 1.0, ("all_reduce", 4): 1.5,
        ("all_reduce", 8): 1.75,
        ("reduce_scatter", 2): 0.5, ("reduce_scatter", 4): 0.75,
        ("reduce_scatter", 8): 0.875,
        ("all_gather", 2): 0.5, ("all_gather", 4): 0.75,
        ("all_gather", 8): 0.875,
        ("all_to_all", 2): 0.5, ("all_to_all", 4): 0.75,
        ("all_to_all", 8): 0.875,
    }

    def test_pinned_factors(self):
        for (op, n), want in self.PINNED.items():
            assert math.isclose(BW.busbw_factor(op, n), want), (op, n)

    def test_calc_bw_log_imports_shared_formula(self):
        from deepspeed_tpu.utils.comms_logging import calc_bw_log

        for (op, n), factor in self.PINNED.items():
            got = calc_bw_log(op, 10 ** 9, 1.0, n)
            assert math.isclose(got["tput_GBps"], 1.0)
            assert math.isclose(got["busbw_GBps"], factor), (op, n)

    def test_reference_aliases_agree(self):
        # the reference API spellings must land on the same factors
        assert BW.busbw_factor("all_gather_into_tensor", 8) == \
            BW.busbw_factor("all_gather", 8)
        assert BW.busbw_factor("reduce_scatter_tensor", 4) == \
            BW.busbw_factor("reduce_scatter", 4)
        assert BW.busbw_factor("inference_all_reduce", 2) == \
            BW.busbw_factor("all_reduce", 2)
        # HLO spellings (incl. async) too
        assert BW.busbw_factor("all-reduce-start", 8) == \
            BW.busbw_factor("all_reduce", 8)

    def test_degenerate_and_p2p(self):
        assert BW.busbw_factor("all_reduce", 1) == 0.0
        assert BW.busbw_factor("collective_permute", 8) == 1.0
        assert BW.busbw_factor("no_such_op", 8) == 1.0

    def test_comm_bench_uses_shared_factors(self):
        # the bench module must not carry its own factor literals anymore
        import inspect

        from deepspeed_tpu.utils import comm_bench

        src = inspect.getsource(comm_bench)
        assert "busbw_factor" in src
        assert "2 * (world - 1) / world" not in src


# --------------------------------------------------------------------- #
# overlap meter: interval math + fenced-timer fallback estimator
# --------------------------------------------------------------------- #
class TestOverlapIntervals:
    def test_exact_half_overlap(self):
        res = overlap_from_intervals([(0.0, 10.0)], [(5.0, 15.0)])
        assert res.compute_busy_s == 10.0
        assert res.comm_busy_s == 10.0
        assert res.overlap_s == 5.0
        assert res.overlap_fraction == 0.5

    def test_union_merges_overlapping_intervals(self):
        res = overlap_from_intervals(
            [(0, 4), (2, 6), (10, 12)], [(3, 5)])
        assert res.compute_busy_s == 8.0   # [0,6] + [10,12]
        assert res.overlap_s == 2.0        # [3,5]
        assert res.overlap_fraction == 1.0

    def test_no_comm_is_vacuously_hidden(self):
        res = overlap_from_intervals([(0, 1)], [])
        assert res.overlap_fraction == 1.0 and res.comm_busy_s == 0.0

    def test_disjoint_zero_overlap(self):
        res = overlap_from_intervals([(0, 1)], [(2, 3)])
        assert res.overlap_fraction == 0.0


class TestOverlapEstimator:
    def test_textbook_case(self):
        # wall 1.0s with 0.8s compute + 0.4s comm → 0.2s must have run
        # concurrently → half the comm was hidden
        res = estimate_overlap(1.0, 0.4, 0.8)
        assert math.isclose(res.overlap_s, 0.2, abs_tol=1e-12)
        assert math.isclose(res.overlap_fraction, 0.5)

    def test_serial_assumption_reports_zero(self):
        # CPU tier: no compute referent → serial assumption, overlap 0
        res = estimate_overlap(1.0, 0.3, None)
        assert res.overlap_fraction == 0.0
        assert math.isclose(res.compute_busy_s, 0.7)

    def test_full_overlap(self):
        res = estimate_overlap(1.0, 0.5, 1.0)
        assert res.overlap_fraction == 1.0

    def test_zero_comm_vacuous(self):
        res = estimate_overlap(1.0, 0.0, 0.9)
        assert res.overlap_fraction == 1.0

    def test_clamps_hold_fraction_in_range(self):
        # degenerate fenced traces must never escape [0, 1]
        for wall, comm, compute in [(0.0, 0.0, None), (1.0, 5.0, 9.0),
                                    (0.5, 0.5, 0.5), (1e-9, 1e-3, None),
                                    (2.0, 1.0, 0.0)]:
            res = estimate_overlap(wall, comm, compute)
            assert 0.0 <= res.overlap_fraction <= 1.0, (wall, comm, compute)
            assert res.comm_busy_s <= max(wall, 0.0) + 1e-12

    def test_measured_path_falls_back_on_cpu(self):
        # the profiler capture on a CPU backend yields no device lanes:
        # measure_overlap must return None (→ estimator), never raise
        import jax.numpy as jnp

        from deepspeed_tpu.profiling.observatory import measure_overlap

        res = measure_overlap(lambda: jnp.ones((8, 8)) @ jnp.ones((8, 8)))
        assert res is None or 0.0 <= res.overlap_fraction <= 1.0

    def test_synthetic_fenced_trace_sweep(self):
        # as the fenced wall shrinks toward max(compute, comm) at fixed
        # legs, the implied overlap must rise monotonically
        fracs = [estimate_overlap(w, 0.4, 0.8).overlap_fraction
                 for w in (1.2, 1.1, 1.0, 0.9, 0.8)]
        assert fracs == sorted(fracs)
        assert math.isclose(fracs[0], 0.0, abs_tol=1e-9)
        assert math.isclose(fracs[-1], 1.0)


# --------------------------------------------------------------------- #
# flops_profiler cost-analysis normalization (satellite)
# --------------------------------------------------------------------- #
class TestCostNormalization:
    def test_shapes(self):
        from deepspeed_tpu.profiling.flops_profiler import normalize_costs

        assert normalize_costs({"flops": 5.0}) == {"flops": 5.0}
        assert normalize_costs([{"flops": 5.0}]) == {"flops": 5.0}
        assert normalize_costs([]) == {}
        assert normalize_costs(None) == {}
        assert normalize_costs(42) == {}

    def test_available_flag(self):
        from deepspeed_tpu.profiling.flops_profiler import (
            cost_analysis_available,
        )

        assert cost_analysis_available({"flops": 1.0})
        assert not cost_analysis_available({})
        assert not cost_analysis_available({"bytes accessed": 2.0})

    def test_profile_fn_surfaces_flag(self):
        import jax.numpy as jnp

        from deepspeed_tpu.profiling.flops_profiler import profile_fn

        out = profile_fn(lambda x: x @ x, jnp.ones((8, 8)))
        assert "cost_analysis_unavailable" in out
        if not out["cost_analysis_unavailable"]:
            assert out["flops"] > 0


# --------------------------------------------------------------------- #
# bench schema v2.1 comms block + diff directions (satellite)
# --------------------------------------------------------------------- #
def _v21_result(**over):
    res = {
        "schema_version": 2.1, "metric": "vs_baseline", "unit": "ratio",
        "value": 0.5, "elapsed_s": 1.0, "platform": "cpu",
        "headline": {"metric": "vs_baseline", "unit": "ratio", "value": 0.5,
                     "comms": {"total_bytes": 1000, "unparsed": 0,
                               "by_kind": {"all_reduce": {
                                   "count": 4, "bytes": 1000,
                                   "bus_bytes": 1750.0}}},
                     "overlap_fraction": 0.25},
        "entries": {"row": {"metrics": {"tokens_per_sec": 10.0},
                            "comms": {"total_bytes": 600, "unparsed": 0,
                                      "by_kind": {"all_gather": {
                                          "count": 2, "bytes": 600,
                                          "bus_bytes": 525.0}}},
                            "overlap_fraction": 0.1}},
    }
    res.update(over)
    return res


class TestBenchSchemaV21:
    def test_v21_result_validates(self):
        from deepspeed_tpu.bench.schema import validate_result

        assert validate_result(_v21_result()) == []

    def test_plain_v2_still_validates(self):
        from deepspeed_tpu.bench.schema import validate_result

        res = _v21_result(schema_version=2)
        del res["headline"]["comms"], res["headline"]["overlap_fraction"]
        del res["entries"]["row"]["comms"]
        del res["entries"]["row"]["overlap_fraction"]
        assert validate_result(res) == []

    def test_records_of_earlier_schema_rounds_still_validate(self):
        """Records that predate the comms blocks (recovered from the
        committed r02/r03 round artifacts) stay valid under v2.1."""
        from deepspeed_tpu.bench.history import default_repo_root
        from deepspeed_tpu.bench.legacy import recover_rounds
        from deepspeed_tpu.bench.schema import validate_record

        records = recover_rounds(default_repo_root())
        assert {rec["round"] for rec in records} == {"r02", "r03"}
        for rec in records:
            assert validate_record(rec) == [], rec.get("round")

    def test_bad_comms_blocks_rejected(self):
        from deepspeed_tpu.bench.schema import validate_result

        bad = _v21_result()
        bad["entries"]["row"]["comms"]["total_bytes"] = -1
        assert any("total_bytes" in e for e in validate_result(bad))
        bad = _v21_result()
        del bad["headline"]["comms"]["by_kind"]
        assert any("by_kind" in e for e in validate_result(bad))
        bad = _v21_result()
        bad["headline"]["overlap_fraction"] = 1.5
        assert any("overlap_fraction" in e for e in validate_result(bad))

    def test_diff_directions(self):
        from deepspeed_tpu.bench.diff import (
            HIGHER_IS_BETTER,
            LOWER_IS_BETTER,
            metric_direction,
        )

        assert metric_direction("comms.total_bytes") == LOWER_IS_BETTER
        assert metric_direction(
            "comms.by_kind.all_reduce.bytes") == LOWER_IS_BETTER
        assert metric_direction("comms.by_kind.all_reduce.count") is None
        assert metric_direction(
            "comms.by_kind.all_reduce.predicted_busbw_gbps") is None
        assert metric_direction("overlap_fraction") == HIGHER_IS_BETTER

    def test_diff_flags_byte_growth_as_regression(self):
        # wire bytes growing 2x must read as a regression; shrinking
        # 2x (the quantized-collective win) as an improvement
        from deepspeed_tpu.bench.diff import diff_results, render_text

        old, new = _v21_result(), _v21_result()
        new["entries"]["row"]["comms"]["total_bytes"] = 1200
        new["entries"]["row"]["comms"]["by_kind"]["all_gather"]["bytes"] = 1200
        diff = diff_results(old, new)
        regressed = {r["metric"] for r in diff["regressions"]}
        assert "comms.total_bytes" in regressed
        shrunk = _v21_result()
        shrunk["entries"]["row"]["comms"]["total_bytes"] = 300
        diff2 = diff_results(old, shrunk)
        improved = {r["metric"] for r in diff2["improvements"]}
        assert "comms.total_bytes" in improved
        # and both render without error
        assert "bench-diff" in render_text(diff)
        assert render_text(diff2)

    def test_overlap_drop_is_regression(self):
        from deepspeed_tpu.bench.diff import diff_results

        old, new = _v21_result(), _v21_result()
        new["entries"]["row"]["overlap_fraction"] = 0.01
        old["entries"]["row"]["overlap_fraction"] = 0.9
        diff = diff_results(old, new)
        assert any(r["metric"] == "overlap_fraction"
                   for r in diff["regressions"])


# --------------------------------------------------------------------- #
# live e2e: engine ledger + step report (the acceptance path)
# --------------------------------------------------------------------- #
def _tiny_engine(stage):
    spec = dst.causal_lm_spec("tiny", dtype="float32", num_layers=2,
                              max_seq_len=64)
    config = {"train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": stage},
              "wall_clock_breakdown": True,
              "steps_per_print": 10 ** 9}
    engine, *_ = dst.initialize(model=spec, config=config)
    return engine


@pytest.mark.slow
class TestLiveEngine:
    def test_zero3_ledger_and_report(self):
        from deepspeed_tpu.runtime.dataloader import synthetic_lm_data

        engine = _tiny_engine(3)
        try:
            data = synthetic_lm_data(8, 64, 512, seed=0)
            engine.forward(next(data))
            engine.backward()
            engine.step()
            ledger = engine.collective_ledger()
            kinds = {k for k, r in ledger.totals_by_kind().items()
                     if r["bytes"] > 0}
            # acceptance: >= 2 distinct kinds with nonzero bytes at zero3
            assert len(kinds) >= 2
            assert BW.ALL_REDUCE in kinds or BW.REDUCE_SCATTER in kinds
            # cached: second call returns the same object, no relower
            assert engine.collective_ledger() is ledger
            report = engine.step_report()
            assert validate_report(report) == []
            assert 0.0 <= report["overlap_fraction"] <= 1.0
            assert report["overlap_source"] in ("profiler", "estimated")
            assert report["phases"], "no phase walls captured"
            for row in report["phases"].values():
                assert row["verdict"] in ("compute-bound", "comm-bound",
                                          "host-bound")
        finally:
            engine.shutdown_telemetry()

    def test_fastgen_ledger_builds_and_caches(self):
        from deepspeed_tpu.inference.fastgen import FastGenEngine

        fg = FastGenEngine("tiny", n_blocks=32, block_size=16,
                           max_blocks_per_seq=4, token_budget=16, seed=0)
        ledger = fg.collective_ledger()
        assert ledger.program == "fastgen_tick"
        assert ledger.unparsed == 0
        # without tensor parallelism the tick legitimately ledgers empty
        assert ledger.total_bytes() >= 0
        assert fg.collective_ledger() is ledger
        # a different token bucket is a DIFFERENT compiled program — its
        # ledger must not be served from the full-budget cache entry
        small = fg.collective_ledger(n_tokens=4)
        assert small is not ledger
        assert small.program == "fastgen_tick_t8"
        assert fg.collective_ledger(n_tokens=4) is small

    def test_bench_comms_block_shape(self):
        from deepspeed_tpu.bench.schema import validate_entry
        from deepspeed_tpu.profiling.observatory import bench_comms_block
        from deepspeed_tpu.runtime.dataloader import synthetic_lm_data

        engine = _tiny_engine(2)
        try:
            data = synthetic_lm_data(8, 64, 512, seed=1)
            engine.forward(next(data))
            engine.backward()
            engine.step()
            # bench passes its measured per-step wall explicitly (the
            # window wall / steps) — with one given, overlap must appear
            block = bench_comms_block(engine, wall_s=0.05)
            assert block["comms"]["total_bytes"] > 0
            assert block["comms"]["by_kind"]
            # the block must survive the bench entry validator
            entry = {"metrics": {"tokens_per_sec": 1.0}, **block}
            assert validate_entry(entry, "row") == []
            assert 0.0 <= block["overlap_fraction"] <= 1.0
        finally:
            engine.shutdown_telemetry()


# --------------------------------------------------------------------- #
# CLI (tools/step-report)
# --------------------------------------------------------------------- #
class TestCli:
    def _run(self, *args):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools", "step-report"),
             *args],
            capture_output=True, text=True, env=env, cwd=REPO_ROOT,
            timeout=300)

    def test_hlo_file_mode(self):
        proc = self._run(
            "--hlo-file",
            os.path.join(FIXTURES, "zero3_tiny_step.hlo.txt"),
            "--world", "8", "--zero-stage", "3", "--link-gbps", "10")
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)
        assert report["mode"] == "ledger_only"
        by_kind = report["ledger"]["by_kind"]
        assert len([k for k, r in by_kind.items() if r["bytes"] > 0]) >= 2
        assert report["ledger"]["predicted_comm_seconds"] > 0

    def test_missing_file_exits_2(self):
        proc = self._run("--hlo-file", "/nonexistent/step.hlo.txt")
        assert proc.returncode == 2
        assert "step-report" in proc.stderr

    def test_read_mode_roundtrip(self, tmp_path):
        proc = self._run(
            "--hlo-file",
            os.path.join(FIXTURES, "moe_tiny_step.hlo.txt"),
            "--world", "8", "--out", str(tmp_path / "r.json"))
        assert proc.returncode == 0, proc.stderr
        proc2 = self._run("--read", str(tmp_path / "r.json"))
        assert proc2.returncode == 0
        assert json.loads(proc2.stdout)["ledger"]["total_bytes"] == \
            json.loads(proc.stdout)["ledger"]["total_bytes"]


# --------------------------------------------------------------------- #
# report validator
# --------------------------------------------------------------------- #
class TestReportValidator:
    def _minimal(self):
        return {
            "report_version": 1, "program": "train_step", "platform": "cpu",
            "verdict": "compute-bound", "overlap_fraction": 0.5,
            "cost_analysis": {"available": True, "flops": 1.0,
                              "bytes_accessed": 2.0},
            "ledger": {"by_kind": {"all_reduce": {"count": 1, "bytes": 4}}},
            "phases": {"fwd": {"wall_s": 0.1, "predicted_comm_s": 0.01,
                               "overlap_fraction": 0.0,
                               "verdict": "compute-bound"}},
        }

    def test_minimal_valid(self):
        assert validate_report(self._minimal()) == []

    def test_rejections(self):
        bad = self._minimal()
        bad["overlap_fraction"] = 2.0
        assert validate_report(bad)
        bad = self._minimal()
        bad["phases"]["fwd"]["verdict"] = "gpu-bound"
        assert validate_report(bad)
        bad = self._minimal()
        bad["ledger"]["by_kind"]["all_reduce"]["bytes"] = 4.5
        assert validate_report(bad)
        assert validate_report("nope")
