"""Perf-observatory schema + recovery tests (``deepspeed_tpu/bench``).

The legacy-ingestion tests run against the committed round artifacts
that remain (BENCH_r02/r03.json at the repo root — r03 is an actual
truncated tail that produced ``"parsed": null``) and against small
driver-shaped artifacts written into ``tmp_path``.
"""
import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.bench import history as history_mod
from deepspeed_tpu.bench import legacy, schema

pytestmark = pytest.mark.bench

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_result(value=1000.0, entries=None, **head_extra):
    """A minimal valid schema-v2 result."""
    head = {"metric": "tokens/sec/chip tiny zero1 bf16", "value": value,
            "unit": "tokens/s/chip", "vs_baseline": 0.5, "mfu": 0.4}
    head.update(head_extra)
    return {
        "schema_version": schema.SCHEMA_VERSION,
        "metric": head["metric"], "value": head["value"],
        "unit": head["unit"], "vs_baseline": head["vs_baseline"],
        "headline": head,
        "entries": entries if entries is not None else {},
    }


# --------------------------------------------------------------------- #
# schema validator round-trip
# --------------------------------------------------------------------- #
class TestSchemaValidator:
    def test_valid_result_roundtrips_through_json(self):
        res = make_result(entries={
            "zero3_llama_750m_bf16": {
                "metrics": {"tokens_per_sec_chip": 24337.2, "mfu": 0.539},
                "trace_phases": {"train_window": {
                    "count": 5, "total_s": 4.9, "p50_s": 0.9,
                    "p95_s": 1.1, "p99_s": 1.2}},
                "memory": {"peak_host_rss_mb": 440.2},
                "elapsed_s": 66.1,
            },
            "comm_bw_onchip": {"skipped_reason": "world=1"},
            "fastgen_paged_splitfuse_gpt2": {"error": "rc=1: boom"},
        })
        assert schema.validate_result(res) == []
        assert schema.validate_result(json.loads(json.dumps(res))) == []

    def test_null_headline_value_is_the_locked_out_failure_mode(self):
        res = make_result()
        res["headline"]["value"] = None
        res["value"] = None
        errs = schema.validate_result(res)
        assert any("null" in e or "number" in e for e in errs)

    def test_zero_value_needs_an_error_explanation(self):
        res = make_result(value=0)
        assert schema.validate_result(res)           # bare 0 → invalid
        res["headline"]["error"] = "budget (0s left < 120s floor)"
        assert schema.validate_result(res) == []     # explained 0 → valid

    def test_headline_and_driver_contract_must_agree(self):
        res = make_result()
        res["value"] = res["headline"]["value"] + 1
        assert any("headline.value" in e
                   for e in schema.validate_result(res))

    def test_wrong_schema_version_rejected(self):
        res = make_result()
        res["schema_version"] = 1
        assert any("schema_version" in e
                   for e in schema.validate_result(res))

    def test_entry_must_be_measured_skipped_or_failed(self):
        res = make_result(entries={"autotune_smoke": {}})
        assert any("at least one of" in e
                   for e in schema.validate_result(res))

    def test_stray_entry_key_rejected(self):
        res = make_result(
            entries={"autotune_smoke": {"tokens_per_sec_chip": 5.0}})
        assert any("unexpected key" in e
                   for e in schema.validate_result(res))

    def test_elastic_block_roundtrips(self):
        res = make_result(entries={"elastic_resume": {
            "metrics": {"reshard_s": 0.32},
            "elastic": {"from_world": 8, "to_world": 4,
                        "convert_s": 0.215, "reshard_s": 0.324},
            "elapsed_s": 12.0,
        }})
        assert schema.validate_result(res) == []

    def test_elastic_block_requires_positive_worlds(self):
        res = make_result(entries={"elastic_resume": {
            "metrics": {"reshard_s": 0.3},
            "elastic": {"from_world": 8, "to_world": 0}}})
        assert any("elastic.to_world" in e
                   for e in schema.validate_result(res))
        res["entries"]["elastic_resume"]["elastic"] = {
            "from_world": True, "to_world": 4}
        assert any("elastic.from_world" in e
                   for e in schema.validate_result(res))

    def test_elastic_wall_times_non_negative(self):
        res = make_result(entries={"elastic_resume": {
            "metrics": {"reshard_s": 0.3},
            "elastic": {"from_world": 8, "to_world": 4,
                        "reshard_s": -1.0}}})
        assert any("elastic.reshard_s" in e
                   for e in schema.validate_result(res))

    def test_pre_elastic_versions_still_validate(self):
        # back-compat: a v2.3 record (predates the elastic block) and a
        # v2.4 record without any elastic block both load unchanged
        for version in (2.3, schema.SCHEMA_VERSION):
            res = make_result()
            res["schema_version"] = version
            assert schema.validate_result(res) == [], version

    def test_tenants_block_roundtrips(self):
        # v2.5: a measured entry may carry per-tenant accounting
        res = make_result(entries={"fleet_sla_multitenant_gpt2": {
            "metrics": {"completed": 12.0},
            "tenants": {
                "hot": {"submitted": 15,
                        "outcomes": {"completed": 3, "rejected": 12},
                        "ttft_p50_s": 0.04, "ttft_p99_s": 0.22},
                "rt": {"submitted": 2, "outcomes": {"completed": 2},
                       "ttft_p50_s": None, "ttft_p99_s": None},
            },
            "elapsed_s": 30.0,
        }})
        assert schema.validate_result(res) == []
        assert schema.validate_result(json.loads(json.dumps(res))) == []

    def test_tenants_block_must_reconcile(self):
        # the invariant IS the schema: submitted != sum(outcomes) is an
        # invalid bench result, not a soft warning
        res = make_result(entries={"fleet_sla_multitenant_gpt2": {
            "metrics": {"completed": 1.0},
            "tenants": {"hot": {"submitted": 5,
                                "outcomes": {"completed": 3}}}}})
        assert any("reconcile" in e for e in schema.validate_result(res))

    def test_tenants_block_shape_errors(self):
        base = {"metrics": {"completed": 1.0}}
        bads = [
            ({"hot": {"outcomes": {}}}, "submitted"),
            ({"hot": {"submitted": -1, "outcomes": {}}}, "submitted"),
            ({"hot": {"submitted": 1,
                      "outcomes": {"completed": -1}}}, "outcomes"),
            ({"hot": {"submitted": 0, "outcomes": {},
                      "ttft_p99_s": -0.5}}, "ttft_p99_s"),
            ({"hot": [1, 2]}, "tenants"),
            ("not-a-dict", "tenants"),
        ]
        for block, needle in bads:
            res = make_result(entries={
                "lane": dict(base, tenants=block)})
            errs = schema.validate_result(res)
            assert any(needle in e for e in errs), (block, errs)

    def test_pre_tenancy_versions_still_validate(self):
        # v2–v2.4 records (no tenants block anywhere) load unchanged
        for version in (2, 2.1, 2.2, 2.3, 2.4, schema.SCHEMA_VERSION):
            res = make_result(entries={
                "fleet_sla_gpt2": {"metrics": {"completed": 8.0}}})
            res["schema_version"] = version
            assert schema.validate_result(res) == [], version

    def test_trace_phase_stats_must_be_complete(self):
        res = make_result(entries={"headline": {
            "metrics": {"mfu": 0.4},
            "trace_phases": {"fwd": {"count": 3, "p50_s": 0.1}}}})
        errs = schema.validate_result(res)
        assert any("total_s" in e for e in errs)

    def test_plan_block_carries_the_cache_verdict(self):
        # v2.3: each entry row may carry the engine's autotune plan-cache
        # verdict — a history round then shows which lanes ran under a
        # cached plan and which planned from scratch
        entry = {"metrics": {"tokens_per_sec_chip": 5.0},
                 "plan": {"status": "hit",
                          "key": "abc123-data8-exact-cpu"}}
        res = make_result(entries={"autotune_plan": entry})
        assert schema.validate_result(res) == []
        entry["plan"] = {"status": "disabled"}     # key absent is fine
        assert schema.validate_result(res) == []
        entry["plan"] = {"status": "banana"}
        assert any("plan.status" in e
                   for e in schema.validate_result(res))
        entry["plan"] = {"status": "hit", "key": 7}
        assert any("plan.key" in e for e in schema.validate_result(res))
        entry["plan"] = "hit"
        assert any("plan must be a dict" in e
                   for e in schema.validate_result(res))

    def test_normalize_hoists_plan_out_of_the_flat_row(self):
        # the raw --entry row is flat: the plan block must land as a
        # STRUCTURAL entry key, not get swept into metrics (where a dict
        # value would also be ungateable)
        row = {"candidates": 8, "plan": {"status": "hit"}}
        out = schema.normalize_entry_row(row)
        assert out["plan"] == {"status": "hit"}
        assert "plan" not in out["metrics"]

    def test_validator_never_raises_on_garbage(self):
        for garbage in (None, 7, "x", [], {"headline": 3, "entries": 4},
                        {"schema_version": "two"}):
            assert schema.validate_result(garbage)   # errors, not a raise


class TestNormalizeEntryRow:
    def test_flat_row_splits_structure_from_metrics(self):
        row = {"tokens_per_sec_chip": 100.0, "mfu": 0.3,
               "telemetry": {}, "trace_phases": {},
               "note": "hi"}
        entry = schema.normalize_entry_row(row, elapsed_s=12.34)
        assert entry["metrics"] == {"tokens_per_sec_chip": 100.0,
                                    "mfu": 0.3}
        assert entry["note"] == "hi"
        assert entry["elapsed_s"] == 12.3
        assert "telemetry" not in entry          # empty ones are dropped
        assert "trace_phases" not in entry

    def test_skip_and_error_markers(self):
        assert schema.normalize_entry_row(
            {"skipped": "budget (9s left < 120s floor)"}
        )["skipped_reason"].startswith("budget")
        assert schema.normalize_entry_row({"error": "rc=1"})["error"] \
            == "rc=1"

    def test_list_rows_wrap(self):
        entry = schema.normalize_entry_row([{"op": "all_reduce"}])
        assert entry["metrics"]["rows"][0]["op"] == "all_reduce"

    def test_idempotent_on_already_normalized(self):
        entry = {"metrics": {"mfu": 0.5}, "elapsed_s": 3.0}
        again = schema.normalize_entry_row(entry)
        assert again["metrics"] == {"mfu": 0.5}
        assert again["elapsed_s"] == 3.0


def write_artifact(tmp_path, name, rc=0, tail="", parsed=None):
    """A driver round artifact ``{n, cmd, rc, tail, parsed}``."""
    path = str(tmp_path / name)
    with open(path, "w") as f:
        json.dump({"n": 1, "cmd": "python bench.py", "rc": rc,
                   "tail": tail, "parsed": parsed}, f)
    return path


V1_PARSED = {"metric": "tokens/sec/chip gpt2_125m zero1 bf16",
             "value": 34443.1, "unit": "tokens/s/chip",
             "vs_baseline": 0.206}


# --------------------------------------------------------------------- #
# legacy recovery of driver round artifacts
# --------------------------------------------------------------------- #
class TestLegacyRecovery:
    def test_complete_from_parsed(self, tmp_path):
        rec = legacy.recover_round_file(write_artifact(
            tmp_path, "BENCH_r01.json", tail="WARNING: noise\n",
            parsed=V1_PARSED))
        assert rec["complete"] and not rec["recovered"]
        assert rec["result"]["headline"]["value"] == 34443.1
        assert schema.validate_record(rec) == []

    def test_r03_truncated_tail_recovers_the_suite(self):
        """r03 is the round where parsed went null: the line's FRONT was
        cut mid-key. The tolerant parser must get the entries back —
        including the one whose key was truncated."""
        rec = legacy.recover_round_file(os.path.join(REPO,
                                                     "BENCH_r03.json"))
        assert rec["recovered"] and not rec["complete"]
        entries = rec["result"]["entries"]
        z = entries["zero3_llama_750m_bf16"]["metrics"]
        assert z["tokens_per_sec_chip"] == 24337.2
        assert z["mfu"] == 0.539
        # the front-truncated key resolves by unique suffix
        bert = entries["zero2_fusedadam_bert_large_fp16"]["metrics"]
        assert bert["tokens_per_sec_chip"] == 38621.7
        assert any("resolved to" in n for n in rec["notes"])
        assert len(entries) >= 8
        assert schema.validate_record(rec) == []

    def test_r03_truncated_entry_internals_do_not_pollute_headline(self):
        """The cut-off first entry's mfu/loss must NOT be claimed as the
        round's headline — a wrong headline is worse than a lost one."""
        rec = legacy.recover_round_file(os.path.join(REPO,
                                                     "BENCH_r03.json"))
        assert "mfu" not in rec["result"]["headline"]
        assert "value" not in rec["result"]["headline"]

    def test_rc124_husk_is_an_honest_empty_record(self, tmp_path):
        """A run killed at its time limit printed no line at all."""
        rec = legacy.recover_round_file(write_artifact(
            tmp_path, "BENCH_r04.json", rc=124,
            tail="WARNING: Platform initialized\nTerminated\n"))
        assert rec["rc"] == 124
        assert rec["result"]["entries"] == {}
        assert any("rc=124" in n for n in rec["notes"])
        assert schema.validate_record(rec) == []

    def test_truncated_tail_recovers_trailing_entries_and_fields(
            self, tmp_path):
        """A tail cut inside an entry: the entry's internals are dropped,
        the whole entries and the trailing top-level fields after it
        come back."""
        tail = ('rg_mb": 996, "host_arg_mb": 498, "temp_mb": 2510}, '
                '"master_moved_to_host": true}, '
                '"autotune_smoke": {"picked_micro_batch": 32}}, '
                '"entry_elapsed_s": {"autotune_smoke": 59.6}, '
                '"total_runtime_s": 693.6}')
        rec = legacy.recover_round_file(write_artifact(
            tmp_path, "BENCH_r05.json", tail=tail))
        assert "host_arg_mb" not in rec["result"]["headline"]
        smoke = rec["result"]["entries"]["autotune_smoke"]
        assert smoke["metrics"]["picked_micro_batch"] == 32
        assert smoke["elapsed_s"] == 59.6     # from entry_elapsed_s
        assert rec["result"]["total_runtime_s"] == 693.6

    def test_upgrade_is_idempotent(self):
        with open(os.path.join(REPO, "BENCH_r02.json")) as f:
            parsed = json.load(f)["parsed"]
        v2 = legacy.upgrade_legacy_result(parsed)
        assert legacy.upgrade_legacy_result(v2) is v2
        assert schema.validate_result(v2) == []
        assert "zero3_llama_750m_bf16" in v2["entries"]

    def test_corrupt_artifact_degrades_to_raw_text_never_raises(
            self, tmp_path):
        """A future damaged BENCH_rNN.json must not abort the whole
        recover run — the parser's contract is 'never raises on the
        garbage it exists to read'."""
        write_artifact(tmp_path, "BENCH_r01.json", parsed=V1_PARSED)
        corrupt = str(tmp_path / "BENCH_r06.json")
        with open(corrupt, "w") as f:
            f.write('{"rc": 0, "tail": "... \\"value\\": 123.0, '
                    '\\"unit\\": \\"u\\"')       # truncated artifact
        rec = legacy.recover_round_file(corrupt)
        assert rec["recovered"]
        assert any("raw text" in n for n in rec["notes"])
        rounds = legacy.recover_rounds(str(tmp_path))
        assert [r["round"] for r in rounds] == ["r01", "r06"]
        assert rounds[0]["complete"]             # r01 still ingested

    def test_recover_from_text_prefers_a_complete_line(self):
        res, notes = legacy.recover_from_text(
            "INFO: noise\n"
            + json.dumps({"metric": "m", "value": 1.0, "unit": "u",
                          "vs_baseline": 0.1}) + "\n")
        assert res["headline"]["value"] == 1.0
        assert notes == []


# --------------------------------------------------------------------- #
# history store + the committed trajectory
# --------------------------------------------------------------------- #
class TestHistory:
    def test_append_load_roundtrip_and_corrupt_line_tolerance(self, tmp_path):
        path = str(tmp_path / "history.jsonl")
        rec = history_mod.record_from_result(make_result(), round_id="r99")
        history_mod.append_record(rec, path)
        with open(path, "a") as f:
            f.write("{corrupt\n")
        history_mod.append_record(
            history_mod.record_from_result(make_result(2000.0),
                                           round_id="r100"), path)
        records, notes = history_mod.load_history(path)
        assert [r["round"] for r in records] == ["r99", "r100"]
        assert len(notes) == 1 and "unparseable" in notes[0]

    def test_latest_skips_uncomparable_husks(self, tmp_path):
        path = str(tmp_path / "history.jsonl")
        history_mod.append_record(
            history_mod.record_from_result(make_result(), "r1"), path)
        husk = {"record_version": 1, "round": "r2", "source": "x",
                "rc": 124, "recovered": True, "complete": False,
                "result": {"headline": {}, "entries": {}}, "notes": []}
        history_mod.append_record(husk, path)
        assert history_mod.latest_record(path=path)["round"] == "r1"
        assert history_mod.latest_record(
            path=path, comparable_only=False)["round"] == "r2"

    def test_same_round_last_append_wins(self, tmp_path):
        path = str(tmp_path / "history.jsonl")
        history_mod.append_record(
            history_mod.record_from_result(make_result(1.0), "r7"), path)
        history_mod.append_record(
            history_mod.record_from_result(make_result(2.0), "r7"), path)
        rec = history_mod.record_for_round("r7", path=path)
        assert rec["result"]["value"] == 2.0


# --------------------------------------------------------------------- #
# bench.py under a starved budget still emits a schema-valid line
# --------------------------------------------------------------------- #
class TestBenchBudgetSubprocess:
    def test_tiny_budget_emits_valid_json_with_explicit_skips(self,
                                                              tmp_path):
        """Locks in the r04 fix (rc=124 left NO line at all): a budget
        that can't fit a single entry must still print one schema-valid
        JSON line whose rows say "budget", and exit 0."""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   BENCH_BUDGET_S="5", BENCH_DSLINT="0",
                   BENCH_GATE="0", BENCH_RECORD="0",
                   BENCH_HISTORY=str(tmp_path),
                   PYTHONPATH=REPO)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, env=env, timeout=240)
        assert out.returncode == 0, out.stderr[-500:]
        result = json.loads(out.stdout.strip().splitlines()[-1])
        assert schema.validate_result(result) == []
        assert "budget" in result["headline"]["error"]
        assert result["entries"], "suite rows must be present, not absent"
        for name, entry in result["entries"].items():
            assert "budget" in entry["skipped_reason"], (name, entry)
