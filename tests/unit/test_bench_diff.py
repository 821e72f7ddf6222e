"""bench-diff + regression-gate tests (``deepspeed_tpu/bench``).

The acceptance scenario from the observatory issue is here verbatim: a
synthetic ≥10% throughput regression whose fwd phase grew must be
flagged WITH the responsible phase named, the gate must exit nonzero on
it and zero on parity, and a recovered entries-only record must be
directly diffable from the CLI.
"""
import copy
import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.bench import cli, gate, history as history_mod
from deepspeed_tpu.bench.diff import (
    diff_results,
    flatten_metrics,
    metric_direction,
    render_markdown,
    render_text,
)

pytestmark = pytest.mark.bench

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def phases(fwd=0.100, bwd=0.200, step=0.050, n=20):
    out = {}
    for name, p50 in (("fwd", fwd), ("bwd", bwd), ("step", step)):
        out[name] = {"count": n, "total_s": round(p50 * n, 6),
                     "p50_s": p50, "p95_s": p50 * 1.1, "p99_s": p50 * 1.2}
    return out


def make_result(tps=10000.0, fwd=0.100, entry_tps=24000.0):
    head = {"metric": "tokens/sec/chip gpt2_125m zero1 bf16",
            "value": tps, "unit": "tokens/s/chip",
            "vs_baseline": round(tps / 167000, 3), "mfu": 0.36,
            "trace_phases": phases(fwd=fwd)}
    return {
        "schema_version": 2,
        "metric": head["metric"], "value": tps, "unit": head["unit"],
        "vs_baseline": head["vs_baseline"], "headline": head,
        "entries": {
            "zero3_llama_750m_bf16": {
                "metrics": {"tokens_per_sec_chip": entry_tps,
                            "mfu": 0.54},
                "trace_phases": phases(fwd=0.300, bwd=0.600),
                "memory": {"peak_host_rss_mb": 1400.0},
                "elapsed_s": 60.0,
            },
            "autotp_inference_gpt2_generate": {
                "metrics": {"decode_tokens_per_sec": 2500.0,
                            "batch": 8, "max_new": 128},
                "elapsed_s": 47.0,
            },
        },
    }


class TestDirections:
    def test_throughput_up_latency_down(self):
        assert metric_direction("tokens_per_sec_chip") == 1
        assert metric_direction("load_0.9.ttft_p95_s") == -1
        assert metric_direction("all_reduce.busbw_gbps") == 1
        assert metric_direction("memory.peak_host_rss_mb") == -1
        assert metric_direction("rel_err") == -1

    def test_uncompared_metrics(self):
        # ranking scores, convergence losses, and config echoes are not
        # perf trajectories
        for name in ("tuner_score", "loss", "batch", "max_new", "n_chips",
                     "picked_micro_batch"):
            assert metric_direction(name) is None

    def test_flatten_keys_comm_tables_by_op(self):
        flat = flatten_metrics({"rows": [
            {"op": "all_reduce", "algbw_gbps": 3.8, "size_mb": 64}]})
        assert flat == {"rows.all_reduce.algbw_gbps": 3.8}

    def test_flatten_nested_sla_loads(self):
        flat = flatten_metrics({"load_0.9": {"ttft_p95_s": 0.5,
                                             "achieved_tokens_per_sec": 90}})
        assert flat["load_0.9.ttft_p95_s"] == 0.5
        assert flat["load_0.9.achieved_tokens_per_sec"] == 90


class TestDiffAttribution:
    def test_parity_is_clean(self):
        d = diff_results(make_result(), make_result())
        assert d["ok"] and d["regressions"] == []

    def test_synthetic_10pct_fwd_regression_names_the_phase(self):
        """The acceptance scenario: tokens/sec drops ~10%, the fwd phase
        p50 grew — attribution must name fwd, with numbers."""
        old = make_result(tps=10000.0, fwd=0.100)
        new = make_result(tps=9000.0, fwd=0.125)     # fwd +25%, tps -10%
        d = diff_results(old, new)
        assert not d["ok"]
        assert any(r["where"] == "headline" and r["metric"] == "value"
                   for r in d["regressions"])
        attr = d["headline"]["attribution"]
        assert attr["phase"] == "fwd"
        assert attr["p50_old_s"] == 0.100 and attr["p50_new_s"] == 0.125
        assert "fwd" in attr["summary"] and "-10.0%" in attr["summary"]
        # bwd/step did not grow — they must not be blamed
        assert attr["p50_growth_frac"] == pytest.approx(0.25)

    def test_per_entry_regression_attributed_to_its_own_phases(self):
        old = make_result()
        new = make_result(entry_tps=20000.0)         # entry -16.7%
        new["entries"]["zero3_llama_750m_bf16"]["trace_phases"] = \
            phases(fwd=0.300, bwd=0.780)             # bwd +30%
        d = diff_results(old, new)
        attr = d["entries"]["zero3_llama_750m_bf16"]["attribution"]
        assert attr["phase"] == "bwd"
        assert attr["regressed_metric"] == "tokens_per_sec_chip"
        assert d["headline"]["attribution"] is None   # headline at parity

    def test_memory_regression_is_diffable(self):
        old, new = make_result(), make_result()
        new["entries"]["zero3_llama_750m_bf16"]["memory"][
            "peak_host_rss_mb"] = 1800.0             # +28%
        d = diff_results(old, new)
        assert any(r["metric"] == "memory.peak_host_rss_mb"
                   for r in d["regressions"])

    def test_cross_model_headline_is_not_compared(self):
        """A local BENCH_MODEL=tiny run vs the recorded gpt2 round must
        not read as a -90% regression — different metric names mean the
        headline is incomparable; entries still diff like-for-like."""
        old = make_result(tps=90000.0)
        new = make_result(tps=8000.0)
        for r in (new, new["headline"]):
            r["metric"] = "tokens/sec/chip tiny zero1 bf16"
        d = diff_results(old, new)
        assert d["ok"]
        assert d["headline"]["fields"] == []
        assert any("not comparable" in n for n in d["notes"])

    def test_improvement_is_not_a_regression(self):
        d = diff_results(make_result(tps=9000.0), make_result(tps=10000.0))
        assert d["ok"]
        assert any(r["metric"] == "value" for r in d["improvements"])

    def test_measured_entry_turning_error_is_flagged(self):
        new = make_result()
        new["entries"]["autotp_inference_gpt2_generate"] = {
            "error": "rc=1: XlaRuntimeError"}
        d = diff_results(make_result(), new)
        assert any(r["where"] == "autotp_inference_gpt2_generate"
                   and r["new"] == "error" for r in d["regressions"])

    def test_budget_skip_is_a_note_not_a_regression(self):
        new = make_result()
        new["entries"]["autotp_inference_gpt2_generate"] = {
            "skipped_reason": "budget (30s left < 90s floor)"}
        d = diff_results(make_result(), new)
        assert d["ok"]
        assert any("autotp" in n for n in d["notes"])

    def test_errored_headline_is_flagged_honestly_not_as_minus_100pct(self):
        """A budget-starved/broken headline carries value=0 + error by
        schema contract. Numeric-comparing it reads as a fake -100%;
        measured -> error must instead be ONE explicit regression row
        (like entries), and error -> error must not flag at all."""
        old = make_result(tps=10000.0)
        new = make_result()
        for side in (new, new["headline"]):
            side["value"] = side["vs_baseline"] = 0
            side["error"] = "entry timed out after 123s"
        d = diff_results(old, new)
        assert not d["ok"]
        head_regs = [r for r in d["regressions"]
                     if r["where"] == "headline"]
        assert head_regs == [{
            "where": "headline", "metric": "(headline)",
            "old": "measured", "new": "error", "delta_frac": None,
            "note": "entry timed out after 123s"}]
        assert d["headline"]["fields"] == []     # no fake -100% rows
        assert any("headline errored in new" in n for n in d["notes"])
        # errored on BOTH sides is not a fresh breakage
        d2 = diff_results(copy.deepcopy(new), copy.deepcopy(new))
        assert not [r for r in d2["regressions"]
                    if r["where"] == "headline"]

    def test_budget_starved_headline_is_a_note_not_a_regression(self):
        """The headline can't carry skipped_reason (driver contract needs
        value), so bench.py folds a budget skip into error='budget ...'.
        That must diff like a budget-skipped entry: noted, never flagged
        — a starved local run is not a measured -> error breakage."""
        old = make_result(tps=10000.0)
        new = make_result()
        for side in (new, new["headline"]):
            side["value"] = side["vs_baseline"] = 0
            side["error"] = "budget (3s left < 120s floor)"
        d = diff_results(old, new)
        assert d["ok"] and not d["regressions"]
        assert d["headline"]["fields"] == []
        assert any("headline errored in new" in n for n in d["notes"])

    def test_zero_baseline_metric_gets_an_explicit_row(self):
        """0 -> nonzero on a direction-compared metric has no relative
        delta, but silently dropping the row would hide e.g. rel_err
        appearing — it must surface un-verdicted, and render."""
        old, new = make_result(), make_result()
        old["entries"]["zero3_llama_750m_bf16"]["metrics"]["rel_err"] = 0.0
        new["entries"]["zero3_llama_750m_bf16"]["metrics"]["rel_err"] = 0.05
        d = diff_results(old, new)
        row = next(r for r in
                   d["entries"]["zero3_llama_750m_bf16"]["fields"]
                   if r["name"] == "rel_err")
        assert row["delta_frac"] is None
        assert not row["regressed"] and not row["improved"]
        assert d["ok"]                       # no verdict without a delta
        assert "zero baseline" in render_text(d, verbose=True)
        render_markdown(d, verbose=True)     # no traceback on None delta

    def test_renderers_cover_the_regression(self):
        d = diff_results(make_result(10000.0, fwd=0.1),
                         make_result(9000.0, fwd=0.125))
        text = render_text(d)
        assert "REGRESSED" in text and "attribution:" in text
        md = render_markdown(d)
        assert "**regressed**" in md and "fwd" in md
        json.dumps(d)                                 # JSON-clean


class TestGate:
    def _history_with(self, tmp_path, result, round_id="r90"):
        path = str(tmp_path / "history.jsonl")
        history_mod.append_record(
            history_mod.record_from_result(result, round_id), path)
        return path

    def test_parity_exits_zero(self, tmp_path):
        path = self._history_with(tmp_path, make_result())
        rc, info = gate.run_gate(make_result(), history_path=path)
        assert rc == gate.GATE_OK and info["ok"]
        assert info["baseline"] == "r90"

    def test_regression_exits_nonzero_with_attribution(self, tmp_path):
        path = self._history_with(tmp_path, make_result(10000.0, fwd=0.1))
        rc, info = gate.run_gate(make_result(9000.0, fwd=0.125),
                                 history_path=path)
        assert rc == gate.GATE_REGRESSED
        assert info["regressions"]
        assert any("fwd" in a for a in info["attribution"])

    def test_no_baseline_exits_zero(self, tmp_path):
        rc, info = gate.run_gate(
            make_result(), history_path=str(tmp_path / "none.jsonl"))
        assert rc == gate.GATE_OK and "no comparable baseline" in info["note"]

    def test_env_threshold_override(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BENCH_GATE_THRESHOLD", "0.5")
        path = self._history_with(tmp_path, make_result(10000.0))
        rc, _ = gate.run_gate(make_result(6000.0), history_path=path)
        assert rc == gate.GATE_OK            # -40% < 50% threshold
        monkeypatch.setenv("BENCH_GATE_THRESHOLD", "0.05")
        rc, _ = gate.run_gate(make_result(6000.0), history_path=path)
        assert rc == gate.GATE_REGRESSED

    def test_disabled_gate(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BENCH_GATE", "0")
        path = self._history_with(tmp_path, make_result(10000.0))
        rc, info = gate.run_gate(make_result(1.0), history_path=path)
        assert rc == gate.GATE_OK and info["disabled"]

    def test_internal_error_is_gate_error_not_a_crash(self, monkeypatch):
        monkeypatch.setattr(history_mod, "latest_record",
                            lambda **kw: (_ for _ in ()).throw(OSError("x")))
        rc, info = gate.run_gate(make_result())
        assert rc == gate.GATE_ERROR and "OSError" in info["error"]

    def test_regressed_round_cannot_become_the_next_baseline(self,
                                                             tmp_path):
        """The ratchet: a run that FAILED its own gate (rc=1) is recorded
        as evidence but skipped for baseline selection — otherwise the
        gate fires exactly once and the regression grandfathers itself."""
        path = self._history_with(tmp_path, make_result(10000.0), "r90")
        history_mod.append_record(
            history_mod.record_from_result(make_result(9000.0), "r91",
                                           rc=gate.GATE_REGRESSED), path)
        rc, info = gate.run_gate(make_result(9000.0), history_path=path)
        assert info["baseline"] == "r90"          # not the regressed r91
        assert rc == gate.GATE_REGRESSED          # still -10% vs r90

    def test_cross_model_record_is_not_a_baseline(self, tmp_path):
        """A recorded BENCH_MODEL=tiny what-if must not become the gpt2
        trajectory's baseline — its incomparable headline would make
        head_fields empty and silently disarm the headline gate."""
        path = self._history_with(tmp_path, make_result(10000.0), "r90")
        tiny = make_result(500.0)
        for r in (tiny, tiny["headline"]):
            r["metric"] = "tokens/sec/chip tiny zero1 bf16"
        history_mod.append_record(
            history_mod.record_from_result(tiny, "tiny-local"), path)
        rc, info = gate.run_gate(make_result(9000.0, fwd=0.125),
                                 history_path=path)
        assert info["baseline"] == "r90"          # skipped the tiny record
        assert rc == gate.GATE_REGRESSED          # still -10% vs r90

    def test_cross_platform_record_is_not_a_baseline(self, tmp_path):
        """A CPU what-if run must not poison the TPU trajectory (and vice
        versa): baseline selection matches the headline platform when
        both sides declare one."""
        tpu = make_result(90000.0)
        tpu["headline"]["platform"] = "tpu"
        cpu = make_result(8000.0)
        cpu["headline"]["platform"] = "cpu"
        path = self._history_with(tmp_path, tpu, "r90")
        history_mod.append_record(
            history_mod.record_from_result(cpu, "cpu-local"), path)
        fresh = make_result(88000.0)
        fresh["headline"]["platform"] = "tpu"
        rc, info = gate.run_gate(fresh, history_path=path)
        assert info["baseline"] == "r90"          # skipped the cpu record
        assert rc == gate.GATE_OK

    def test_noisy_lane_attribution_is_filtered_with_its_regression(
            self, tmp_path):
        """A noisy lane's phase must not be blamed on stderr for a gate
        failure it was excluded from: only gated entries contribute
        attribution lines."""
        base = make_result(10000.0, fwd=0.1)
        base["entries"]["pipeline_1f1b_cpu_mesh"] = {
            "metrics": {"tokens_per_sec_chip": 1000.0},
            "trace_phases": {"pipeline_flush": {
                "count": 9, "total_s": 0.9, "p50_s": 0.1,
                "p95_s": 0.11, "p99_s": 0.12}}}
        fresh = copy.deepcopy(base)
        fresh["value"] = fresh["headline"]["value"] = 9000.0
        fresh["headline"]["trace_phases"] = phases(fwd=0.125)
        noisy = fresh["entries"]["pipeline_1f1b_cpu_mesh"]
        noisy["metrics"]["tokens_per_sec_chip"] = 500.0
        noisy["trace_phases"]["pipeline_flush"]["p50_s"] = 0.3
        path = self._history_with(tmp_path, base)
        rc, info = gate.run_gate(fresh, history_path=path)
        assert rc == gate.GATE_REGRESSED
        assert info["noisy_regressions_ignored"] == 1
        assert any("fwd" in a for a in info["attribution"])
        assert not any("pipeline_flush" in a for a in info["attribution"])

    def test_entries_only_record_does_not_shadow_headline_baseline(
            self, tmp_path):
        """The shipped-history shape: the LATEST record (recovered r05)
        has no headline, so naive latest-comparable selection would
        silently disarm the headline gate forever. Tier-1 selection must
        reach back to the last headline-bearing round and still fire."""
        path = self._history_with(tmp_path, make_result(10000.0, fwd=0.1),
                                  "r90")
        entries_only = {"schema_version": 2, "entries": {
            "comm_bw_onchip": {"metrics": {"rows": [
                {"op": "all_reduce", "busbw_gbps": 100.0}]}}}}
        history_mod.append_record(
            history_mod.record_from_result(entries_only, "r91"), path)
        rc, info = gate.run_gate(make_result(9000.0, fwd=0.125),
                                 history_path=path)
        assert info["baseline"] == "r90"
        assert rc == gate.GATE_REGRESSED
        assert any("fwd" in a for a in info["attribution"])

    def test_platform_declaring_fresh_run_skips_platformless_records(
            self, tmp_path):
        """Records of earlier rounds predate the platform field. A
        fresh run that DOES declare one (every schema-v2 headline) must
        not numeric-gate against them — a CPU box vs the TPU-recorded
        r02 headline reads as a fake -99%. No qualifying baseline ⇒
        GATE_OK; the gate re-arms once a platform-stamped record lands."""
        path = self._history_with(tmp_path, make_result(90000.0), "r90")
        fresh = make_result(900.0)                    # would be -99%
        fresh["headline"]["platform"] = "cpu"
        rc, info = gate.run_gate(fresh, history_path=path)
        assert rc == gate.GATE_OK
        assert info["baseline"] is None
        assert "no comparable baseline" in info["note"]
        # once a same-platform record exists, gating resumes against it
        stamped = make_result(10000.0, fwd=0.1)
        stamped["headline"]["platform"] = "cpu"
        history_mod.append_record(
            history_mod.record_from_result(stamped, "r91"), path)
        fresh2 = make_result(9000.0, fwd=0.125)
        fresh2["headline"]["platform"] = "cpu"
        rc, info = gate.run_gate(fresh2, history_path=path)
        assert info["baseline"] == "r91"
        assert rc == gate.GATE_REGRESSED

    def test_noisy_only_record_yields_to_gateable_entries_record(
            self, tmp_path):
        """Tier 2: with no headline-bearing record anywhere, the baseline
        must carry at least one NON-noisy comparable entry — a record
        whose only comparables are CPU-mesh noise lanes would have every
        regression filtered, a baseline that can never fire."""
        gateable = {"schema_version": 2, "entries": {
            "zero3_llama_750m_bf16": {
                "metrics": {"tokens_per_sec_chip": 24000.0}}}}
        noisy_only = {"schema_version": 2, "entries": {
            "comm_cpu_mesh_world8": {"metrics": {"busbw_world8": [
                {"op": "all_reduce", "busbw_gbps": 1.75}]}}}}
        path = str(tmp_path / "history.jsonl")
        history_mod.append_record(
            history_mod.record_from_result(gateable, "r90"), path)
        history_mod.append_record(
            history_mod.record_from_result(noisy_only, "r91"), path)
        fresh = make_result()
        fresh["entries"]["zero3_llama_750m_bf16"]["metrics"][
            "tokens_per_sec_chip"] = 20000.0          # -16.7% vs r90
        rc, info = gate.run_gate(fresh, history_path=path)
        assert info["baseline"] == "r90"
        assert rc == gate.GATE_REGRESSED

    def test_noisy_cpu_mesh_lanes_do_not_fail_the_gate(self, tmp_path):
        base = make_result()
        base["entries"]["comm_cpu_mesh_world8"] = {"metrics": {
            "busbw_world8": [{"op": "all_reduce", "busbw_gbps": 1.75}]}}
        fresh = copy.deepcopy(base)
        fresh["entries"]["comm_cpu_mesh_world8"]["metrics"][
            "busbw_world8"][0]["busbw_gbps"] = 1.12      # the real r03→r05 swing
        path = self._history_with(tmp_path, base)
        rc, info = gate.run_gate(fresh, history_path=path)
        assert rc == gate.GATE_OK
        assert info["noisy_regressions_ignored"] == 1


class TestNoiseBand:
    """Per-platform noise band (ISSUE 16 satellite): a regression inside
    the lane's own measured round-to-round noise floor warns instead of
    failing — the CPU lane's r08 fired on a ~5.5% drift with zero code
    changes against a ~14% same-platform noise floor."""

    def _cpu_history(self, tmp_path, values=(90.0, 100.0)):
        # values land in file order: the LAST one is the gate baseline;
        # all of them feed the noise-band stddev
        path = str(tmp_path / "history.jsonl")
        for i, v in enumerate(values):
            res = make_result(v)
            res["headline"]["platform"] = "cpu"
            history_mod.append_record(
                history_mod.record_from_result(res, f"r{90 + i}"), path)
        return path

    def _fresh(self, value):
        res = make_result(value)
        res["headline"]["platform"] = "cpu"
        return res

    def test_band_derived_from_same_platform_history(self, tmp_path):
        path = self._cpu_history(tmp_path)
        records, _ = history_mod.load_history(path)
        band = gate.platform_noise_band(
            records, "cpu", make_result()["headline"]["metric"])
        # [90, 100]: sample stddev 7.07, mean 95 → 2σ_rel ≈ 0.1489
        assert band == pytest.approx(0.1489, abs=1e-3)
        # under 2 samples or no declared platform → no band
        assert gate.platform_noise_band(records[:1], "cpu", None) is None
        assert gate.platform_noise_band(records, None, None) is None

    def test_band_is_capped(self, tmp_path):
        path = self._cpu_history(tmp_path, values=(10.0, 100.0))
        records, _ = history_mod.load_history(path)
        band = gate.platform_noise_band(records, "cpu", None)
        assert band == gate.NOISE_BAND_CAP

    def test_env_override_and_disable(self, monkeypatch):
        monkeypatch.setenv("BENCH_GATE_NOISE", "0.2")
        assert gate.platform_noise_band([], None, None) == 0.2
        monkeypatch.setenv("BENCH_GATE_NOISE", "0")
        assert gate.platform_noise_band([], "cpu", None) is None
        monkeypatch.setenv("BENCH_GATE_NOISE", "garbage")
        assert gate.platform_noise_band([], "cpu", None) is None

    def test_within_band_regression_warns_not_fails(self, tmp_path):
        # -8% vs the r91 baseline: past the 5% threshold, inside the
        # ~14.9% derived band → reported under noise_within_band, rc 0
        path = self._cpu_history(tmp_path)
        rc, info = gate.run_gate(self._fresh(92.0), history_path=path)
        assert rc == gate.GATE_OK and info["ok"]
        assert info["noise_band"] == pytest.approx(0.1489, abs=1e-3)
        assert info["noise_within_band"]
        assert not info["regressions"]

    def test_beyond_band_regression_still_fails(self, tmp_path):
        path = self._cpu_history(tmp_path)
        rc, info = gate.run_gate(self._fresh(80.0), history_path=path)
        assert rc == gate.GATE_REGRESSED
        assert info["regressions"]

    def test_noise_zero_restores_the_strict_gate(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv("BENCH_GATE_NOISE", "0")
        path = self._cpu_history(tmp_path)
        rc, info = gate.run_gate(self._fresh(92.0), history_path=path)
        assert rc == gate.GATE_REGRESSED
        assert "noise_band" not in info

    def test_error_transition_always_gates(self, tmp_path, monkeypatch):
        # an error is never noise: even a sky-high band must not waive a
        # measured → errored headline (delta_frac is None there)
        monkeypatch.setenv("BENCH_GATE_NOISE", "10")
        path = self._cpu_history(tmp_path)
        fresh = self._fresh(92.0)
        for side in (fresh, fresh["headline"]):
            side["value"] = 0
            side["error"] = "entry timed out after 123s"
        rc, info = gate.run_gate(fresh, history_path=path)
        assert rc == gate.GATE_REGRESSED
        assert any(r["delta_frac"] is None for r in info["regressions"])


class TestBenchDiffCli:
    def test_injected_regression_flagged_from_a_recovered_record(
            self, tmp_path, capsys):
        """Acceptance: bench-diff against a RECOVERED (entries-only, no
        headline) history record flags an injected ≥10% synthetic
        regression inside a row table; exit 1 on it, 0 on parity."""
        hist = str(tmp_path / "history.jsonl")
        recovered = {"schema_version": 2, "headline": {}, "entries": {
            "comm_cpu_mesh_world8": {"metrics": {"compressed_wire_world8": [
                {"op": "all_reduce_exact_fp32", "wire_reduction": 1.0},
                {"op": "reduce_scatter_qgz_int8",
                 "wire_reduction": 3.94}]}}}}
        history_mod.append_record(
            history_mod.record_from_result(recovered, "r05"), hist)
        r05 = history_mod.record_for_round("r05", path=hist)
        fresh = copy.deepcopy(r05["result"])
        wire = fresh["entries"]["comm_cpu_mesh_world8"]["metrics"][
            "compressed_wire_world8"]
        qgz = next(r for r in wire if r["op"] == "reduce_scatter_qgz_int8")
        qgz["wire_reduction"] = round(qgz["wire_reduction"] * 0.85, 2)
        fresh_path = str(tmp_path / "fresh.json")
        with open(fresh_path, "w") as f:
            json.dump(fresh, f)
        rc = cli.main(["r05", fresh_path, "--history", hist,
                       "--repo", REPO])
        out = capsys.readouterr().out
        assert rc == gate.GATE_REGRESSED
        assert "reduce_scatter_qgz_int8.wire_reduction" in out
        assert "REGRESSED" in out
        # parity: the record against itself is clean
        assert cli.main(["r05", "r05", "--history", hist,
                         "--repo", REPO]) == gate.GATE_OK

    def test_round_spec_falls_back_to_committed_artifact(self, tmp_path,
                                                         capsys):
        """r03 resolved straight from BENCH_r03.json when the history
        file doesn't know it — live tail recovery through the CLI."""
        empty_hist = str(tmp_path / "h.jsonl")
        rc = cli.main(["r03", "r03", "--history", empty_hist,
                       "--repo", REPO, "--format", "json"])
        assert rc == gate.GATE_OK
        diff = json.loads(capsys.readouterr().out)
        assert "zero3_llama_750m_bf16" in diff["entries"]

    def test_synthetic_phase_attribution_through_the_cli(self, tmp_path,
                                                         capsys):
        old_p, new_p = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        with open(old_p, "w") as f:
            json.dump(make_result(10000.0, fwd=0.1), f)
        with open(new_p, "w") as f:
            json.dump(make_result(8900.0, fwd=0.130), f)
        rc = cli.main([old_p, new_p, "--format", "markdown"])
        out = capsys.readouterr().out
        assert rc == gate.GATE_REGRESSED
        assert "Attribution" in out and "'fwd'" in out

    def test_usage_error_exits_2(self, capsys):
        assert cli.main(["/nonexistent/x.json", "latest"]) \
            == gate.GATE_ERROR
        assert "error" in capsys.readouterr().err

    def test_unpadded_round_spec_resolves_like_padded(self, tmp_path):
        """`r5` and `r05` are the same round — both must resolve through
        history first (a superseding record must not be bypassed in
        favor of a committed BENCH_r05.json artifact)."""
        hist = str(tmp_path / "history.jsonl")
        superseding = make_result(tps=12345.0)
        history_mod.append_record(
            history_mod.record_from_result(superseding, "r05"), hist)
        padded = cli.resolve_spec("r05", hist, REPO)
        unpadded = cli.resolve_spec("r5", hist, REPO)
        assert unpadded == padded
        label, result, _ = unpadded
        assert label == "r05"
        # the history record won — not a live artifact re-recovery
        assert result["headline"]["value"] == 12345.0

    def test_directory_spec_exits_2_not_traceback(self, tmp_path, capsys):
        """An unreadable spec (a directory) is an internal error (2),
        never a 'regression found' (1) — CI reads the dslint-shaped
        contract."""
        assert cli.main([str(tmp_path), "r03", "--repo", REPO,
                         "--history", str(tmp_path / "h.jsonl")]) \
            == gate.GATE_ERROR
        assert "error" in capsys.readouterr().err

    def test_malformed_round_spec_exits_2_not_traceback(self, tmp_path,
                                                        capsys):
        assert cli.main(["rr3", "r03", "--repo", REPO,
                         "--history", str(tmp_path / "h.jsonl")]) \
            == gate.GATE_ERROR
        assert "error" in capsys.readouterr().err

    def test_infinity_metric_renders_without_traceback(self, tmp_path,
                                                       capsys):
        """json.loads accepts the Infinity literal; a corrupted artifact
        carrying one must not traceback out of the renderer (exit 1 is
        reserved for real regressions)."""
        old_p, new_p = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        with open(old_p, "w") as f:
            json.dump(make_result(10000.0), f)
        bad = make_result(10000.0)
        bad["entries"]["zero3_llama_750m_bf16"]["metrics"][
            "tokens_per_sec_chip"] = float("inf")
        with open(new_p, "w") as f:
            f.write(json.dumps(bad))              # emits Infinity literal
        rc = cli.main([old_p, new_p])
        out = capsys.readouterr().out
        assert rc in (gate.GATE_OK, gate.GATE_REGRESSED)
        assert "inf" in out

    def test_shim_runs_without_the_framework_or_jax(self, tmp_path):
        """tools/bench-diff must work on a box where jax (and the
        framework __init__ that imports it) is unavailable — the stub
        parent package keeps the observatory stdlib-only end to end."""
        old_p, new_p = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        with open(old_p, "w") as f:
            json.dump(make_result(10000.0), f)
        with open(new_p, "w") as f:
            json.dump(make_result(10000.0), f)
        driver = str(tmp_path / "drive.py")
        with open(driver, "w") as f:
            f.write(
                "import runpy, sys\n"
                "class _Block:\n"
                "    def find_spec(self, name, path=None, target=None):\n"
                "        if name == 'jax' or name.startswith('jax.'):\n"
                "            raise ImportError('jax blocked by test')\n"
                "sys.meta_path.insert(0, _Block())\n"
                f"sys.argv = ['bench-diff', {old_p!r}, {new_p!r}]\n"
                f"runpy.run_path({os.path.join(REPO, 'tools', 'bench-diff')!r}, "
                "run_name='__main__')\n")
        out = subprocess.run([sys.executable, driver],
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr[-800:]
        assert "bench-diff" in out.stdout

    def test_no_gate_forces_zero(self, tmp_path, capsys):
        old_p, new_p = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        with open(old_p, "w") as f:
            json.dump(make_result(10000.0), f)
        with open(new_p, "w") as f:
            json.dump(make_result(5000.0), f)
        assert cli.main([old_p, new_p, "--no-gate"]) == gate.GATE_OK
