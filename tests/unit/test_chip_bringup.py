"""What the chip bring-up added, as far as a CPU can check it: the compile
cache's one rule, probes that raise instead of carrying on on the CPU,
and ``chip_smoke.py``'s refusals. That the smoke PASSES is only ever shown
by a run on the chip (CHANGES.md records those)."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run_py(args, cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "DSTPU_CHAOS")}
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, cwd=cwd,
        timeout=600, env={**base, "JAX_PLATFORMS": "cpu", **env})


# --------------------------------------------------------------------- #
# compile cache
# --------------------------------------------------------------------- #
class TestCompileCache:
    def test_env_variable_set_leaves_config_untouched(self, monkeypatch,
                                                      tmp_path):
        from deepspeed_tpu.utils import compile_cache

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.ensure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_names_are_part_of_the_cache_key(self, monkeypatch, tmp_path):
        """An executable cached under an older build's scopes and kernel
        names must not be loaded for the same arithmetic: a device trace
        carries the names of the executable that ran."""
        from deepspeed_tpu.utils import compile_cache

        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        flag = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, flag)
        try:
            jax.config.update(flag, False)
            compile_cache.ensure_compile_cache()
            assert getattr(jax.config, flag) is True
        finally:
            jax.config.update(flag, before)

    def test_unset_is_one_path_under_the_checkout_for_every_process(
            self, monkeypatch, tmp_path):
        from deepspeed_tpu.utils import compile_cache

        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            here = compile_cache.ensure_compile_cache()
            assert jax.config.jax_compilation_cache_dir == here
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert here == os.path.join(REPO, ".jax_cache")
        # another process, another working directory: the same directory
        out = run_py(
            ["-c", "import jax\n"
             "from deepspeed_tpu.utils.compile_cache import "
             "ensure_compile_cache\n"
             "p = ensure_compile_cache()\n"
             "assert jax.config.jax_compilation_cache_dir == p\n"
             "print(p)"], cwd=str(tmp_path), PYTHONPATH=REPO)
        assert out.returncode == 0, out.stderr[-800:]
        assert out.stdout.strip().splitlines()[-1] == here


# --------------------------------------------------------------------- #
# probes that raise
# --------------------------------------------------------------------- #
class TestProbesRaise:
    def test_detect_name_raises_when_the_backend_cannot_start(
            self, monkeypatch):
        from deepspeed_tpu.accelerator import real_accelerator

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu': "
                               "TPU is already in use by another process")

        monkeypatch.delenv("DSTPU_ACCELERATOR", raising=False)
        monkeypatch.setattr(jax, "devices", no_backend)
        with pytest.raises(RuntimeError, match="already in use"):
            real_accelerator._detect_name()

    def test_unknown_tpu_device_kind_is_an_error_not_a_default(self):
        from deepspeed_tpu.comm import bandwidth as BW
        from deepspeed_tpu.utils import chip_specs as C

        for lookup in (C.chip_peak_tflops, C.chip_hbm_gbps,
                       C.chip_hbm_bytes, BW.chip_link_gbps):
            with pytest.raises(C.UnknownChipError, match="TPU v9"):
                lookup("TPU v9")
        assert C.chip_peak_tflops("TPU v5 lite") == 197.0
        assert BW.chip_link_gbps("TPU v5 lite") == 200.0
        # a CPU host has no datasheet row and gets the caller's default
        assert C.chip_peak_tflops("cpu") is None
        assert BW.chip_link_gbps("cpu") == BW.DEFAULT_LINK_GBPS

    def test_tpu_memory_stats_raise_rather_than_report_zeros(
            self, monkeypatch):
        from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

        class NoStats:
            def memory_stats(self):
                return None

        monkeypatch.setattr(jax, "local_devices", lambda: [NoStats()])
        with pytest.raises(RuntimeError, match="memory_stats"):
            TPU_Accelerator().memory_stats()

    def test_plan_confirm_leg_fails_loudly_on_a_tpu(self, monkeypatch):
        import deepspeed_tpu as dst
        from deepspeed_tpu.autotuning import planner

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        engine = planner.PlanEngine(
            dst.causal_lm_spec("tiny"), {"train_micro_batch_size_per_gpu": 1},
            confirm_top_k=1)
        with pytest.raises(planner.PlanError, match="child process"):
            engine.run()


# --------------------------------------------------------------------- #
# chip_smoke.py
# --------------------------------------------------------------------- #
class TestChipSmoke:
    def test_without_a_tpu_it_exits_nonzero_and_prints_no_result(self):
        out = run_py([SMOKE], cwd=REPO)
        assert out.returncode != 0
        assert out.stdout.strip() == ""
        assert "not a TPU" in out.stderr

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        import shutil

        shutil.copy(SMOKE, tmp_path)
        out = run_py([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path),
                     PYTHONPATH="")
        assert out.returncode != 0
        assert out.stdout.strip() == ""

    def test_rehearsal_runs_every_phase_and_never_prints_the_pass_line(
            self):
        out = run_py([SMOKE, "--rehearse-cpu"], cwd=REPO,
                     XLA_FLAGS="--xla_force_host_platform_device_count=4")
        assert out.returncode == 0, out.stderr[-1500:]
        report, verdict = out.stdout.strip().splitlines()[-2:]
        # the last line holds exactly the keys the driver's check reads
        device = {"platform": "cpu", "kind": "cpu", "count": 4}
        assert json.loads(verdict) == {"ok": False, "device": device}
        res = json.loads(report)
        assert res["ok"] is False and res["rehearsal"] is True
        assert res["device"] == device
        assert set(res["phases"]) == {"kernels", "train", "serve"}
        train, serve = res["phases"]["train"], res["phases"]["serve"]
        assert train["layers"] == 8 and train["mesh"] == {"data": 4}
        assert train["losses"][-1] < train["losses"][0]
        assert len(train["shard_bytes_per_device"]["params"]) == 4
        assert serve["requests"] == 8 and serve["tick_failures"] == 0
        assert res["claim"] is None

    def test_a_failing_serving_tick_fails_the_serving_check(self):
        """'Drained' is not a pass: the frontend absorbs a tick failure
        by design (evicts a suspect, keeps ticking, every request ends
        terminal), so the smoke must count it."""
        import chip_smoke
        from deepspeed_tpu.testing import chaos

        chaos.arm("serving/tick=fail:1")
        try:
            with pytest.raises(AssertionError,
                               match="ended failed|tick_failures"):
                chip_smoke.serve_phase(chip_smoke.REHEARSAL, True)
        finally:
            chaos.disarm()
