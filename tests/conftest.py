"""Test bootstrap: force an 8-device virtual CPU mesh.

This is the "distributed-without-a-cluster" harness (reference
``tests/unit/common.py`` ``DistributedExec``; SURVEY.md §4) — multi-chip behavior
is exercised on host-platform virtual devices with REAL XLA collectives.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DSTPU_ACCELERATOR"] = "cpu"

# 8 device threads can time-slice a single core on small runners: the
# default 20s/40s collective-rendezvous deadlines then abort long fused
# programs spuriously (F rendezvous.cc:127) — raise them well clear. The
# flags only exist in some jaxlib builds and unknown XLA_FLAGS hard-abort
# the backend (which used to kill the whole session) — probe first.
from deepspeed_tpu.utils.xla_compat import (  # noqa: E402
    cpu_collective_timeout_flags,
)

os.environ["XLA_FLAGS"] = (
    os.environ["XLA_FLAGS"] + cpu_collective_timeout_flags()).strip()

# The lane's time is XLA's CPU compiles (two thirds of a family file's wall),
# and six xdist workers ask for more cores than a shared box gives them:
# LLVM's optimisation of toy-sized programs is what the lane can do without
# (seven files at once under -O0 and under -O1: 1,168 against 1,489 CPU
# seconds, PR 62; -O0 against the default on one kernel file: 140 / 247).
# The HLO passes run as they did; a flag the caller set stands; child
# processes (launcher, chaos) inherit the variable. A test that holds real
# CPU seconds to a bound compiles its own program optimised
# (``jax.jit(compiler_options=)``: ``test_tick_account.py``).
for _flag in ("--xla_backend_optimization_level=0",
              "--xla_llvm_disable_expensive_passes=true"):
    if _flag.split("=")[0] not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += " " + _flag

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
# every engine points JAX at <checkout>/.jax_cache (utils/compile_cache.py);
# the suite must compile what it tests, not load a previous run's executables
jax.config.update("jax_enable_compilation_cache", False)


def pytest_sessionstart(session):
    n = len(jax.devices())
    assert n >= 8, (
        f"tests need >=8 virtual CPU devices, got {n}. XLA_FLAGS must be set "
        "before the first jax backend use")


@pytest.fixture(autouse=True)
def _reset_global_state():
    yield
    # Each test may build its own mesh; reset globals between tests.
    from deepspeed_tpu.comm import comm as comm_mod
    from deepspeed_tpu.comm import mesh as mesh_mod

    mesh_mod.reset_mesh()
    comm_mod._initialized = False
    comm_mod.comms_logger.reset()
    comm_mod.comms_logger.enabled = False


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: measured >= 5s on the 1-core box "
        "(tests/slow_tests.txt; fast pre-commit tier = -m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests that kill/signal REAL "
        "subprocesses (CPU backend, no TPU I/O — runs in tier-1; "
        "deselect with -m 'not chaos' on boxes where subprocesses are "
        "restricted)")
    config.addinivalue_line(
        "markers", "analysis: dslint static-analysis tests (AST-only, no "
        "device work; the self-enforcement pass runs the full linter over "
        "deepspeed_tpu/ and fails tier-1 on any non-baselined finding)")
    config.addinivalue_line(
        "markers", "bench: perf-trajectory observatory tests (schema "
        "validator, legacy-round recovery, bench-diff attribution, "
        "regression-gate exit codes — stdlib-level, tier-1-eligible "
        "under JAX_PLATFORMS=cpu; the committed BENCH_r02/r03.json and "
        "records written into tmp_path are the fixtures)")
    config.addinivalue_line(
        "markers", "observatory: XLA execution-observatory tests "
        "(compiled-collective ledger over committed HLO fixtures, "
        "overlap-meter estimator math, roofline step reports — tier-1-"
        "eligible under JAX_PLATFORMS=cpu; the live e2e tests lower the "
        "real zero2/zero3 tiny-model step on the 8-device virtual mesh)")
    config.addinivalue_line(
        "markers", "overlap: bucketed compute/collective overlap-scheduler "
        "tests (pure bucket planning, bucketed-vs-unbucketed engine "
        "allclose per ZeRO stage on the 8-device virtual mesh, async "
        "start/done pair pinning over committed HLO fixtures — tier-1-"
        "eligible under JAX_PLATFORMS=cpu)")
    config.addinivalue_line(
        "markers", "hlolint: compiled-program contract-checker tests "
        "(rule passes + committed contracts over the committed HLO "
        "fixtures, CLI exit-code matrix, shrink-only contract rewrites, "
        "live engine.lint_step + bench refuse-to-record — tier-1-"
        "eligible under JAX_PLATFORMS=cpu; the seven committed "
        "observatory_fixtures/*.hlo.txt are enforced against "
        "analysis/hlolint/contracts/ here)")
    config.addinivalue_line(
        "markers", "memlint: compiled-program MEMORY contract-checker "
        "tests (donation/aliasing verification over the committed HLO "
        "fixtures' entry headers, residency vs the ZeRO prediction, "
        "shrink-only memory contracts, the OOM pre-flight refusal at "
        "initialize, the PR-14 double-donation shape caught statically "
        "— tier-1-eligible under JAX_PLATFORMS=cpu; the seven committed "
        "observatory_fixtures/*.hlo.txt are enforced against "
        "analysis/memlint/contracts/ here)")
    config.addinivalue_line(
        "markers", "overload: serving burst/shedding tests (CPU backend, "
        "tier-1-eligible). Each runs under a SIGALRM per-test timeout "
        "(default 120s; overload(timeout_s=N) overrides) so a Python-level "
        "hang (spinning drain loop, deadlocked bookkeeping) fails THAT "
        "test fast instead of eating the suite budget. A hang inside a "
        "C-level XLA call can't be interrupted this way — the outer "
        "tier-1 `timeout` still bounds those")
    config.addinivalue_line(
        "markers", "guardian: training-run guardian tests (numerics "
        "sentinel skip-update, EMA anomaly bands, checkpoint rollback + "
        "microbatch bisect + bad-batch quarantine over the checkpointable "
        "loader, bounded escalation into the elastic agent — CPU backend, "
        "tier-1-eligible under JAX_PLATFORMS=cpu; the chaos acceptance "
        "runs arm train/nan_grads and data/poison_batch against a bf16 "
        "zero-3 engine and pin the curve against an uninjected twin)")
    config.addinivalue_line(
        "markers", "fleet: multi-replica serving-fleet tests (FleetRouter "
        "failover/hedging/draining over chaos-killed and chaos-hung "
        "replicas — CPU backend, tier-1-eligible under JAX_PLATFORMS=cpu; "
        "the zero-lost-uid / zero-KV-leak invariants are the acceptance "
        "criteria)")
    config.addinivalue_line(
        "markers", "elastic: world-size-elastic tests (universal-resume "
        "bit-coherence matrix 8→{4,2} on sub-meshes of the 8-device "
        "virtual host, placement-oracle refusal, reshard CLI exit codes, "
        "ElasticAgent resharding rebuilds incl. a REAL subprocess kill + "
        "forced device-count change — CPU backend, tier-1-eligible under "
        "JAX_PLATFORMS=cpu; heavy uninterrupted-twin comparisons ride "
        "the slow lane)")
    config.addinivalue_line(
        "markers", "tenancy: multi-tenant QoS tests (per-tenant quotas, "
        "weighted-fair admission, tier-aware shedding, tenant-scoped "
        "poison quarantine, fleet-wide per-tenant accounting — CPU "
        "backend, tier-1-eligible under JAX_PLATFORMS=cpu; the "
        "hot-tenant chaos acceptance pins zero-loss + exact per-tenant "
        "reconciliation through a replica kill AND an autoscale resize "
        "mid-burst; also registered in pytest.ini)")
    config.addinivalue_line(
        "markers", "racelint: concurrency contract-checker tests (static "
        "thread-roster/shared-state/lock-order/blocking/signal rules over "
        "committed fixture files, CLI exit-code matrix, shrink-only "
        "concurrency contracts, the full self-enforcement pass over "
        "deepspeed_tpu/ with an EMPTY baseline, and the DYNAMIC lockset/"
        "lock-order sanitizer catching seeded race + deadlock fixtures "
        "deterministically under the sync_point interleaving fuzzer — "
        "AST + threads only, tier-1-eligible under JAX_PLATFORMS=cpu)")
    config.addinivalue_line(
        "markers", "slo: fleet-observatory tests (request-lifecycle "
        "ledger + goodput/waste reconciliation, multi-window SLO "
        "burn-rate alerting, KV/prefix opportunity metering, tenant-"
        "filtered exposition, bench schema-v2.6 slo blocks, the "
        "fleet-report CLI exit-code matrix — CPU backend, tier-1-"
        "eligible under JAX_PLATFORMS=cpu; the chaos acceptance pins a "
        "fast-window burn alert FIRING during a replica-kill burst and "
        "CLEARING after quorum recovery under an injected clock, with "
        "zero lost uids and observe-only decision equality)")
    config.addinivalue_line(
        "markers", "autotune: observatory-driven plan-engine tests "
        "(plan schema + canary enforcement, analytic OOM refusal, "
        "plan-key purity, engine plan-cache hit/stale/fail_on_stale, "
        "bench gate noise band, predicted-state pins against the "
        "committed memlint contracts — tier-1-eligible under "
        "JAX_PLATFORMS=cpu on the 8-device virtual mesh)")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Per-test SIGALRM timeout for ``overload``-marked tests (no
    pytest-timeout on this image). Only armed on the main thread of a
    platform with SIGALRM; elsewhere the marker is timeout-less."""
    import signal
    import threading

    marker = item.get_closest_marker("overload")
    if marker is None or not hasattr(signal, "SIGALRM") \
            or threading.current_thread() is not threading.main_thread():
        return (yield)
    timeout_s = marker.kwargs.get("timeout_s", 120)

    def _on_alarm(signum, frame):
        pytest.fail(f"overload test exceeded its {timeout_s}s timeout "
                    "(hung engine tick?)", pytrace=True)

    old_handler = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout_s)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


def pytest_collection_modifyitems(config, items):
    """Mark nodeids listed in tests/slow_tests.txt as slow — the list is
    measured data (tools/update_slow_marks.py), not hand-maintained
    decorators. Fast tier: ``pytest -m "not slow"`` (~7 min vs ~57)."""
    import os

    path = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    if not os.path.exists(path):
        return
    slow = {ln.strip() for ln in open(path)
            if ln.strip() and not ln.startswith("#")}
    for item in items:
        if item.nodeid in slow:
            item.add_marker(pytest.mark.slow)
