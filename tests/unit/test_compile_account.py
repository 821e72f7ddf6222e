"""The compile account (``telemetry/host.py``): what the process traces,
lowers, loads from the persistent cache and compiles, by program, from
JAX's own ``jax.monitoring`` events; and the JAX behaviour the expert
step's meter was moved for: a program that holds a host callback is never
written to the persistent cache."""
import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring
from jax.experimental.compilation_cache import compilation_cache

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import host


@pytest.fixture
def account(tmp_path, monkeypatch):
    """The account installed over a persistent cache of the test's own
    (``tests/conftest.py`` turns the cache off for every other test; what
    it set is restored), every entry written however small or quick."""
    telemetry.reset()
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    telemetry.configure_tracing(enabled=True)
    telemetry.install_compile_account()
    # how long this box takes over a small program is not the tests' to
    # depend on: every event under its own label, but where a test says
    monkeypatch.setattr(host, "SMALL_PROGRAM_S", 0.0)
    yield telemetry.get_registry()
    telemetry.reset()
    for k, v in keep.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _events(registry, **labels):
    return sum(v for k, v in registry.get(
        "xla_program_events_total").labels_items()
        if labels.items() <= dict(k).items())


def _seconds(registry, **labels):
    return sum(v for k, v in registry.get(
        "xla_program_seconds_total").labels_items()
        if labels.items() <= dict(k).items())


def big_program(x):
    for i in range(40):
        x = jnp.sin(x) @ x + i
    return x


def big_program_with_callback(x):
    jax.debug.callback(lambda v: None, x[0, 0])
    return big_program(x)


def small_program(x):
    return x + 1


def test_compile_then_load_under_the_programs_own_label(account):
    """Compiled, ``jax.clear_caches()``, compiled again: a ``compile`` then
    a ``load`` (the persistent cache's hit fired inside the second
    backend-compile interval) under the function's own name, each phase's
    seconds its own; the saved seconds and the flight recorder's
    ``xla_compile`` spans follow."""
    x = jnp.ones((48, 48))
    for _ in range(2):
        jax.block_until_ready(jax.jit(big_program)(x))
        jax.clear_caches()
    assert _events(account, program="big_program", phase="compile") == 1
    assert _events(account, program="big_program", phase="load") == 1
    assert _events(account, program="big_program", phase="trace") == 2
    assert _events(account, program="big_program", phase="lower") == 2
    assert _seconds(account, program="big_program", phase="load") \
        < _seconds(account, program="big_program", phase="compile")
    # (JAX keeps an entry's compile time in whole seconds: what a hit of a
    # program this small saved reads 0 or less; never a counter's to lose)
    saved = account.get("xla_cache_seconds_saved_total")
    was = saved.total()
    assert was >= 0
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", 1.5)
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", -0.2)
    assert saved.total() - was == pytest.approx(1.5)
    # a running sum of every phase, the labels' sum
    assert host.compile_seconds() == pytest.approx(_seconds(account))
    telemetry.refresh_host_counters()
    spans = [(e["args"]["program"], e["args"]["phase"])
             for e in telemetry.get_tracer().export_chrome()["traceEvents"]
             if e["name"] == "xla_compile"]
    assert ("big_program", "compile") in spans
    assert ("big_program", "load") in spans


def test_an_event_under_50_ms_goes_to_other(account, monkeypatch):
    """The label set stays small: an event shorter than ``SMALL_PROGRAM_S``
    is accounted under ``program="other"``, its seconds kept."""
    monkeypatch.undo()
    assert host.SMALL_PROGRAM_S == 0.05
    event = "/jax/core/compile/backend_compile_duration"
    for seconds in (0.049, 0.051):
        monitoring.record_scalar(event, 0.0, fun_name="jit(tick)")
        monitoring.record_event_duration_secs(event, seconds,
                                              fun_name="jit(tick)")
    assert _seconds(account, program="tick", phase="compile") == 0.051
    assert _seconds(account, program="other", phase="compile") == 0.049
    assert _events(account) == 2
    jax.block_until_ready(jax.jit(small_program)(jnp.ones((3,))))
    assert _events(account, program="small_program", phase="trace") == 0
    assert _events(account, program="other", phase="trace") >= 1


def test_nested_events_are_counted_once(account):
    """An outer program's trace holds its inner ``jit``'s: the outer's
    series holds its seconds without the inner's, so the labels sum to the
    wall time of the outermost events."""
    inner = jax.jit(big_program)

    def outer(x):
        return inner(x) * 2.0

    x = jnp.ones((48, 48))
    spent = -host.compile_seconds()
    walls = []

    def spy(event, seconds, **kw):
        if kw.get("fun_name") in ("outer", "jit(outer)"):
            walls.append(seconds)

    monitoring.register_event_duration_secs_listener(spy)
    try:
        jax.block_until_ready(jax.jit(outer)(x))
    finally:
        monitoring.unregister_event_duration_listener(spy)
    spent += host.compile_seconds()
    assert _events(account, program="big_program", phase="trace") == 1
    # outer's trace, lowering and compile: everything inside is in them
    assert spent == pytest.approx(sum(walls), rel=1e-6)


def test_a_program_with_a_host_callback_compiles_every_time(account):
    """The JAX behaviour PR 51 rests on (``jax/_src/compiler.py``,
    ``_cache_write``: "Not writing persistent cache entry ... because it
    uses host callbacks"): with the cache on, a program that holds one
    ``jax.debug.callback`` reads ``compile`` both rounds."""
    x = jnp.ones((48, 48))
    for _ in range(2):
        jax.block_until_ready(jax.jit(big_program_with_callback)(x))
        jax.clear_caches()
    name = "big_program_with_callback"
    assert _events(account, program=name, phase="compile") == 2
    assert _events(account, program=name, phase="load") == 0


def test_reset_removes_the_listeners(account):
    def listeners():
        return (len(monitoring.get_scalar_listeners()),
                len(monitoring.get_event_listeners()),
                len(monitoring.get_event_duration_listeners()))

    installed = listeners()
    telemetry.install_compile_account()        # once a process
    assert listeners() == installed
    telemetry.reset()
    assert listeners() == tuple(n - 1 for n in installed)
    assert host.compile_seconds() == 0.0
    jax.block_until_ready(jax.jit(small_program)(jnp.ones((3,))))
    assert _events(account) == 0


def test_engine_init_is_a_span_with_its_parts(account):
    """Both engines' constructors: ``engine_init`` and, inside it, the
    parts they have; what the constructor spent under the compile path is
    counted beside it."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.inference.fastgen import FastGenEngine
    from deepspeed_tpu.models import transformer as T

    hist = account.histogram("span_seconds")

    def spans_of():
        return {dict(k)["span"]: c.count for k, c in hist.labels_items()}

    cfg = T.get_model_config("tiny")
    FastGenEngine(cfg, T.init_params(cfg, jax.random.PRNGKey(0)),
                  n_blocks=16, block_size=8, max_blocks_per_seq=4,
                  token_budget=16)
    serve = spans_of()
    assert all(serve.get(s) == 1 for s in (
        "engine_init", "device_attach", "params_init", "state_init"))
    dst.initialize(model=dst.causal_lm_spec(cfg), config={
        "train_micro_batch_size_per_gpu": 1, "train_batch_size": 8,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}}})
    both = spans_of()
    assert all(both.get(s) == 2 for s in (
        "engine_init", "device_attach", "params_init", "state_init"))
    under = account.get("engine_init_compile_seconds_total").total()
    assert 0 < under <= hist.summary(span="engine_init")["sum"]
