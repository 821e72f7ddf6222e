"""Unified telemetry: metrics registry, trace spans, exposition.

One process-wide registry unifies the repo's metric islands — fenced timers
(``utils/timer``), monitor fan-out (``monitor/monitor``), FLOPS profiling
(``profiling/flops_profiler``), comms stats (``utils/comms_logging``) — and
the two hot subsystems are instrumented end-to-end (``runtime/engine``,
``inference/fastgen``). Read paths: a Prometheus-text ``/metrics`` HTTP
endpoint, a JSON ``snapshot()``, and a bridge into ``MonitorMaster`` so
CSV/TensorBoard/W&B get every scalar for free.

Module-level convenience API (all operate on the default registry)::

    from deepspeed_tpu import telemetry

    ticks = telemetry.counter("fastgen_ticks_total", "SplitFuse ticks")
    ticks.inc(kind="decode")
    with telemetry.span("decode_tick"):      # histogram + XLA trace annotation
        run_tick()
    telemetry.snapshot()                      # JSON-ready dict
    srv = telemetry.start_metrics_server(0)   # /metrics on an ephemeral port

Metric name catalog: README.md "Observability".
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from deepspeed_tpu.telemetry.bridge import MonitorBridge
from deepspeed_tpu.telemetry.exposition import (
    MetricsServer,
    clear_health_probes,
    clear_slo_provider,
    health_probe_names,
    health_report,
    register_health_probe,
    render_prometheus as _render,
    snapshot as _snapshot,
    start_metrics_server as _start_server,
    stop_metrics_server as _stop_server,
    unique_health_probe_name,
    unregister_health_probe,
)
from deepspeed_tpu.telemetry.registry import (
    DEFAULT_WINDOW_INTERVALS,
    DEFAULT_REGISTRY as _default_registry,
    DEFAULT_WINDOW_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    label_key,
)
from deepspeed_tpu.telemetry.spans import StallWatchdog, span as _span
from deepspeed_tpu.telemetry import host, tracing
from deepspeed_tpu.telemetry.host import (
    compile_seconds,
    engine_init,
    gc_pause_seconds,
    install_compile_account,
    install_gc_span,
    refresh as refresh_host_counters,
)
from deepspeed_tpu.telemetry.tracing import (
    Tracer,
    configure as configure_tracing,
    get_tracer,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricsServer",
    "MonitorBridge", "StallWatchdog", "Tracer", "counter", "gauge",
    "histogram", "get_registry", "get_tracer", "configure_tracing",
    "tracing", "span", "snapshot", "render_prometheus",
    "start_metrics_server", "stop_metrics_server", "add_collector", "reset",
    "register_health_probe", "unregister_health_probe", "health_report",
    "health_probe_names", "clear_health_probes", "unique_health_probe_name",
    "label_key", "install_gc_span", "gc_pause_seconds",
    "install_compile_account", "compile_seconds", "engine_init",
    "refresh_host_counters",
]


def get_registry() -> MetricsRegistry:
    return _default_registry


def counter(name: str, description: str = "") -> Counter:
    return _default_registry.counter(name, description)


def gauge(name: str, description: str = "") -> Gauge:
    return _default_registry.gauge(name, description)


def histogram(name: str, description: str = "",
              buckets: Optional[Sequence[float]] = None,
              window_s: float = DEFAULT_WINDOW_S,
              window_intervals: int = DEFAULT_WINDOW_INTERVALS) -> Histogram:
    return _default_registry.histogram(
        name, description, buckets=buckets,
        window_s=window_s, window_intervals=window_intervals)


#: ``span(name, attrs={...}, **labels)`` on the default registry: the class
#: itself, so that a call site pays for no wrapper (``telemetry/spans.py``)
span = _span


def add_collector(fn) -> None:
    _default_registry.add_collector(fn)


def snapshot() -> Dict[str, Any]:
    return _snapshot(_default_registry)


def render_prometheus() -> str:
    return _render(_default_registry)


def start_metrics_server(port: int = 0) -> MetricsServer:
    return _start_server(_default_registry, port=port)


def stop_metrics_server() -> None:
    _stop_server()


def reset() -> None:
    """Tests only: stop the server, clear the default registry, drop any
    registered health probes and /slo provider, take the ``gc_pause``
    span out of ``gc.callbacks`` and the compile account's listeners out
    of ``jax.monitoring``, and disable/clear the default tracer."""
    _stop_server()
    clear_health_probes()
    clear_slo_provider()
    host.reset()
    tracing.reset()
    _default_registry.reset()
