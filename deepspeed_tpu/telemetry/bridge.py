"""Registry → MonitorMaster bridge: scalars fan out to CSV/TB/W&B for free.

The monitor backends speak ``(tag, value, step)`` events; the bridge walks
the registry's counters and gauges (histograms forward their count/sum —
the backends have no native histogram type) and writes one event batch.
The engine calls :meth:`publish` at its existing print boundary, so the
monitor cadence matches the reference's ``steps_per_print`` flow and no new
host syncs land on the hot path.
"""
from __future__ import annotations

from typing import List, Tuple

from deepspeed_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    format_labels,
)


class MonitorBridge:
    def __init__(self, monitor, registry: MetricsRegistry,
                 prefix: str = "Telemetry/"):
        self.monitor = monitor
        self.registry = registry
        self.prefix = prefix

    def _tag(self, name: str, key) -> str:
        # CSV backends turn '/' into '_'; labels flatten into the tag
        suffix = format_labels(key).replace('"', "").replace("{", ".") \
            .replace("}", "").replace("=", "_").replace(",", ".")
        return f"{self.prefix}{name}{suffix}"

    def events(self, step: int) -> List[Tuple[str, float, int]]:
        self.registry.collect()
        events: List[Tuple[str, float, int]] = []
        for metric in self.registry.metrics():
            if isinstance(metric, Histogram):
                for key, child in metric.labels_items():
                    base = self._tag(metric.name, key)
                    events.append((base + ".count", float(child.count), step))
                    events.append((base + ".sum", float(child.sum), step))
            elif isinstance(metric, (Counter, Gauge)):
                for key, value in metric.labels_items():
                    events.append((self._tag(metric.name, key),
                                   float(value), step))
        return events

    def publish(self, step: int) -> None:
        if self.monitor is None or not getattr(self.monitor, "enabled", False):
            return
        self.monitor.write_events(self.events(step))
