"""Wall-clock timers with device-synchronization fences.

Parity: reference ``deepspeed/utils/timer.py`` (``SynchronizedWallClockTimer``,
``ThroughputTimer``). On TPU there are no user-visible streams/events, so
synchronization is a ``jax.block_until_ready`` fence on a trivial device value
(``accelerator.synchronize``) before reading the host clock — the
``is_synchronized_device`` escape hatch the reference keeps for exactly this
class of device (``accelerator/abstract_accelerator.py:19``).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist, logger

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"
TRAIN_BATCH_TIMER = "train_batch"


def _sync() -> None:
    from deepspeed_tpu.accelerator import get_accelerator

    get_accelerator().synchronize()


def _phase_hist():
    """Telemetry feed: every fenced timer stop lands in the unified
    registry as ``train_phase_seconds{phase=<timer name>}`` — the fwd/bwd/
    step breakdown becomes scrapeable instead of log-only. Looked up fresh
    per stop (locked dict get; timers only run under wall_clock_breakdown)
    so registry resets can't strand a cached handle."""
    from deepspeed_tpu import telemetry

    return telemetry.histogram(
        "train_phase_seconds",
        "fenced wall time of named engine phases (fwd/bwd/step/"
        "train_batch timers)")


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self.started = False
        self._start_time = 0.0
        self._elapsed = 0.0
        self._record: List[float] = []

    def start(self, sync: bool = True) -> None:
        if self.started:
            return
        if sync:
            _sync()
        self._start_time = time.perf_counter()
        self.started = True

    def stop(self, record: bool = True, sync: bool = True) -> None:
        if not self.started:
            return
        if sync:
            _sync()
        delta = time.perf_counter() - self._start_time
        self._elapsed += delta
        if record:
            self._record.append(delta)
        self.started = False
        try:
            _phase_hist().observe(delta, phase=self.name)
        except Exception as e:   # telemetry must never break a timer
            logger.debug(f"phase-histogram observe failed "
                         f"({type(e).__name__}: {e})")

    def reset(self) -> None:
        self.started = False
        self._elapsed = 0.0

    def elapsed(self, reset: bool = True) -> float:
        out = self._elapsed
        if self.started:
            out += time.perf_counter() - self._start_time
        if reset:
            self._elapsed = 0.0
        return out

    def mean(self) -> float:
        if not self._record:
            return 0.0
        return sum(self._record) / len(self._record)


class SynchronizedWallClockTimer:
    """Named timer registry; each timer fences the device before reading the clock."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def has_timer(self, name: str) -> bool:
        return name in self.timers

    @staticmethod
    def memory_usage() -> str:
        from deepspeed_tpu.accelerator import get_accelerator

        stats = get_accelerator().memory_stats()
        ib = stats.get("bytes_in_use", 0)
        pk = stats.get("peak_bytes_in_use", 0)
        return f"mem: in_use={ib / 2**30:.2f}GB peak={pk / 2**30:.2f}GB"

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            memory_breakdown: bool = False, ranks=None) -> None:
        assert normalizer > 0.0
        parts = []
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {elapsed:.2f}ms")
        msg = "time (ms) | " + " | ".join(parts)
        if memory_breakdown:
            msg += " | " + self.memory_usage()
        log_dist(msg, ranks=ranks or [0])

    def get_mean(self, names: List[str], normalizer: float = 1.0) -> Dict[str, float]:
        assert normalizer > 0.0
        return {
            n: self.timers[n].mean() * 1000.0 / normalizer
            for n in names
            if n in self.timers
        }


class ThroughputTimer:
    """Tracks samples/sec across steps (reference ``utils/timer.py`` analog).

    Unlike the reference (CUDA events are cheap), a device fence on TPU
    costs a full host↔device round trip and serializes the dispatch
    pipeline. So this timer measures
    WINDOWS: it fences once per ``steps_per_output`` report boundary and
    divides the window wall time by the steps in it. Between boundaries a
    train step pays zero sync overhead; with ``steps_per_output=None`` it
    never fences at all. Aggregate throughput is identical (each window is
    fence-to-fence wall time).
    """

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: Optional[int] = None,
                 monitor_memory: bool = False, logging_fn=None):
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or (lambda m: log_dist(m, ranks=[0]))
        self.global_step_count = 0
        self.local_step_count = 0
        self.total_elapsed_time = 0.0   # fenced wall time since start_step
        self._counted_steps = 0         # steps covered by total_elapsed_time
        self._window_start: Optional[float] = None
        self._window_steps = 0
        self.started = False

    def update_epoch_count(self) -> None:
        self.local_step_count = 0

    def _should_report(self, steps: int = 1) -> bool:
        """True when the last ``steps`` increment crossed a report boundary
        (a fused multi-step stop may jump OVER the exact multiple)."""
        spo = self.steps_per_output
        if not spo:
            return False
        return (self.global_step_count // spo) > \
            ((self.global_step_count - steps) // spo)

    def start(self) -> None:
        self.started = True
        if self._window_start is None and self.global_step_count >= self.start_step:
            _sync()  # one fence to open the measurement window
            self._window_start = time.perf_counter()
            self._window_steps = 0

    def stop(self, global_step: bool = True, report_speed: bool = True,
             steps: int = 1) -> None:
        """``steps`` > 1 credits one fused multi-step dispatch
        (engine.train_batches) with all the optimizer steps it ran."""
        if not self.started:
            return
        self.started = False
        self.local_step_count += steps
        if global_step:
            self.global_step_count += steps
        if self._window_start is None or not global_step:
            return
        self._window_steps += steps
        if self._should_report(steps):
            duration, steps = self._close_window()
            if report_speed and steps:
                self.logging(
                    f"step={self.global_step_count} "
                    f"samples/sec={self.avg_samples_per_sec():.2f} "
                    f"ms/step={duration / steps * 1000:.1f}")

    def _close_window(self):
        """Fence, accrue the open window, and start a new one."""
        _sync()
        duration = time.perf_counter() - self._window_start
        steps = self._window_steps
        self.total_elapsed_time += duration
        self._counted_steps += steps
        self._window_start = time.perf_counter()
        self._window_steps = 0
        return duration, steps

    def avg_samples_per_sec(self) -> float:
        # close the in-flight window lazily so the query is accurate at any
        # step (one fence per query, none per step)
        if self._window_start is not None and self._window_steps:
            self._close_window()
        if self._counted_steps == 0 or self.total_elapsed_time == 0.0:
            return 0.0
        return self.batch_size / (self.total_elapsed_time / self._counted_steps)
