"""Circuit breaker around the FastGen engine tick.

The serving loop's failure mode is not one bad request — it is a sick
device (runtime crashed, HBM poisoned, device lost) making
EVERY tick raise. Without a breaker each incoming request still pays a
full tick attempt before failing, so a dead replica burns its whole
queue at device-timeout speed. The breaker converts that into fail-fast:

* **closed** — normal service; consecutive tick failures are counted and
  any success resets the streak.
* **open** — after ``failure_threshold`` consecutive failures, ticks are
  rejected immediately (no engine call) for a backoff window. Each
  re-open doubles the backoff up to ``backoff_max_s`` (exponential
  backoff against a persistently sick device). The window endpoint is
  stretched by up to ``jitter_frac`` of uniform jitter: N replicas of a
  fleet that trip together on one shared fault would otherwise compute
  identical ``_open_until`` windows and probe in lockstep — a
  fleet-level thundering herd against whatever they share. The doubling
  ramp itself stays un-jittered (deterministic severity), only the
  window endpoint spreads. ``rng`` is injectable/seedable so tests with
  an injected clock stay deterministic.
* **half-open** — when the backoff window expires, exactly ONE probe
  tick is let through; success closes the circuit (and resets the
  backoff), failure re-opens it with the doubled window.

State is exported as the ``serving_circuit_state`` gauge (0 = closed,
1 = half-open, 2 = open — monotone in severity) and every transition
bumps ``serving_circuit_transitions_total{to=...}``. The clock is
injectable so tests drive the backoff window deterministically.

Dependency-free (stdlib + the telemetry registry, which is itself
stdlib-only): importable from health-check threads without touching a
device runtime.
"""
from __future__ import annotations

import random
import time
from typing import Callable, Optional

from deepspeed_tpu import telemetry

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

#: gauge encoding, monotone in severity (alert on > 0)
_STATE_VALUE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class CircuitBreaker:
    """Consecutive-failure circuit breaker with half-open probing.

    Not thread-safe by itself — the serving loop owns it (the same
    single-threaded contract as ``FastGenEngine``).
    """

    def __init__(self, failure_threshold: int = 5, backoff_s: float = 0.5,
                 backoff_max_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic,
                 jitter_frac: float = 0.0,
                 rng: Optional[random.Random] = None):
        self.failure_threshold = failure_threshold
        self.backoff_s = backoff_s
        self.backoff_max_s = backoff_max_s
        self.jitter_frac = jitter_frac
        # seedable so an injected-clock test path is deterministic; the
        # frontend seeds it from the replica NAME so co-tripping replicas
        # de-synchronize while each one's schedule stays reproducible
        self._rng = rng if rng is not None else random.Random()
        self._clock = clock
        self.state = CLOSED
        self.failure_streak = 0
        self._open_until = 0.0
        self._cur_backoff = backoff_s
        self._tm_state = telemetry.gauge(
            "serving_circuit_state",
            "engine-tick circuit: 0=closed, 1=half-open, 2=open")
        self._tm_trans = telemetry.counter(
            "serving_circuit_transitions_total",
            "circuit state transitions by destination state")
        self._tm_state.set(0)

    # ------------------------------------------------------------------ #
    def _transition(self, state: str) -> None:
        if state == self.state:
            return
        self.state = state
        self._tm_state.set(_STATE_VALUE[state])
        self._tm_trans.inc(to=state)
        if state == OPEN:
            # the flight recorder holds the ticks/requests that led to
            # the failure streak — dump them while they're still in the
            # buffer (no-op unless telemetry.tracing is on)
            from deepspeed_tpu.telemetry import tracing

            tracing.get_tracer().dump_flight(
                "circuit_open",
                note=f"failure_streak={self.failure_streak}")

    def allow(self) -> bool:
        """Whether a tick may run now. An expired open window transitions
        to half-open and admits exactly ONE probe — further calls reject
        until the probe's record_success/record_failure lands (each exits
        half-open), so a sick device never sees back-to-back probes."""
        if self.state == CLOSED:
            return True
        if self.state == OPEN and self._clock() >= self._open_until:
            self._transition(HALF_OPEN)
            return True
        # OPEN inside the window, or HALF_OPEN with the probe outstanding
        return False

    def record_success(self) -> None:
        self.failure_streak = 0
        if self.state != CLOSED:
            self._cur_backoff = self.backoff_s   # healthy again: reset ramp
            self._transition(CLOSED)

    def _jittered(self, backoff: float) -> float:
        """The open-window length actually applied: the ramp value
        stretched by up to ``jitter_frac`` (never shortened — jitter must
        not probe a sick device EARLIER than the ramp promises)."""
        if self.jitter_frac <= 0.0:
            return backoff
        return backoff * (1.0 + self.jitter_frac * self._rng.random())

    def record_failure(self) -> None:
        self.failure_streak += 1
        if self.state == HALF_OPEN:
            # failed probe: re-open with doubled backoff (capped)
            self._cur_backoff = min(self._cur_backoff * 2,
                                    self.backoff_max_s)
            self._open_until = self._clock() + self._jittered(
                self._cur_backoff)
            self._transition(OPEN)
        elif self.state == CLOSED and \
                self.failure_streak >= self.failure_threshold:
            self._open_until = self._clock() + self._jittered(
                self._cur_backoff)
            self._transition(OPEN)

    def retry_after_s(self) -> Optional[float]:
        """Seconds until the next probe window (None when not open) —
        the honest retry-after hint for circuit-open rejections."""
        if self.state != OPEN:
            return None
        return max(0.0, self._open_until - self._clock())
