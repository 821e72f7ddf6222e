"""ServingFrontend: the resilience wrapper around ``FastGenEngine``.

The engine is a scheduler — it admits what it is given and backpressures
on KV capacity, but it has no opinion about *whether* a request should
be admitted, what to do when traffic exceeds capacity, or how to keep
the loop alive when a tick raises. This front-end owns those policies:

* **bounded admission** — ``submit()`` applies the queue cap and KV
  high-watermark (``serving/admission.py``) and answers with a
  structured :class:`Admitted` / :class:`Overloaded` / :class:`Rejected`
  instead of letting the queue grow without limit;
* **load shedding + degradation** — the configured shed policy picks a
  victim when a bound is hit (at most one per admission), and under KV
  pressure new grants are clamped before anyone is shed;
* **circuit breaking + poison isolation** — ``run_tick()`` wraps the
  engine tick: consecutive failures open the circuit
  (``serving/circuit.py``), and on each failing tick the newest request
  admitted since the last healthy tick is evicted and failed (reason
  ``poisoned``) — the loop was healthy before it arrived, so it is the
  prime suspect; a device-wide fault leaves no suspects and accumulates
  into the breaker instead;
* **terminal resolution** — every submitted uid ends in exactly one
  terminal state (``completed | shed | expired | failed | rejected``)
  queryable via :meth:`result`; shed/expired/failed requests release
  their KV blocks at resolution, so a burst can never leak pool blocks;
* **request-scoped tracing** — when ``telemetry.tracing`` is on, every
  uid gets a flight-recorder trace: admission verdict (incl. shed /
  overload reasons), queue wait at first service, the tick spans that
  served it, and its terminal state — one slow request's full timeline
  is reconstructable from ``/trace`` or a flight dump.

Single-threaded like the engine itself: one loop calls ``submit``/
``run_tick``; the health probes (``serving/health.py``) are the only
cross-thread readers and touch host scalars only.

Chaos hooks: ``run_tick`` passes through the ``serving/hang`` and
``serving/tick`` fault points (``deepspeed_tpu/testing/chaos.py``) so
tests and operators can inject tick failures
(``DSTPU_CHAOS="serving/tick=fail:3"``) or tick HANGS
(``serving/hang=hang:0.5:3`` — blocks without raising, the
stale-heartbeat shape) and watch the circuit / staleness detectors
react. Both points are scoped by the frontend's resolved ``name``, so a
fleet can target one replica (``serving/tick@replica-1=fail:999``).
"""
from __future__ import annotations

import collections
import dataclasses
import random
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Union

from deepspeed_tpu import telemetry
from deepspeed_tpu.serving.admission import (
    REASON_CIRCUIT_OPEN,
    REASON_INVALID,
    AdmissionController,
    Admitted,
    Overloaded,
    Rejected,
    _Candidate,
    retry_after_from_backlog,
)
from deepspeed_tpu.serving.circuit import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
)
from deepspeed_tpu.serving.health import HealthSurface
from deepspeed_tpu.serving.tenancy import TenantRegistry
from deepspeed_tpu.telemetry import exposition
from deepspeed_tpu.telemetry import tracing as _tracing
from deepspeed_tpu.testing.chaos import chaos_point
from deepspeed_tpu.utils.logging import logger

#: terminal request states (every submit eventually lands in exactly one)
COMPLETED = "completed"
SHED = "shed"
EXPIRED = "expired"
FAILED = "failed"
REJECTED = "rejected"
ACTIVE = "active"

#: the two series of ``serving_loop_seconds_total``, keyed once
_LOOP_PARTS = (telemetry.label_key(part="tick"),
               telemetry.label_key(part="caller"))


@dataclasses.dataclass
class RequestResult:
    uid: int
    state: str                       # active | completed | shed | ...
    tokens: List[int] = dataclasses.field(default_factory=list)
    reason: str = ""
    detail: str = ""
    # resolved tenant the request ran under ("" only on legacy records
    # constructed without one — every frontend/fleet path stamps it)
    tenant: str = ""


class _Request:
    __slots__ = ("uid", "max_new_tokens", "degraded", "submit_t", "order",
                 "abs_deadline", "served", "first_token", "tenant",
                 "quota_blocks")

    def __init__(self, uid: int, max_new_tokens: int, degraded: bool,
                 submit_t: float, order: int,
                 abs_deadline: Optional[float], tenant: str,
                 quota_blocks: int):
        self.uid = uid
        self.max_new_tokens = max_new_tokens
        self.degraded = degraded
        self.submit_t = submit_t
        self.order = order
        self.abs_deadline = abs_deadline   # frontend clock; None = none
        self.served = False                # first prefill progress seen
        self.first_token = False           # first generated token seen
        self.tenant = tenant               # resolved tenant name
        self.quota_blocks = quota_blocks   # KV charge held in the registry


class ServingFrontend:
    """Admission + shedding + circuit breaking + health over one
    ``FastGenEngine``. ``config`` is a ``ServingSectionConfig``, a plain
    dict of its keys, or None (defaults); ``clock`` is injectable for
    deterministic tests."""

    def __init__(self, engine, config=None,
                 clock=time.monotonic, register_health: bool = True,
                 health_name: str = "serving", tenancy=None):
        from deepspeed_tpu.runtime.config import ServingSectionConfig
        from deepspeed_tpu.runtime.config_utils import config_from_dict

        if config is None:
            config = ServingSectionConfig()
        elif isinstance(config, dict):
            config = config_from_dict(ServingSectionConfig, config,
                                      path="serving.")
        else:
            config.validate()   # dict path validates inside from_dict
        self.engine = engine
        self.cfg = config
        self.clock = clock
        # per-tenant quotas / fairness / quarantine (serving/tenancy.py):
        # a TenancySectionConfig, a dict of its keys, an existing
        # TenantRegistry (fleet replicas SHARE one so quotas hold
        # fleet-wide), or None — defaults are quota-free, so untagged
        # single-tenant callers see pre-tenancy behavior exactly
        self.tenancy = TenantRegistry.ensure(tenancy, clock=clock)
        # resolve the replica NAME first (unique against registered health
        # probes when registering): it scopes this frontend's chaos points
        # and seeds its breaker jitter — a fleet hands out distinct names
        # itself when register_health is off
        self.name = telemetry.unique_health_probe_name(health_name) \
            if register_health else health_name
        self.breaker = CircuitBreaker(
            failure_threshold=config.circuit_failure_threshold,
            backoff_s=config.circuit_backoff_s,
            backoff_max_s=config.circuit_backoff_max_s, clock=clock,
            jitter_frac=config.circuit_jitter_frac,
            # per-NAME seed: deterministic per replica, distinct across
            # replicas — seeding all replicas identically would recreate
            # the lockstep-probe herd the jitter exists to break
            rng=random.Random(zlib.crc32(self.name.encode())))
        self.ctrl = AdmissionController(
            max_queue=config.max_queue,
            kv_high_watermark=config.kv_high_watermark,
            kv_degrade_watermark=config.kv_degrade_watermark,
            degraded_max_new_tokens=config.degraded_max_new_tokens,
            shed_policy=config.shed_policy)
        self._reqs: Dict[int, _Request] = {}      # active only
        # terminal records, insertion-ordered and bounded (oldest evicted
        # past cfg.max_result_history): sustained overload with fresh uids
        # must not grow frontend memory without limit
        self._results: Dict[int, RequestResult] = {}
        # rejected uids in record order, lazily invalidated — gives the
        # evict-rejections-first policy an O(1) victim during exactly the
        # rejection storms that exercise it (entries whose record was
        # dropped or superseded are skipped at pop time)
        self._rejected_fifo: collections.deque = collections.deque()
        self._order_counter = 0
        self._ticks_run = 0              # protected ticks entered, for spans
        self._suspects: List[int] = []   # admitted since last healthy tick
        # stamped by run_tick on the serving loop; the health-probe thread
        # only READS it (atomic float — tearing-tolerant by design)
        self.last_tick_t: Optional[float] = None   # guarded-by: single-writer
        # wall duration of the last COMPLETED tick (any outcome): a router
        # in the same thread can't observe a hang while it's blocked inside
        # the tick, so post-hoc duration is its hang-vs-crash evidence
        self.last_tick_duration_s: float = 0.0   # guarded-by: single-writer
        # the last return of run_tick while a request was still active
        # (None: none was, and the wait for the next is no one's share)
        self._loop_return_t: Optional[float] = None
        # the default tracer is a stable singleton (configure mutates it
        # in place) — cache the handle; every call is a no-op while
        # tracing is disabled
        self._tracer = _tracing.get_tracer()
        # fleet observatory back-reference (serving/observatory): the
        # owning FleetRouter installs one; every hook below is
        # None-guarded so a standalone frontend pays nothing
        self.observatory = None
        self._setup_telemetry()
        self.health: Optional[HealthSurface] = None
        if register_health:
            # a second frontend in one process (multi-model replica) must
            # not silently replace the first one's probes — and closing
            # either must not unregister the survivor's — so the collision
            # suffix above picked a fresh name
            self.health = HealthSurface(self, name=self.name)

    @classmethod
    def from_ds_config(cls, engine, config, **kw) -> "ServingFrontend":
        """Build from a full runtime config (dict / JSON path /
        ``DeepSpeedTPUConfig``), using its ``"serving"`` and
        ``"tenancy"`` sections."""
        from deepspeed_tpu.runtime.config import load_config

        full_cfg = load_config(config)
        kw.setdefault("tenancy", full_cfg.tenancy)
        return cls(engine, config=full_cfg.serving, **kw)

    def adopt_tenancy(self, registry: TenantRegistry) -> None:
        """Swap in a SHARED tenant registry (fleet install / rolling
        restart), re-homing any live charges so fleet-wide quotas stay
        exact through ``replace_replica`` and autoscaler resizes."""
        if registry is self.tenancy:
            return
        for req in self._reqs.values():
            self.tenancy.release(req.tenant, req.quota_blocks)
            registry.transfer_inflight(req.tenant, req.quota_blocks)
        self.tenancy = registry
        # keep ?tenant= exposition filtering addressable exactly as far
        # as the tenancy label-cardinality guard records labels
        exposition.set_tenant_filter_cap(registry.cfg.max_tenant_labels)

    # ------------------------------------------------------------------ #
    def _setup_telemetry(self) -> None:
        telemetry.install_gc_span()
        self._tm_loop = telemetry.counter(
            "serving_loop_seconds_total",
            "seconds of the serving loop by part: tick (run_tick entry to "
            "return) / caller (the previous return to this entry while a "
            "request was active). Over a run of ticks the engine's tick "
            "periods sum to caller + tick; tick less the engine's six "
            "phases is the frontend's own share")
        self._tm_admit = telemetry.counter(
            "serving_admitted_total", "requests admitted past the front-end")
        self._tm_reject = telemetry.counter(
            "serving_rejected_total",
            "requests rejected at admission, by reason "
            "(queue_full / kv_pressure / circuit_open / invalid)")
        self._tm_shed = telemetry.counter(
            "serving_shed_total",
            "live requests shed to admit newer traffic, by policy")
        self._tm_degrade = telemetry.counter(
            "serving_degraded_total",
            "admissions whose max_new_tokens was clamped under KV pressure")
        self._tm_resolved = telemetry.counter(
            "serving_resolved_total",
            "requests reaching a terminal state, by outcome")
        self._tm_wait = telemetry.histogram(
            "serving_queue_wait_seconds",
            "submit() to first prefill progress (service start)")
        self._tm_tick_fail = telemetry.counter(
            "serving_tick_failures_total",
            "engine ticks that raised, by exception type")
        self._tm_poison = telemetry.counter(
            "serving_poison_evictions_total",
            "suspect requests evicted after a failing tick")
        # per-tenant series: labels pass through the registry's
        # cardinality guard (over-cap tenants fold into "other")
        self._tm_t_admit = telemetry.counter(
            "serving_tenant_admitted_total",
            "requests admitted past the front-end, by tenant")
        self._tm_t_reject = telemetry.counter(
            "serving_tenant_rejected_total",
            "admission rejections by tenant and reason (capacity "
            "reasons plus tenant_rate_limited / tenant_concurrency / "
            "tenant_kv_quota / tenant_fair_share / tenant_quarantined)")
        self._tm_t_resolved = telemetry.counter(
            "serving_tenant_resolved_total",
            "terminal request states by tenant and outcome")
        # long sliding window (10 s × 60 intervals) so per-tenant SLO
        # objectives can read windowed bad-fractions over the burn-rate
        # engine's slow window; window shape binds at FIRST creation
        # process-wide, clock rebinding is per-call (fleet replicas all
        # share their router's clock, so last-wins is also all-win)
        self._tm_t_ttft = telemetry.histogram(
            "serving_tenant_ttft_seconds",
            "submit() to the harvest that first saw a generated token, "
            "by tenant (per-tenant p99 TTFT source)", window_s=600.0,
            window_intervals=60)
        self._tm_t_ttft.set_window_clock(self.clock)
        self._tm_t_quar = telemetry.counter(
            "serving_tenant_quarantines_total",
            "per-tenant poison quarantines tripped, by tenant")

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #
    def active_count(self) -> int:
        return len(self._reqs)

    def active_uids(self) -> List[int]:
        """Active uids in admission order (oldest first)."""
        return sorted(self._reqs, key=lambda u: self._reqs[u].order)

    def _tokens_of(self, uid: int) -> List[int]:
        """Tokens generated so far, empty when the engine no longer
        tracks the uid (flushed externally — the frontend must answer,
        not KeyError)."""
        if uid in self.engine.seqs:
            return list(self.engine.query(uid)[1])
        return []

    def result(self, uid: int) -> RequestResult:
        """Terminal record for ``uid``, or its live ``active`` view.
        Unknown uids raise KeyError (they were never submitted)."""
        if uid in self._reqs:
            return RequestResult(uid, ACTIVE, self._tokens_of(uid),
                                 tenant=self._reqs[uid].tenant)
        return self._results[uid]

    def drop_result(self, uid: int) -> None:
        """Forget a terminal record after delivering it (records are also
        evicted oldest-first past ``max_result_history`` as a backstop)."""
        self._results.pop(uid, None)

    def _record_result(self, result: RequestResult) -> None:
        prev = self._results.pop(result.uid, None)   # re-insert at tail
        self._results[result.uid] = result
        if result.state == REJECTED and \
                not (prev is not None and prev.state == REJECTED):
            # a uid re-rejected in place reuses its existing fifo entry —
            # one client hammering one uid through a long open window
            # must not grow the sidecar deque per retry
            self._rejected_fifo.append(result.uid)
        while len(self._results) > self.cfg.max_result_history:
            # evict oldest REJECTED records first: the rejected caller
            # already got its answer synchronously from submit(), while
            # completed/shed/expired records are what result() polling
            # exists for — a rejection storm must not wash those away
            victim = None
            while self._rejected_fifo:
                u = self._rejected_fifo.popleft()
                r = self._results.get(u)
                if r is not None and r.state == REJECTED:
                    victim = u
                    break
            self._results.pop(victim if victim is not None
                              else next(iter(self._results)))

    def _token_seconds(self) -> float:
        est = self.engine.est_token_seconds()
        return est if est is not None else self.cfg.assumed_token_seconds

    def _outstanding_tokens(self) -> int:
        """Backlog estimate: prompt tokens still to prefill + decode
        grant still unserved, across active requests."""
        total = 0
        for uid, req in self._reqs.items():
            seq = self.engine.seqs.get(uid)
            if seq is None or seq.done:
                continue
            total += seq.prefill_remaining
            total += max(0, req.max_new_tokens - len(seq.generated))
        return total

    def backlog_tokens(self) -> int:
        """Public backlog estimate (tokens still to prefill + decode) —
        what a fleet router multiplies by ``est_token_seconds()`` to score
        this replica's projected wait."""
        return self._outstanding_tokens()

    # ------------------------------------------------------------------ #
    # router hooks: cancellation + re-materialization
    # ------------------------------------------------------------------ #
    def cancel(self, uid: int, reason: str = "cancelled",
               detail: str = "") -> bool:
        """Resolve an ACTIVE uid as ``failed(reason)`` and release its KV
        blocks — the router's hedge-cancel / migration / failover hook.
        Returns False (no-op) for unknown or already-terminal uids, so a
        cancel racing a completion never clobbers the real outcome."""
        if uid not in self._reqs:
            return False
        self._resolve(uid, FAILED, self._tokens_of(uid), reason=reason,
                      detail=detail)
        return True

    def rematerialize(self, uid: int) -> Optional[Dict[str, Any]]:
        """Host-side snapshot of an active request for resubmission on
        ANOTHER replica: the original prompt, tokens generated so far
        (greedy decode continues bit-identically from prompt+generated),
        and the remaining decode grant. None when the uid is not active
        here or the engine no longer tracks it."""
        req = self._reqs.get(uid)
        if req is None:
            return None
        snap = self.engine.rematerialize(uid)
        if snap is None:
            return None
        snap["max_new_tokens"] = req.max_new_tokens
        snap["remaining_new_tokens"] = max(
            0, req.max_new_tokens - len(snap["generated"]))
        return snap

    def _kv_util(self, extra_blocks: int = 0) -> float:
        return self.engine.kv_utilization(extra_blocks)

    # ------------------------------------------------------------------ #
    # admission
    # ------------------------------------------------------------------ #
    def submit(self, uid: int, prompt: Sequence[int],
               deadline_s: Optional[float] = None,
               max_new_tokens: Optional[int] = None,
               tenant: Optional[str] = None,
               charge_quota: bool = True
               ) -> Union[Admitted, Overloaded, Rejected]:
        """Admit one request through the resilience ladder. Never raises
        for request-shaped problems — invalid requests come back as
        :class:`Rejected`, capacity problems as :class:`Overloaded`
        (both also recorded as terminal results for ``result(uid)``).

        ``tenant`` scopes the request to a QoS tenant (None/"" = the
        shared default tenant — pre-tenancy callers are unchanged).
        ``charge_quota=False`` is the fleet-dispatch path: the router
        already drew the tenant's rate buckets once at ITS front door,
        so replica-level (re)dispatches of the same request skip the
        rate check here (concurrency, KV quota, fairness and quarantine
        still apply — they meter live resources, not offered load)."""
        with telemetry.span("serving_submit", attrs={"uid": uid}):
            return self._submit(uid, list(prompt), deadline_s,
                                max_new_tokens, tenant, charge_quota)

    def _submit(self, uid: int, prompt: List[int],
                deadline_s: Optional[float],
                max_new_tokens: Optional[int], tenant: Optional[str],
                charge_quota: bool
                ) -> Union[Admitted, Overloaded, Rejected]:
        tenant = self.tenancy.resolve(tenant)
        if max_new_tokens is None:
            max_new_tokens = self.cfg.default_max_new_tokens
        # request trace opens at the front door so even a rejection has a
        # timeline (no-op if the uid is already live: a duplicate submit
        # must not clobber the live request's trace — its rejection lands
        # as an event on that trace instead)
        self._tracer.request_begin(uid, prompt_len=len(prompt),
                                   tenant=tenant)
        now = self.clock()
        # the deadline the ENGINE will enforce: an explicit per-request
        # one, else the engine's request_deadline_s default — the shed
        # policy must rank by the same deadline the scheduler expires by,
        # or deadline_aware protects requests that are about to expire
        eff_deadline_s = deadline_s if deadline_s is not None \
            else self.engine.request_deadline_s
        # fold finished-but-unharvested requests out of the queue first:
        # without this, work that completed during the LAST tick still
        # counts toward max_queue and spuriously rejects this admission
        self._harvest()

        # 1) validity — never shed a victim for a request that can't run
        if uid in self._reqs or uid in self.engine.seqs:
            return self._reject_invalid(uid, f"uid {uid} is still active",
                                        tenant=tenant)
        if len(prompt) >= self.engine.max_len:
            return self._reject_invalid(
                uid, f"prompt len {len(prompt)} >= engine max_len "
                f"{self.engine.max_len}", tenant=tenant)
        if not prompt:
            return self._reject_invalid(uid, "empty prompt", tenant=tenant)

        # 2) circuit open — fail fast INSIDE the backoff window. Once the
        # window expires the request is ADMITTED as the probe vehicle:
        # with an empty queue nothing ever calls run_tick (the documented
        # drive loops stop at zero active requests), so rejecting here
        # after expiry would brick the replica forever — the half-open
        # probe needs work to tick over
        if self.breaker.state != CLOSED:
            retry = self.breaker.retry_after_s()
            if retry is None or retry > 0:
                return self._reject_overloaded(
                    uid, REASON_CIRCUIT_OPEN,
                    retry if retry is not None
                    else self.cfg.circuit_backoff_s,
                    detail=f"circuit {self.breaker.state}", tenant=tenant)

        # 3) tenancy — quotas, rate limits, quarantine, and (under
        # contended capacity) the weighted-fair share check, BEFORE any
        # victim is considered: a request its tenant isn't entitled to
        # run must never shed someone else's work to make room
        tok_s = self._token_seconds()
        blocks_needed = len(prompt) // self.engine.block_size + 1
        # quota charge covers the decode growth too, not just the prompt
        # footprint the capacity check projects — released at resolution
        quota_blocks = (len(prompt) + max_new_tokens) \
            // self.engine.block_size + 1
        contended = (
            len(self._reqs) + 1 >= self.cfg.max_queue
            * self.tenancy.cfg.fair_contention_queue_frac
            or self._kv_util(blocks_needed) >= self.cfg.kv_degrade_watermark)
        gate = self.tenancy.admission_gate(
            tenant, cost_tokens=len(prompt) + max_new_tokens,
            blocks=quota_blocks, token_seconds=tok_s,
            contended=contended, charge_rate=charge_quota)
        if gate is not None:
            t_reason, t_retry, t_detail = gate
            return self._reject_overloaded(uid, t_reason, t_retry,
                                           detail=t_detail, tenant=tenant)

        # 4) capacity — queue cap and KV high watermark, shed per policy
        # (victim selection is tier-aware: batch pays before standard
        # pays before realtime, deadline slack breaking ties in-tier).
        # A firing SLO burn alert may tighten the queue bound — but ONLY
        # when the operator opted in (slo.shed_on_burn); the default
        # observe-only engine always answers 0.0 here
        obs = self.observatory
        tighten = obs.slo.shed_tighten() \
            if obs is not None and obs.slo is not None else 0.0
        reason = self.ctrl.overload_reason(
            len(self._reqs), self._kv_util(blocks_needed), tighten=tighten)
        if reason is not None:
            incoming = _Candidate(
                uid=uid, age_order=self._order_counter,
                deadline_s=(now + eff_deadline_s)
                if eff_deadline_s is not None else None,
                remaining_tokens=len(prompt) + max_new_tokens, incoming=True,
                tier_rank=self.tenancy.tier_rank(tenant))
            victim = self.ctrl.pick_victim(
                self._candidates(), incoming, now, tok_s)
            if victim is not None and reason == "kv_pressure":
                # shed only when freeing the victim's blocks can actually
                # clear the bound — killing a live request AND rejecting
                # the incoming one serves nobody (queue_full always
                # clears: any victim frees a slot)
                vblocks = len(self.engine.seqs[victim].blocks) \
                    if victim in self.engine.seqs else 0
                if self._kv_util(blocks_needed - vblocks) \
                        > self.ctrl.kv_high_watermark:
                    victim = None
            if victim is not None:
                self._shed(victim, reason)
                # one victim per admission: recheck, reject if still over
                reason = self.ctrl.overload_reason(
                    len(self._reqs), self._kv_util(blocks_needed),
                    tighten=tighten)
            if reason is not None:
                retry = retry_after_from_backlog(
                    self._outstanding_tokens(), tok_s)
                return self._reject_overloaded(uid, reason, retry,
                                               tenant=tenant)

        # 5) graceful degradation — clamp the grant before anyone sheds.
        # PROJECTED utilization (incoming prompt included), matching the
        # rejection check: the request that itself pushes the pool into
        # the degrade band must not escape the clamp
        grant, degraded = self.ctrl.degraded_grant(
            self._kv_util(blocks_needed), max_new_tokens)
        if degraded:
            self._tm_degrade.inc()

        # 6) admit (engine put is batch-atomic: raises admit nothing)
        try:
            self.engine.put([uid], [prompt], deadline_s=deadline_s)
        except ValueError as e:   # race-shaped residue; treat as invalid
            return self._reject_invalid(uid, str(e), tenant=tenant)
        self._order_counter += 1
        self._reqs[uid] = _Request(
            uid, grant, degraded, now, self._order_counter,
            (now + eff_deadline_s) if eff_deadline_s is not None else None,
            tenant, quota_blocks)
        self.tenancy.charge_admit(tenant, len(prompt) + max_new_tokens,
                                  quota_blocks)
        self._suspects.append(uid)
        self._results.pop(uid, None)   # resubmission of a terminal uid
        self._tm_admit.inc()
        self._tm_t_admit.inc(tenant=self.tenancy.label(tenant))
        self._tracer.request_event(uid, "admission", verdict="admitted",
                                   grant=grant, degraded=degraded)
        return Admitted(uid, grant, degraded)

    def _candidates(self) -> List[_Candidate]:
        out = []
        for uid, req in self._reqs.items():
            seq = self.engine.seqs.get(uid)
            if seq is None or seq.done:
                continue   # already terminal; harvest will resolve it
            out.append(_Candidate(
                uid=uid, age_order=req.order, deadline_s=req.abs_deadline,
                remaining_tokens=seq.prefill_remaining
                + max(0, req.max_new_tokens - len(seq.generated)),
                tier_rank=self.tenancy.tier_rank(req.tenant)))
        return out

    def _record_rejection(self, uid: int, reason: str, detail: str,
                          tenant: str = "") -> None:
        """Terminal record for a rejected submission — UNLESS the uid is
        currently active (a duplicate submission must not clobber the
        live request's lifecycle tracking)."""
        self._tm_reject.inc(reason=reason)
        self._tm_t_reject.inc(tenant=self.tenancy.label(tenant),
                              reason=reason)
        if uid not in self._reqs:
            self._record_result(RequestResult(uid, REJECTED, [], reason,
                                              detail, tenant=tenant))
            self._tm_resolved.inc(outcome=REJECTED)
            self._tm_t_resolved.inc(tenant=self.tenancy.label(tenant),
                                    outcome=REJECTED)
            self._tracer.request_end(uid, REJECTED, reason=reason,
                                     detail=detail, tenant=tenant)

    def _reject_invalid(self, uid: int, detail: str,
                        tenant: str = "") -> Rejected:
        self._tracer.request_event(uid, "admission", verdict="rejected",
                                   reason=REASON_INVALID, detail=detail)
        self._record_rejection(uid, REASON_INVALID, detail, tenant=tenant)
        return Rejected(uid, REASON_INVALID, detail)

    def _reject_overloaded(self, uid: int, reason: str, retry_after: float,
                           detail: str = "", tenant: str = "") -> Overloaded:
        self._tracer.request_event(
            uid, "admission", verdict="overloaded", reason=reason,
            retry_after_s=round(retry_after, 3), detail=detail)
        self._record_rejection(uid, reason, detail, tenant=tenant)
        return Overloaded(uid, reason, round(retry_after, 3),
                          self.ctrl.shed_policy, detail, tenant=tenant)

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    def _resolve(self, uid: int, state: str, tokens: List[int],
                 reason: str = "", detail: str = "",
                 flush: bool = True) -> None:
        """Move ``uid`` to a terminal state; frees engine bookkeeping
        (and its KV blocks) when it was admitted, and returns the
        tenant's registry charges."""
        if flush:
            self.engine.flush([uid])
        req = self._reqs.pop(uid, None)
        tenant = ""
        if req is not None:
            tenant = req.tenant
            self.tenancy.release(req.tenant, req.quota_blocks)
        if uid in self._suspects:
            self._suspects.remove(uid)
        self._record_result(RequestResult(uid, state, tokens, reason,
                                          detail, tenant=tenant))
        self._tm_resolved.inc(outcome=state)
        self._tm_t_resolved.inc(tenant=self.tenancy.label(tenant),
                                outcome=state)
        self._tracer.request_end(uid, state, reason=reason, detail=detail,
                                 tokens=len(tokens), tenant=tenant)

    def _shed(self, uid: int, reason: str) -> None:
        # waste attribution happens at the FLEET layer (the router may
        # carry this victim's tokens forward — only it knows whether
        # they were truly discarded), not here
        tokens = self._tokens_of(uid)
        self._tm_shed.inc(policy=self.ctrl.shed_policy)
        logger.warning(f"serving: shedding request {uid} "
                       f"(policy={self.ctrl.shed_policy}, reason={reason})")
        self._resolve(uid, SHED, tokens, reason=reason)

    def _evict_suspect(self, exc: BaseException) -> None:
        """Poison isolation: the newest request admitted since the last
        healthy tick is evicted and failed — the loop worked before it
        arrived. No suspects (a fault with no admission to blame) leaves
        the failure to the circuit breaker alone."""
        while self._suspects:
            uid = self._suspects.pop()
            if uid in self._reqs:
                tenant = self._reqs[uid].tenant
                self._tm_poison.inc()
                logger.warning(
                    f"serving: evicting suspect request {uid} after tick "
                    f"failure: {type(exc).__name__}: {exc}")
                self._resolve(uid, FAILED, self._tokens_of(uid),
                              reason="poisoned",
                              detail=f"{type(exc).__name__}: {exc}")
                # tenant-scoped containment: a tenant repeatedly caught
                # poisoning ticks trips ITS quarantine — the replica
                # keeps serving everyone else instead of eating the
                # whole blast through the breaker
                if self.tenancy.record_poison(tenant):
                    self._tm_t_quar.inc(tenant=self.tenancy.label(tenant))
                return

    def last_tick_age_s(self) -> Optional[float]:
        """Monotonic seconds since the last ``run_tick`` ENTRY (None before
        the first tick) — the router's staleness evidence. A concurrent
        observer sees this grow while a tick is blocked inside a hung
        device call; a same-thread router additionally reads
        ``last_tick_duration_s`` after the call returns."""
        if self.last_tick_t is None:
            return None
        return max(0.0, self.clock() - self.last_tick_t)

    def run_tick(self) -> bool:
        """One protected engine tick. Returns True when a tick ran and
        succeeded; False when the circuit rejected it or it failed (the
        failure is absorbed — the loop NEVER sees the exception)."""
        t0 = self.clock()
        # since the last return, with a request waiting: the caller's
        # share of the loop (its submits, its reads of what is new)
        caller_s = t0 - self._loop_return_t \
            if self._loop_return_t is not None else 0.0
        self.last_tick_t = t0              # heartbeat: the loop is alive
        try:
            return self._run_tick_guarded()
        finally:
            # every exit (success, rejection, absorbed failure, even a
            # propagating KeyboardInterrupt) stamps the duration a router
            # reads for post-hoc hang detection
            t1 = self.clock()
            self.last_tick_duration_s = t1 - t0
            self._tm_loop.inc_keys(_LOOP_PARTS, (t1 - t0, caller_s))
            self._loop_return_t = t1 if self._reqs else None

    def _run_tick_guarded(self) -> bool:
        if not self.breaker.allow():
            return False
        # a half-open probe's failure is presumed DEVICE fault (the
        # circuit opened on repeated failures before any of the currently
        # queued requests ticked) — don't scapegoat the request that
        # happened to carry the probe
        probing = self.breaker.state == HALF_OPEN
        try:
            self._ticks_run += 1
            with telemetry.span("serving_tick",
                                attrs={"tick": self._ticks_run}):
                # hang FIRST (a stuck tick blocks before it fails), then
                # the raise point; both scoped by replica name so fleet
                # chaos can target one replica (point@name rules)
                chaos_point("serving/hang", scope=self.name)
                chaos_point("serving/tick", scope=self.name)
                self.engine.step()
        except Exception as e:
            # always leave a trace: with no suspect to evict this branch
            # would otherwise be metrics-only, and a replica going dark
            # with zero log output is undebuggable. Bounded spam: ticks
            # inside an open window never reach here
            logger.warning(
                f"serving: engine tick failed ({type(e).__name__}: {e}); "
                f"failure streak {self.breaker.failure_streak + 1}, "
                f"circuit {self.breaker.state}")
            self._tm_tick_fail.inc(error=type(e).__name__)
            self._tracer.event("tick_failure", error=type(e).__name__,
                               streak=self.breaker.failure_streak + 1)
            self.breaker.record_failure()
            if not probing:
                self._evict_suspect(e)
            self._harvest()
            return False
        except BaseException:
            # KeyboardInterrupt/SystemExit mid-tick: still settle the
            # breaker before propagating — a half-open probe that records
            # nothing would wedge HALF_OPEN forever (allow() only has a
            # time-based escape from OPEN)
            self.breaker.record_failure()
            raise
        self.breaker.record_success()
        self._suspects.clear()
        self._harvest()
        return True

    def _harvest(self) -> None:
        """Fold engine state into request lifecycle: queue-wait
        observation at first service, time to first token when the first
        generated token is seen, terminal resolution (+ flush, which
        releases KV blocks) for expired / completed / grant-reached
        requests."""
        with telemetry.span("serving_harvest"):
            for uid in list(self._reqs):
                req = self._reqs[uid]
                seq = self.engine.seqs.get(uid)
                if seq is None:   # flushed behind our back — fail loudly-ish
                    self._resolve(uid, FAILED, [], reason="evicted",
                                  detail="sequence flushed outside the "
                                  "frontend", flush=False)
                    continue
                if not req.served and (seq.prefilled > 0 or seq.done):
                    req.served = True
                    wait_s = self.clock() - req.submit_t
                    self._tm_wait.observe(wait_s)
                    if self.observatory is not None:
                        # fleet TTFT: first service on ANY replica counts
                        # once (the observatory dedups hedge/failover copies)
                        self.observatory.note_first_service(uid, wait_s)
                    self._tracer.request_event(uid, "first_service",
                                               queue_wait_s=round(wait_s, 6))
                if not req.first_token and seq.generated:
                    # a token is visible to the caller at the harvest
                    # after the tick that sampled it: a long prompt's
                    # chunks lie between first service and this
                    req.first_token = True
                    ttft_s = self.clock() - req.submit_t
                    self._tm_t_ttft.observe(
                        ttft_s, tenant=self.tenancy.label(req.tenant))
                    self._tracer.request_event(uid, "first_token",
                                               ttft_s=round(ttft_s, 6))
                if seq.expired:
                    self._resolve(uid, EXPIRED, list(seq.generated),
                                  reason="deadline")
                elif seq.done or len(seq.generated) >= req.max_new_tokens:
                    self._resolve(uid, COMPLETED,
                                  list(seq.generated)[:req.max_new_tokens])

    def run_until_drained(self, max_ticks: int = 10_000,
                          open_wait_cap_s: float = 0.05,
                          deadline_s: Optional[float] = None) -> int:
        """Tick until no request is active (or ``max_ticks``, or
        ``deadline_s`` of wall clock); returns ticks consumed. While the
        circuit is open, each rejected tick sleeps toward the probe window
        (capped at ``open_wait_cap_s``) instead of busy-spinning a core
        through the backoff — so the drain actually waits out an open
        circuit rather than burning its whole tick budget in milliseconds.
        Callers writing their own loop should do the same with
        ``breaker.retry_after_s()``. ``deadline_s`` is the wall-clock
        escape the tick budget can no longer provide: with open-circuit
        sleeps in the loop, ``max_ticks`` bounds iterations but not TIME —
        a drain against a sick replica would otherwise wait out every
        doubled backoff window before giving up."""
        ticks = 0
        t0 = self.clock()
        while self._reqs and ticks < max_ticks:
            if deadline_s is not None and self.clock() - t0 >= deadline_s:
                break
            if not self.run_tick() and self.breaker.state == OPEN:
                retry = self.breaker.retry_after_s()
                # real wall sleep only under the real clock: with an
                # injected test clock the open window expires on FAKE
                # time, which no amount of real sleeping advances — the
                # test owns time and must advance it itself
                if retry and self.clock is time.monotonic:
                    wait = min(retry, open_wait_cap_s)
                    if deadline_s is not None:
                        wait = min(wait, max(
                            0.0, deadline_s - (self.clock() - t0)))
                    time.sleep(wait)
            ticks += 1
        return ticks

    def close(self) -> None:
        """Unregister health probes and resolve any still-active request
        as failed/draining (blocks released)."""
        for uid in list(self._reqs):
            self._resolve(uid, FAILED, self._tokens_of(uid),
                          reason="shutdown")
        if self.health is not None:
            self.health.close()
            self.health = None

    def __enter__(self) -> "ServingFrontend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
