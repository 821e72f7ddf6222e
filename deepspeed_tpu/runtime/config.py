"""JSON config → typed config tree.

Parity: reference ``runtime/config.py:676`` (``DeepSpeedConfig``) and the pydantic
sub-models (``runtime/zero/config.py:90`` ``DeepSpeedZeroConfig``, fp16/bf16
sections, ``monitor/config.py``, comms logger config). Key names are kept
JSON-compatible with the reference so existing DeepSpeed configs parse unchanged
(CUDA-only knobs are accepted and ignored with a warning). TPU-native additions
live under the ``"mesh"`` section (parallel axis sizes).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

from deepspeed_tpu.runtime.config_utils import (
    DeepSpeedConfigError,
    config_from_dict,
)
from deepspeed_tpu.comm.mesh import MeshConfig
from deepspeed_tpu.runtime.zenflow import ZenFlowSectionConfig
from deepspeed_tpu.utils.logging import logger


@dataclasses.dataclass
class FP16Config:
    """Reference ``runtime/fp16`` config section."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0.0


@dataclasses.dataclass
class BF16Config:
    enabled: bool = False
    # bf16 grad accumulation dtype (reference bf16 section + data_types)
    immediate_grad_update: bool = True
    # False drops the fp32 master copy: params live in bf16, each optimizer
    # leaf computes its update in fp32 on the fly (no materialized fp32
    # tree). Not a reference option (its bf16_optimizer always keeps an
    # fp32 flat master, runtime/bf16_optimizer.py) — the TPU memory answer
    # for fitting multi-B-param models in one chip's HBM, paired with
    # optimizer="adafactor" (ops/optimizer.py).
    fp32_master: bool = True


@dataclasses.dataclass
class OptimizerConfig:
    type: str = "adam"
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class OffloadConfig:
    """Reference ``runtime/zero/offload_config.py`` analog."""
    device: str = "none"  # none | cpu (host memory) | nvme
    nvme_path: Optional[str] = None
    pin_memory: bool = True
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    ratio: float = 1.0
    # SuperOffload-class host execution (reference superoffload_stage3.py):
    # run the optimizer update ON the host CPU backend with fp32 master +
    # moments resident in host RAM; device keeps 16-bit params only.
    host_step: bool = False
    # ZenFlow overlap semantics for host_step: defer applying the host
    # update by one step so it fully overlaps device compute. None = unset:
    # zenflow.overlap_step decides when zenflow is enabled, else off. An
    # explicit False always wins (no silent staleness).
    overlap_step: Optional[bool] = None


@dataclasses.dataclass
class ZeroConfig:
    """Reference ``DeepSpeedZeroConfig`` (``runtime/zero/config.py:90``).

    On TPU the stages are sharding policies applied to the train state:
      0 = replicated; 1 = optimizer state sharded over data axes;
      2 = + gradients reduce-scattered; 3 = + parameters sharded (FSDP-style).

    Overlap scheduling (``parallel/overlap.py``; README "Overlap
    scheduler"): ``overlap_comm`` gates the bucketed compute/collective
    overlap scheduler inside the compiled step. ``reduce_bucket_size``
    bounds each gradient-sync bucket (leaves grouped and fenced so each
    bucket's reduce can start as soon as its grads are final);
    ``allgather_bucket_size`` bounds the layer-chunk parameters at
    stages 1-2; ``stage3_prefetch_bucket_size`` bounds the ZeRO-3
    layer-chunk whose parameters are all-gathered one chunk ahead of
    compute (the double-buffered prefetch). All three are the
    reference's JSON spellings, semantics AND units — ELEMENT counts
    (numel), not bytes, exactly as in ``stage_1_and_2.py`` IPG buckets
    and ``partitioned_param_coordinator`` prefetch — so a ported
    reference config buckets at the same granularity here.

    Step-phase overlap (the optimizer update — Automatic Cross-Replica
    Sharding of Weight Update, arXiv:2004.13336): ``overlap_step``
    splits the sharded weight update into ``update_bucket_size``-bounded
    fenced buckets in backward-completion order and defers the
    post-update parameter publish (cast/all-gather) behind the same
    fence chain, double-buffering the gathered compute params through
    train-step state into the NEXT step's forward. Rides the overlap
    scheduler (inactive when ``overlap_comm`` is off or stage < 1).
    ``update_bucket_size`` follows the PR-8 bucket-key contract
    (ELEMENT counts, float/"auto" coercion); ``"auto"`` = follow
    ``reduce_bucket_size`` so update buckets chain one-for-one onto the
    grad-sync buckets.
    """
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = True
    # step-phase overlap (2004.13336): bucketed weight update under the
    # fence chain + deferred param publish double-buffered into the next
    # forward. Gated by overlap_comm like the rest of the scheduler.
    overlap_step: bool = True
    # "auto" = follow reduce_bucket_size (update buckets chain onto the
    # grad-sync buckets one-for-one); element counts otherwise
    update_bucket_size: Any = "auto"
    offload_optimizer: OffloadConfig = dataclasses.field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = dataclasses.field(default_factory=OffloadConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_param_persistence_threshold: int = 100_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    # ZeRO++ knobs (hpZ / qwZ / qgZ — reference zero/config.py:309-330)
    zero_hpz_partition_size: int = 1
    # MiCS replica-group sharding (reference zero/mics.py:63 MiCS_Init): shard
    # ZeRO state within groups of this size, replicate across groups. Resolved
    # onto the 'zshard' mesh axis; zero_hpz_partition_size behaves the same way
    # (hpZ secondary partition = MiCS-style subgrouping on TPU).
    mics_shard_size: int = 0
    mics_hierarchical_params_gather: bool = False
    # ZenFlow importance-split updates (reference runtime/zenflow/)
    zenflow: "ZenFlowSectionConfig" = dataclasses.field(
        default_factory=lambda: ZenFlowSectionConfig())
    # SuperOffload alias (reference superoffload/superoffload_stage3.py):
    # equivalent to offload_optimizer={"device": "cpu", "host_step": true,
    # "overlap_step": true}
    super_offload: bool = False
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    zero_quantized_nontrainable_weights: bool = False
    # LoCo error feedback for the quantized gradient reduce (reference
    # runtime/comm/coalesced_collectives.py:81 all_to_all_loco_quant_reduce):
    # per-rank residual re-enters the next round's send. Requires
    # zero_quantized_gradients; costs one full-gradient-sized fp32 buffer
    # per rank.
    loco_error_feedback: bool = False
    round_robin_gradients: bool = False
    ignore_unused_parameters: bool = True

    def validate(self) -> None:
        if self.stage not in (0, 1, 2, 3):
            raise DeepSpeedConfigError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        for key in ("reduce_bucket_size", "allgather_bucket_size",
                    "stage3_prefetch_bucket_size"):
            val = getattr(self, key)
            # reference-ecosystem spellings normalize: JSON scientific
            # notation (5e8 -> float) coerces to int, HF-integration
            # "auto" falls back to the schema default
            if val == "auto":
                val = dataclasses.fields(type(self))
                val = next(f.default for f in val if f.name == key)
                setattr(self, key, val)
            elif isinstance(val, float) and not isinstance(val, bool) \
                    and float(val).is_integer():
                val = int(val)
                setattr(self, key, val)
            if not isinstance(val, int) or isinstance(val, bool) or val <= 0:
                # consumed by the overlap scheduler (parallel/overlap.py):
                # a zero/negative bucket would plan nothing silently
                raise DeepSpeedConfigError(
                    f"zero_optimization.{key} must be a positive int "
                    f"(elements), got {val!r}")
        # update_bucket_size follows the same normalization contract but
        # keeps "auto" as its resolved spelling: auto = follow
        # reduce_bucket_size (the engine resolves it, which knows the
        # final reduce bucket after ITS coercion)
        ub = self.update_bucket_size
        if ub != "auto":
            if isinstance(ub, float) and not isinstance(ub, bool) \
                    and float(ub).is_integer():
                ub = int(ub)
                self.update_bucket_size = ub
            if not isinstance(ub, int) or isinstance(ub, bool) or ub <= 0:
                raise DeepSpeedConfigError(
                    "zero_optimization.update_bucket_size must be a "
                    f"positive int (elements) or \"auto\", got {ub!r}")
        if not isinstance(self.overlap_step, bool):
            raise DeepSpeedConfigError(
                "zero_optimization.overlap_step must be a bool, got "
                f"{self.overlap_step!r}")
        # the subgroup keys follow the same normalization contract but
        # both have an OFF spelling the reference schema allows (hpZ:
        # ge=0 — 0 and 1 both mean no secondary partition; MiCS: 0) —
        # non-negative, never positive-only. Anything else raises loudly:
        # a malformed subgroup silently degrading to exact full-world
        # collectives is the config-no-op class of bug. The mesh-
        # dependent half (must divide and fit the device world) lives in
        # the engine, which knows the world.
        for key in ("zero_hpz_partition_size", "mics_shard_size"):
            val = getattr(self, key)
            if val == "auto":
                val = dataclasses.fields(type(self))
                val = next(f.default for f in val if f.name == key)
                setattr(self, key, val)
            elif isinstance(val, float) and not isinstance(val, bool) \
                    and float(val).is_integer():
                val = int(val)
                setattr(self, key, val)
            if not isinstance(val, int) or isinstance(val, bool) or val < 0:
                raise DeepSpeedConfigError(
                    f"zero_optimization.{key} must be a non-negative int "
                    f"(ranks; 0 = off), got {val!r}")


@dataclasses.dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    prof_ops: List[str] = dataclasses.field(default_factory=list)
    debug: bool = False


@dataclasses.dataclass
class TelemetryConfig:
    """The unified telemetry subsystem (``deepspeed_tpu/telemetry``).

    ``enabled`` gates metric recording process-wide (the registry is also
    process-0 gated like the monitor). ``http_port`` starts the Prometheus
    ``/metrics`` endpoint when >= 0 (0 = ephemeral port; -1 = off).
    ``stall_deadline_s`` arms the training stall watchdog: a warning (with
    the last-completed span) logs when no optimizer step finishes within
    the deadline. ``monitor_bridge`` forwards registry scalars into the
    configured MonitorMaster backends at the ``steps_per_print`` cadence
    (a no-op unless a monitor backend is enabled).

    ``tracing`` turns on the structured tracer + flight recorder
    (``telemetry/tracing.py``): every ``telemetry.span`` site and every
    serving request gets a timeline entry in a ring buffer of
    ``trace_buffer_events`` completed spans, sampled per trace at
    ``trace_sample_rate``, with crash-context dumps (stall, circuit
    open, preemption, engine-step exception) written under
    ``flight_dump_dir``. Off by default — a disabled tracer costs one
    attribute check per span."""
    enabled: bool = True
    http_port: int = -1
    stall_deadline_s: float = 0.0
    monitor_bridge: bool = True
    tracing: bool = False
    trace_buffer_events: int = 4096
    trace_sample_rate: float = 1.0
    flight_dump_dir: str = "flight_dumps"

    def validate(self) -> None:
        if not (0.0 <= self.trace_sample_rate <= 1.0):
            raise DeepSpeedConfigError(
                "telemetry.trace_sample_rate must be in [0, 1], got "
                f"{self.trace_sample_rate}")
        if self.trace_buffer_events < 1:
            raise DeepSpeedConfigError(
                "telemetry.trace_buffer_events must be >= 1, got "
                f"{self.trace_buffer_events} (a zero-size flight recorder "
                "dumps empty context)")


@dataclasses.dataclass
class HlolintSectionConfig:
    """Compiled-program contract enforcement at initialize
    (``deepspeed_tpu/analysis/hlolint``).

    ``enabled`` lowers the engine's REAL fused train step once at
    initialize (the same lowering the observatory ledger caches — no
    extra compile for jobs that also ledger/report) and runs the
    hlolint rule passes over it: async-pair structure, fenced bucket
    counts, wire dtypes, replication, host transfers. ``contract``
    names a committed contract JSON to hold the step to on top of the
    structural rules. With ``fail_on_violation`` (default) a violation
    refuses the job before any chip time is spent — the same posture
    bench.py takes before recording a round; off, violations log and
    the job proceeds."""
    enabled: bool = False
    contract: str = ""
    fail_on_violation: bool = True

    def validate(self) -> None:
        if self.contract and not isinstance(self.contract, str):
            raise DeepSpeedConfigError(
                f"hlolint.contract must be a path string, got "
                f"{type(self.contract).__name__}")


@dataclasses.dataclass
class MemlintSectionConfig:
    """Compiled-program MEMORY contract enforcement at initialize
    (``deepspeed_tpu/analysis/memlint`` — hlolint's memory-side
    sibling; README "Memory contracts").

    ``enabled`` lints the engine's REAL lowered train step once at
    initialize (the same cached observatory lowering hlolint and the
    ledger share — no extra compile): donation/aliasing verification
    over the entry header, residency vs the ZeRO partitioning-math
    prediction, and the OOM pre-flight gate. ``contract`` names a
    committed memory contract JSON to hold the step to on top.
    ``hbm_budget_bytes`` sets the pre-flight budget explicitly — 0
    (default) means the chip's datasheet HBM capacity
    (``utils/chip_specs``); the datasheet-less CPU tier arms the gate
    only from an explicit budget. With ``fail_on_violation`` (default)
    a violation refuses the job before any chip time is spent."""
    enabled: bool = False
    contract: str = ""
    hbm_budget_bytes: int = 0
    fail_on_violation: bool = True

    def validate(self) -> None:
        if self.contract and not isinstance(self.contract, str):
            raise DeepSpeedConfigError(
                f"memlint.contract must be a path string, got "
                f"{type(self.contract).__name__}")
        if not isinstance(self.hbm_budget_bytes, (int, float)) \
                or isinstance(self.hbm_budget_bytes, bool) \
                or self.hbm_budget_bytes < 0:
            raise DeepSpeedConfigError(
                "memlint.hbm_budget_bytes must be a non-negative byte "
                f"count (0 = datasheet capacity), got "
                f"{self.hbm_budget_bytes!r}")
        self.hbm_budget_bytes = int(self.hbm_budget_bytes)


@dataclasses.dataclass
class AutotuningSectionConfig:
    """Observatory-driven plan engine (``deepspeed_tpu/autotuning/planner``).

    Reuses the reference's ``"autotuning"`` section name (previously
    accepted-and-ignored on TPU) for the TPU-native plan cache:
    ``enabled`` makes the engine look up a committed plan for its
    ``(model_fingerprint, mesh_shape, wire_format, platform)`` key under
    ``plan_cache_dir`` at initialize and apply the planned knobs to any
    knob the user left at its default (explicit JSON settings always
    win). ``fail_on_stale`` refuses initialize when the user's explicit
    config CONTRADICTS the cached plan (a stale plan silently mis-tuned
    a job once; the refusal names the conflicting knobs) — off, the
    conflict logs and the user's values stand. ``confirm_top_k`` /
    ``max_candidates`` bound the planner's measured-confirmation windows
    and enumerated candidate count when ``tools/plan`` builds the cache.
    """
    enabled: bool = False
    plan_cache_dir: str = "autotune_plans"
    confirm_top_k: int = 2
    max_candidates: int = 64
    fail_on_stale: bool = False

    def validate(self) -> None:
        if not isinstance(self.plan_cache_dir, str):
            raise DeepSpeedConfigError(
                "autotuning.plan_cache_dir must be a path string, got "
                f"{type(self.plan_cache_dir).__name__}")
        if not isinstance(self.confirm_top_k, int) \
                or isinstance(self.confirm_top_k, bool) \
                or self.confirm_top_k < 0:
            raise DeepSpeedConfigError(
                "autotuning.confirm_top_k must be a non-negative int, "
                f"got {self.confirm_top_k!r}")
        if not isinstance(self.max_candidates, int) \
                or isinstance(self.max_candidates, bool) \
                or self.max_candidates < 1:
            raise DeepSpeedConfigError(
                "autotuning.max_candidates must be a positive int, got "
                f"{self.max_candidates!r}")


@dataclasses.dataclass
class ElasticitySectionConfig:
    """World-size-elastic training (``deepspeed_tpu/elasticity/``;
    README "Elastic worlds").

    Consumed by :class:`~deepspeed_tpu.elasticity.elastic_agent.
    ElasticAgent` via ``ElasticAgentConfig.from_section``: ``enabled``
    marks the run as supervise-and-resize (the launcher/driver decides
    to wrap ``train`` in an agent); ``max_restarts`` /
    ``restart_backoff_s`` / ``restart_backoff_max_s`` bound the
    supervised restart loop; ``reload_on_restart`` reloads the newest
    committed checkpoint on every rebuild — through the universal
    RESHARDING path when the acquired world differs from the
    checkpointed one. ``min_world_size`` is the floor below which a
    resize is terminal rather than a silent slow resume.
    ``hpz_candidates`` lists ZeRO++ hpZ subgroup sizes the placement
    oracle surveys per acquired world (non-divisors are skipped).
    ``universal_dir`` overrides where the resharding conversion lands
    ("" = ``<checkpoint_dir>/universal``). NOTE: the legacy reference
    keys (``elastic_training``/``micro_batch_sizes`` …) stay handled by
    ``elasticity/elasticity.compute_elastic_config`` — this section
    configures the TPU-native agent, not the batch-size solver."""
    enabled: bool = False
    max_restarts: int = 3
    restart_backoff_s: float = 1.0
    restart_backoff_max_s: float = 60.0
    reload_on_restart: bool = True
    min_world_size: int = 1
    hpz_candidates: list = dataclasses.field(default_factory=list)
    universal_dir: str = ""

    def validate(self) -> None:
        if not isinstance(self.max_restarts, int) \
                or isinstance(self.max_restarts, bool) \
                or self.max_restarts < 0:
            raise DeepSpeedConfigError(
                "elasticity.max_restarts must be a non-negative int, "
                f"got {self.max_restarts!r}")
        if self.restart_backoff_s <= 0 \
                or self.restart_backoff_max_s < self.restart_backoff_s:
            raise DeepSpeedConfigError(
                "elasticity restart backoff must satisfy 0 < "
                "restart_backoff_s <= restart_backoff_max_s, got "
                f"{self.restart_backoff_s} / {self.restart_backoff_max_s}")
        if not isinstance(self.min_world_size, int) \
                or isinstance(self.min_world_size, bool) \
                or self.min_world_size < 1:
            raise DeepSpeedConfigError(
                "elasticity.min_world_size must be a positive int, got "
                f"{self.min_world_size!r}")
        if not isinstance(self.hpz_candidates, (list, tuple)) or any(
                not isinstance(h, int) or isinstance(h, bool) or h < 1
                for h in self.hpz_candidates):
            raise DeepSpeedConfigError(
                "elasticity.hpz_candidates must be a list of positive "
                f"ints (subgroup sizes), got {self.hpz_candidates!r}")
        if not isinstance(self.universal_dir, str):
            raise DeepSpeedConfigError(
                "elasticity.universal_dir must be a path string, got "
                f"{type(self.universal_dir).__name__}")


@dataclasses.dataclass
class ServingSectionConfig:
    """Serving resilience front-end (``deepspeed_tpu/serving``).

    Admission is bounded by ``max_queue`` live requests and a KV-pool
    ``kv_high_watermark`` (projected utilization after admitting the
    prompt); past either bound the configured ``shed_policy`` decides who
    pays: ``reject_newest`` turns the incoming request away,
    ``reject_oldest`` sheds the longest-lived request to make room, and
    ``deadline_aware`` sheds whichever request (incoming included) is
    least likely to meet its deadline at current decode throughput.
    Between ``kv_degrade_watermark`` and the high watermark new
    admissions are accepted but their ``max_new_tokens`` is clamped to
    ``degraded_max_new_tokens`` (graceful degradation before shedding).

    The circuit breaker opens after ``circuit_failure_threshold``
    consecutive engine-tick failures: requests are rejected immediately
    for ``circuit_backoff_s`` (doubling per re-open up to
    ``circuit_backoff_max_s``), then ONE half-open probe tick decides
    between closing and re-opening. ``heartbeat_timeout_s`` bounds the
    ``/healthz`` liveness window (stale tick heartbeat = sick replica)."""
    max_queue: int = 64
    kv_high_watermark: float = 0.95
    kv_degrade_watermark: float = 0.80
    degraded_max_new_tokens: int = 32
    default_max_new_tokens: int = 128
    shed_policy: str = "reject_newest"  # reject_newest | reject_oldest | deadline_aware
    circuit_failure_threshold: int = 5
    circuit_backoff_s: float = 0.5
    circuit_backoff_max_s: float = 30.0
    # open-window endpoint jitter (fraction of the ramp value, uniform,
    # stretch-only): replicas that trip together must not probe in
    # lockstep (fleet-level thundering herd); 0 disables
    circuit_jitter_frac: float = 0.1
    heartbeat_timeout_s: float = 15.0
    # retry-after hint fallback when no decode-throughput sample exists
    # yet (cold engine): assumed seconds per generated token
    assumed_token_seconds: float = 0.05
    # terminal RequestResult records kept for result() polling, oldest
    # evicted first — sustained overload with fresh uids must not grow
    # frontend memory without bound (callers should drop_result() after
    # delivery; this cap is the backstop)
    max_result_history: int = 4096

    def validate(self) -> None:
        if self.shed_policy not in ("reject_newest", "reject_oldest",
                                    "deadline_aware"):
            raise DeepSpeedConfigError(
                "serving.shed_policy must be reject_newest|reject_oldest|"
                f"deadline_aware, got {self.shed_policy!r}")
        if not (0.0 < self.kv_high_watermark <= 1.0):
            raise DeepSpeedConfigError(
                f"serving.kv_high_watermark must be in (0, 1], got "
                f"{self.kv_high_watermark}")
        if self.kv_degrade_watermark > self.kv_high_watermark:
            raise DeepSpeedConfigError(
                "serving.kv_degrade_watermark must not exceed "
                f"kv_high_watermark ({self.kv_degrade_watermark} > "
                f"{self.kv_high_watermark})")
        if self.max_queue < 1:
            raise DeepSpeedConfigError(
                f"serving.max_queue must be >= 1, got {self.max_queue}")
        if self.circuit_failure_threshold < 1:
            raise DeepSpeedConfigError(
                "serving.circuit_failure_threshold must be >= 1, got "
                f"{self.circuit_failure_threshold}")
        if self.max_result_history < 1:
            raise DeepSpeedConfigError(
                "serving.max_result_history must be >= 1, got "
                f"{self.max_result_history}")
        if self.kv_degrade_watermark < 0:
            raise DeepSpeedConfigError(
                "serving.kv_degrade_watermark must be >= 0, got "
                f"{self.kv_degrade_watermark}")
        if self.degraded_max_new_tokens < 1 \
                or self.default_max_new_tokens < 1:
            raise DeepSpeedConfigError(
                "serving.degraded_max_new_tokens / default_max_new_tokens "
                f"must be >= 1, got {self.degraded_max_new_tokens} / "
                f"{self.default_max_new_tokens}")
        if self.circuit_backoff_s <= 0 \
                or self.circuit_backoff_max_s < self.circuit_backoff_s:
            raise DeepSpeedConfigError(
                "serving circuit backoff must satisfy 0 < circuit_backoff_s "
                f"<= circuit_backoff_max_s, got {self.circuit_backoff_s} / "
                f"{self.circuit_backoff_max_s} (a zero backoff probes a "
                "sick device at full tick rate — the hammering the breaker "
                "exists to prevent)")
        if self.heartbeat_timeout_s <= 0 or self.assumed_token_seconds <= 0:
            raise DeepSpeedConfigError(
                "serving.heartbeat_timeout_s and assumed_token_seconds "
                f"must be > 0, got {self.heartbeat_timeout_s} / "
                f"{self.assumed_token_seconds}")
        if not (0.0 <= self.circuit_jitter_frac < 1.0):
            raise DeepSpeedConfigError(
                "serving.circuit_jitter_frac must be in [0, 1), got "
                f"{self.circuit_jitter_frac}")


@dataclasses.dataclass
class FleetSectionConfig:
    """Multi-replica serving fleet (``deepspeed_tpu/serving/fleet.py``).

    A :class:`~deepspeed_tpu.serving.fleet.FleetRouter` owns N serving
    frontends and routes by measured decode throughput, KV headroom,
    circuit state and queue depth. ``min_ready_replicas`` is the
    readiness quorum (``/readyz`` is ready iff at least that many
    replicas are routable). Failover resubmits a lost request up to
    ``max_attempts`` total attempts with exponential backoff
    (``retry_backoff_s`` doubling to ``retry_backoff_max_s``, stretched
    by up to ``retry_jitter_frac`` of uniform jitter) and an
    excluded-replica set; a replica whose last tick blocked longer than
    ``heartbeat_stale_s`` (or whose heartbeat is that stale with work
    pending) is treated as hung. Hedged dispatch (``hedge_enabled``)
    duplicates a still-running request onto a second replica once its
    age passes the ``hedge_percentile`` of observed completion
    latencies (floored at ``hedge_min_s``); first completion wins and
    the loser is cancelled. ``migrate_on_drain`` moves in-flight work
    off a draining replica instead of waiting it out.

    Autoscaling (``serving/fleet.FleetAutoscaler``; README "Elastic
    worlds"): driven by telemetry the frontends already export — mean
    active requests per ready replica (queue depth), the worst
    replica's KV-pool utilization, and the p99 of observed completion
    latency (the TTFT proxy when no request has finished yet). Scale-out
    adds a replica when queue depth exceeds ``scale_out_queue_depth``,
    KV utilization exceeds ``scale_out_kv_util``, or p99 latency
    exceeds ``scale_out_p99_latency_s`` (0 disables that trigger);
    scale-in drains+migrates the least-loaded replica when queue depth
    falls below ``scale_in_queue_depth`` AND KV pressure is off. Both
    directions respect ``autoscale_min_replicas`` /
    ``autoscale_max_replicas`` and wait ``autoscale_cooldown_ticks``
    ticks between scale events (resize thrash protection)."""
    min_ready_replicas: int = 1
    max_attempts: int = 3
    retry_backoff_s: float = 0.05
    retry_backoff_max_s: float = 2.0
    retry_jitter_frac: float = 0.25
    heartbeat_stale_s: float = 5.0
    hedge_enabled: bool = False
    hedge_percentile: float = 0.95
    hedge_min_s: float = 0.05
    migrate_on_drain: bool = True
    max_result_history: int = 4096
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    scale_out_queue_depth: float = 8.0
    scale_in_queue_depth: float = 1.0
    scale_out_kv_util: float = 0.85
    scale_out_p99_latency_s: float = 0.0
    autoscale_cooldown_ticks: int = 8

    def validate(self) -> None:
        if self.min_ready_replicas < 1:
            raise DeepSpeedConfigError(
                "fleet.min_ready_replicas must be >= 1, got "
                f"{self.min_ready_replicas}")
        if self.max_attempts < 1:
            raise DeepSpeedConfigError(
                f"fleet.max_attempts must be >= 1, got {self.max_attempts}")
        if self.retry_backoff_s <= 0 \
                or self.retry_backoff_max_s < self.retry_backoff_s:
            raise DeepSpeedConfigError(
                "fleet retry backoff must satisfy 0 < retry_backoff_s <= "
                f"retry_backoff_max_s, got {self.retry_backoff_s} / "
                f"{self.retry_backoff_max_s}")
        if not (0.0 <= self.retry_jitter_frac < 1.0):
            raise DeepSpeedConfigError(
                "fleet.retry_jitter_frac must be in [0, 1), got "
                f"{self.retry_jitter_frac}")
        if self.heartbeat_stale_s <= 0:
            raise DeepSpeedConfigError(
                "fleet.heartbeat_stale_s must be > 0, got "
                f"{self.heartbeat_stale_s}")
        if not (0.0 < self.hedge_percentile <= 1.0):
            raise DeepSpeedConfigError(
                "fleet.hedge_percentile must be in (0, 1], got "
                f"{self.hedge_percentile}")
        if self.hedge_min_s < 0:
            raise DeepSpeedConfigError(
                f"fleet.hedge_min_s must be >= 0, got {self.hedge_min_s}")
        if self.max_result_history < 1:
            raise DeepSpeedConfigError(
                "fleet.max_result_history must be >= 1, got "
                f"{self.max_result_history}")
        if not (1 <= self.autoscale_min_replicas
                <= self.autoscale_max_replicas):
            raise DeepSpeedConfigError(
                "fleet autoscale bounds must satisfy 1 <= "
                "autoscale_min_replicas <= autoscale_max_replicas, got "
                f"{self.autoscale_min_replicas} / "
                f"{self.autoscale_max_replicas}")
        if self.scale_in_queue_depth >= self.scale_out_queue_depth:
            raise DeepSpeedConfigError(
                "fleet.scale_in_queue_depth must be below "
                "scale_out_queue_depth (equal thresholds oscillate), got "
                f"{self.scale_in_queue_depth} >= "
                f"{self.scale_out_queue_depth}")
        if not (0.0 < self.scale_out_kv_util <= 1.0):
            raise DeepSpeedConfigError(
                "fleet.scale_out_kv_util must be in (0, 1], got "
                f"{self.scale_out_kv_util}")
        if self.scale_out_p99_latency_s < 0:
            raise DeepSpeedConfigError(
                "fleet.scale_out_p99_latency_s must be >= 0 (0 disables "
                f"the latency trigger), got {self.scale_out_p99_latency_s}")
        if not isinstance(self.autoscale_cooldown_ticks, int) \
                or isinstance(self.autoscale_cooldown_ticks, bool) \
                or self.autoscale_cooldown_ticks < 0:
            raise DeepSpeedConfigError(
                "fleet.autoscale_cooldown_ticks must be a non-negative "
                f"int, got {self.autoscale_cooldown_ticks!r}")


@dataclasses.dataclass
class TenantQuotaConfig:
    """One tenant's QoS entry inside ``tenancy.tenants`` (see
    :class:`TenancySectionConfig`). Every quota defaults to 0 =
    unlimited; ``tier`` places the tenant on the shed ladder (``batch``
    sheds before ``standard`` before ``realtime``) and picks its default
    fair-share weight."""
    tier: str = "standard"       # realtime | standard | batch
    requests_per_s: float = 0.0  # token-bucket rate limits (0 = none)
    tokens_per_s: float = 0.0
    burst_requests: float = 0.0  # bucket capacities (0 = one rate-second)
    burst_tokens: float = 0.0
    max_concurrent: int = 0      # live request copies (0 = unlimited)
    max_kv_blocks: int = 0       # projected KV blocks held (0 = unlimited)
    weight: float = 0.0          # fair-share weight (0 = tier default)

    def validate(self) -> None:
        if self.tier not in ("realtime", "standard", "batch"):
            raise DeepSpeedConfigError(
                "tenancy tenant tier must be realtime|standard|batch, "
                f"got {self.tier!r}")
        for key in ("requests_per_s", "tokens_per_s", "burst_requests",
                    "burst_tokens", "weight"):
            if getattr(self, key) < 0:
                raise DeepSpeedConfigError(
                    f"tenancy tenant {key} must be >= 0, got "
                    f"{getattr(self, key)}")
        if self.max_concurrent < 0 or self.max_kv_blocks < 0:
            raise DeepSpeedConfigError(
                "tenancy tenant max_concurrent / max_kv_blocks must be "
                f">= 0, got {self.max_concurrent} / {self.max_kv_blocks}")


@dataclasses.dataclass
class TenancySectionConfig:
    """Multi-tenant QoS (``deepspeed_tpu/serving/tenancy.py``; README
    "Multi-tenant QoS").

    ``tenants`` maps tenant name to a :class:`TenantQuotaConfig` dict;
    unknown tenants (and untagged traffic, which resolves to the
    ``"default"`` tenant) fall back to ``default_tier`` with no quotas.
    ``tier_weights`` sets the fair-share weight per tier (overridable
    per tenant). Under contended capacity — queue at least
    ``fair_contention_queue_frac`` of ``serving.max_queue`` full, or KV
    past the degrade watermark — a tenant whose virtual token counter
    leads the fair-queueing floor by more than
    ``fair_share_horizon_tokens`` weighted tokens is turned away with a
    drain-time retry hint. ``poison_quarantine_threshold`` suspect
    evictions inside ``poison_quarantine_s`` quarantine the tenant for
    that window (per-tenant circuit instead of a whole-replica blast).
    ``max_tenant_labels`` bounds per-tenant metric label cardinality
    (overflow folds into ``"other"``); ``max_tracked_tenants`` bounds
    internal registry state (idle tenants evicted LRU-first)."""
    default_tier: str = "standard"
    tier_weights: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"realtime": 8.0, "standard": 4.0,
                                 "batch": 1.0})
    tenants: Dict[str, Any] = dataclasses.field(default_factory=dict)
    max_tenant_labels: int = 32
    max_tracked_tenants: int = 1024
    fair_share_horizon_tokens: float = 256.0
    fair_contention_queue_frac: float = 0.5
    poison_quarantine_threshold: int = 3
    poison_quarantine_s: float = 30.0

    def validate(self) -> None:
        if self.default_tier not in ("realtime", "standard", "batch"):
            raise DeepSpeedConfigError(
                "tenancy.default_tier must be realtime|standard|batch, "
                f"got {self.default_tier!r}")
        for tier, w in self.tier_weights.items():
            if tier not in ("realtime", "standard", "batch"):
                raise DeepSpeedConfigError(
                    f"tenancy.tier_weights has unknown tier {tier!r}")
            if not isinstance(w, (int, float)) or w <= 0:
                raise DeepSpeedConfigError(
                    f"tenancy.tier_weights[{tier!r}] must be > 0, got "
                    f"{w!r}")
        if not isinstance(self.tenants, dict):
            raise DeepSpeedConfigError(
                "tenancy.tenants must be a dict of tenant name -> quota "
                f"entry, got {type(self.tenants).__name__}")
        if self.max_tenant_labels < 1:
            raise DeepSpeedConfigError(
                "tenancy.max_tenant_labels must be >= 1, got "
                f"{self.max_tenant_labels}")
        if self.max_tracked_tenants < 1:
            raise DeepSpeedConfigError(
                "tenancy.max_tracked_tenants must be >= 1, got "
                f"{self.max_tracked_tenants}")
        if self.fair_share_horizon_tokens <= 0:
            raise DeepSpeedConfigError(
                "tenancy.fair_share_horizon_tokens must be > 0, got "
                f"{self.fair_share_horizon_tokens}")
        if not (0.0 < self.fair_contention_queue_frac <= 1.0):
            raise DeepSpeedConfigError(
                "tenancy.fair_contention_queue_frac must be in (0, 1], "
                f"got {self.fair_contention_queue_frac}")
        if self.poison_quarantine_threshold < 1:
            raise DeepSpeedConfigError(
                "tenancy.poison_quarantine_threshold must be >= 1, got "
                f"{self.poison_quarantine_threshold}")
        if self.poison_quarantine_s <= 0:
            raise DeepSpeedConfigError(
                "tenancy.poison_quarantine_s must be > 0, got "
                f"{self.poison_quarantine_s}")


@dataclasses.dataclass
class SloObjectiveConfig:
    """One declarative objective inside ``slo.objectives`` (see
    :class:`SloSectionConfig`). ``metric`` picks the measured signal:
    ``ttft_p99_s`` (queue-wait to first service), ``decode_token_p99_s``
    (per-token decode latency) — both latency objectives need a
    ``threshold_s`` — or ``availability`` (fraction of terminal requests
    that completed). ``target`` is the objective itself (e.g. 0.99 =
    "99% of requests under threshold" / "99% of requests succeed");
    burn rate is bad-fraction divided by the (1 - target) error budget.
    ``tenant`` scopes the objective to one tenant's traffic ("" =
    fleet-wide)."""
    name: str = ""
    metric: str = "ttft_p99_s"  # ttft_p99_s | decode_token_p99_s | availability
    threshold_s: float = 0.0
    target: float = 0.99
    tenant: str = ""

    def validate(self) -> None:
        if not self.name:
            raise DeepSpeedConfigError(
                "slo objective entries need a non-empty name (alert "
                "state and report rows are keyed by it)")
        if self.metric not in ("ttft_p99_s", "decode_token_p99_s",
                               "availability"):
            raise DeepSpeedConfigError(
                f"slo objective {self.name!r} metric must be ttft_p99_s|"
                f"decode_token_p99_s|availability, got {self.metric!r}")
        if not (0.0 < self.target < 1.0):
            raise DeepSpeedConfigError(
                f"slo objective {self.name!r} target must be in (0, 1) — "
                "a target of 1.0 leaves a zero error budget and every "
                f"burn rate divides by zero — got {self.target}")
        if self.metric != "availability" and self.threshold_s <= 0:
            raise DeepSpeedConfigError(
                f"slo objective {self.name!r} ({self.metric}) needs "
                f"threshold_s > 0, got {self.threshold_s}")


@dataclasses.dataclass
class SloSectionConfig:
    """SLO burn-rate engine (``serving/observatory/slo.py``; README
    "Fleet observatory").

    ``objectives`` is a list of :class:`SloObjectiveConfig` dicts.
    Each objective is evaluated SRE-workbook style over TWO sliding
    windows (``fast_window_s`` / ``slow_window_s``): an alert FIRES only
    while BOTH windows burn error budget faster than
    ``burn_rate_threshold`` (fast window = responsive, slow window =
    de-flappers), and clears as soon as either recovers. The
    request-lifecycle ring keeps the last ``ledger_size`` terminal
    records (availability objectives and the fleet-report CLI read it).
    Actions are observe-only by default: ``autoscale_on_burn`` lets a
    firing objective become a ``slo_burn`` scale-out reason for the
    ``FleetAutoscaler``; ``shed_on_burn`` tightens the admission
    ladder's queue bound by ``shed_tighten_frac`` while any objective
    fires. Both default False so the engine provably changes no
    decision until the operator opts in."""
    enabled: bool = True
    objectives: List[Any] = dataclasses.field(default_factory=list)
    fast_window_s: float = 60.0
    slow_window_s: float = 300.0
    burn_rate_threshold: float = 14.4
    ledger_size: int = 2048
    autoscale_on_burn: bool = False
    shed_on_burn: bool = False
    shed_tighten_frac: float = 0.25

    def validate(self) -> None:
        if not isinstance(self.objectives, list):
            raise DeepSpeedConfigError(
                "slo.objectives must be a list of objective entries, got "
                f"{type(self.objectives).__name__}")
        if not (0 < self.fast_window_s < self.slow_window_s):
            raise DeepSpeedConfigError(
                "slo windows must satisfy 0 < fast_window_s < "
                f"slow_window_s, got {self.fast_window_s} / "
                f"{self.slow_window_s}")
        if self.burn_rate_threshold <= 0:
            raise DeepSpeedConfigError(
                "slo.burn_rate_threshold must be > 0, got "
                f"{self.burn_rate_threshold}")
        if self.ledger_size < 1:
            raise DeepSpeedConfigError(
                f"slo.ledger_size must be >= 1, got {self.ledger_size}")
        if not (0.0 <= self.shed_tighten_frac < 1.0):
            raise DeepSpeedConfigError(
                "slo.shed_tighten_frac must be in [0, 1) — tightening by "
                "a full 1.0 would close the queue entirely — got "
                f"{self.shed_tighten_frac}")
        names = set()
        for entry in self.objectives:
            if isinstance(entry, SloObjectiveConfig):
                obj = entry
                obj.validate()
            elif isinstance(entry, dict):
                from deepspeed_tpu.runtime.config_utils import (
                    config_from_dict as _cfd)
                obj = _cfd(SloObjectiveConfig, entry, path="slo.objectives.")
            else:
                raise DeepSpeedConfigError(
                    "slo.objectives entries must be dicts, got "
                    f"{type(entry).__name__}")
            if obj.name in names:
                raise DeepSpeedConfigError(
                    f"slo.objectives has duplicate name {obj.name!r}")
            names.add(obj.name)

    def parsed_objectives(self) -> List[SloObjectiveConfig]:
        """The objectives as validated dataclasses (dict entries from a
        JSON config are built here; already-typed entries pass through)."""
        out: List[SloObjectiveConfig] = []
        for entry in self.objectives:
            if isinstance(entry, SloObjectiveConfig):
                out.append(entry)
            else:
                from deepspeed_tpu.runtime.config_utils import (
                    config_from_dict as _cfd)
                out.append(_cfd(SloObjectiveConfig, entry,
                                path="slo.objectives."))
        return out


@dataclasses.dataclass
class CheckpointSectionConfig:
    """Durable-checkpoint knobs (``checkpoint/fault_tolerance.py``).

    Every save commits atomically: tmp-dir write → fsync → ``COMMITTED``
    manifest (per-file size + CRC32 + step) → rename → ``latest``.
    ``writer`` supersedes the legacy top-level ``checkpoint_writer`` when
    set. ``keep_n`` prunes all but the newest N committed tags after each
    commit (0 = keep everything). ``verify_checksums=False`` skips the
    CRC pass on load/walk-back (size + marker checks remain). Transient
    I/O errors retry ``save_retries`` times with exponential backoff
    (``retry_backoff_s`` doubling) + uniform jitter (``retry_jitter_s``)."""
    writer: Optional[str] = None   # orbax | fast (None → checkpoint_writer)
    keep_n: int = 0
    verify_checksums: bool = True
    fsync: bool = True
    save_retries: int = 3
    retry_backoff_s: float = 0.2
    retry_jitter_s: float = 0.2

    def validate(self) -> None:
        if self.writer not in (None, "orbax", "fast"):
            raise DeepSpeedConfigError(
                f"checkpoint.writer must be orbax|fast, got {self.writer!r}"
                " (a typo would silently fall back to the orbax path)")


@dataclasses.dataclass
class FaultToleranceConfig:
    """Preemption-safe training (``runtime/engine.py`` handlers).

    ``resume_dir`` is the checkpoint root used for ``auto_resume`` and
    emergency saves (env ``DSTPU_RESUME_DIR`` supplies a default — set by
    ``launcher --resume_dir``). ``auto_resume=True`` makes ``initialize``
    restore the newest committed checkpoint there (step + RNG + scheduler
    client state) before returning; a missing/empty dir is a cold start,
    not an error (env ``DSTPU_AUTO_RESUME=1`` also enables this).
    ``graceful_preemption`` installs a SIGTERM handler that drains any
    in-flight async save, writes an emergency checkpoint, and exits 0 —
    the preemptible-VM contract; it arms only when ``resume_dir`` or
    ``auto_resume`` is also set (a handler with nowhere to save would
    change process signal behavior for nothing). ``on_stall`` escalates
    the telemetry stall watchdog beyond its log line: ``"dump_trace"``
    writes a flight-recorder dump naming the last-completed span
    (requires ``telemetry.tracing``; a no-op without it), and
    ``"checkpoint"`` additionally writes an emergency checkpoint of the
    last completed state (the dump rides along — a stall report without
    its surrounding timeline answers nothing)."""
    # tri-state so env defaults can't override an EXPLICIT false in the
    # JSON (None = unset → falsy, env DSTPU_AUTO_RESUME may enable)
    auto_resume: Optional[bool] = None
    resume_dir: Optional[str] = None
    graceful_preemption: bool = True
    emergency_tag_prefix: str = "emergency"
    on_stall: str = "log"   # log | dump_trace | checkpoint

    def validate(self) -> None:
        if self.on_stall not in ("log", "dump_trace", "checkpoint"):
            raise DeepSpeedConfigError(
                f"fault_tolerance.on_stall must be log|dump_trace|"
                f"checkpoint, got {self.on_stall!r}")


@dataclasses.dataclass
class GuardianSectionConfig:
    """Training-run guardian (``runtime/guardian.py``; README "Training
    guardian").

    ``enabled`` arms the whole subsystem. ``nonfinite_guard`` extends the
    fp16 loss-scaler's device-side skip-update ``lax.cond`` to bf16/fp32:
    a step whose gradients are non-finite never touches the weights (no
    scaler — pure skip, counted in the same device-side ``skips``
    counter). Host-side anomaly detection rides the metrics the engine
    already device_gets each ``steps_per_print`` cadence — zero extra
    host syncs on the hot path: ``z_threshold`` standard deviations
    outside the EMA/variance band of loss or grad-norm (after
    ``warmup_observations`` samples; ``ema_decay`` is the band's memory)
    flags an anomaly. On a confirmed anomaly the guardian dumps a flight
    trace, rolls engine+optimizer+scaler+loader back to the last
    committed checkpoint tag, bisects the offending window microbatch by
    microbatch (``bisect_microbatches``), quarantines the culprit batch
    (``quarantine``) and continues. More than ``max_rollbacks`` rollbacks
    within ``rollback_window_steps`` escalates a structured
    ``RestartableFailure`` into the ``ElasticAgent`` backoff path.
    ``checkpoint_every`` > 0 makes ``TrainingGuardian.run`` write its own
    rollback anchors at that step cadence (0 = the caller checkpoints)."""
    enabled: bool = False
    nonfinite_guard: bool = True
    z_threshold: float = 6.0
    warmup_observations: int = 8
    ema_decay: float = 0.7
    max_rollbacks: int = 2
    rollback_window_steps: int = 500
    checkpoint_every: int = 0
    bisect_microbatches: bool = True
    quarantine: bool = True

    def validate(self) -> None:
        if self.z_threshold <= 0:
            raise DeepSpeedConfigError(
                f"guardian.z_threshold must be > 0, got {self.z_threshold}")
        if not 0.0 < self.ema_decay < 1.0:
            raise DeepSpeedConfigError(
                "guardian.ema_decay must be in (0, 1), got "
                f"{self.ema_decay}")
        for key in ("warmup_observations", "max_rollbacks",
                    "rollback_window_steps", "checkpoint_every"):
            val = getattr(self, key)
            if not isinstance(val, int) or isinstance(val, bool) or val < 0:
                raise DeepSpeedConfigError(
                    f"guardian.{key} must be a non-negative int, got "
                    f"{val!r}")


@dataclasses.dataclass
class ActivationCheckpointingConfig:
    """Reference ``runtime/activation_checkpointing`` config. On TPU this selects a
    ``jax.checkpoint`` (remat) policy applied to the per-layer scan."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: named remat policy (see runtime/activation_checkpointing)
    policy: str = "none"  # none | full | dots_saveable | save_nothing | offload_dots


@dataclasses.dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclasses.dataclass
class MonitorBackendConfig:
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"
    team: Optional[str] = None
    project: Optional[str] = None
    group: Optional[str] = None


@dataclasses.dataclass
class DataTypesConfig:
    grad_accum_dtype: Optional[str] = None


@dataclasses.dataclass
class MeshSectionConfig:
    """TPU-native: named mesh axis sizes. -1 absorbs remaining devices."""
    pipe: int = 1
    data: int = -1
    zshard: int = 1  # MiCS/hpZ subgroup size (see zero_optimization.mics_shard_size)
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def to_mesh_config(self) -> MeshConfig:
        return MeshConfig(pipe=self.pipe, data=self.data, zshard=self.zshard,
                          expert=self.expert, seq=self.seq, tensor=self.tensor)


@dataclasses.dataclass
class TensorParallelConfig:
    autotp_size: int = 1
    tp_grain_size: int = 1


@dataclasses.dataclass
class SequenceParallelConfig:
    """AutoSP config hook (reference ``compile_autosp`` engine.py:1160 /
    DeepCompile ``sp_compile`` pass): when ``auto`` is set the engine runs
    the AutoSP planning pass (``sequence/auto_sp.py``) over the model spec at
    initialize — mechanism (ulysses vs KV ring) chosen by feasibility + comm
    cost on the mesh's 'seq' axis."""
    auto: bool = False
    # informational check: if set, must match the mesh 'seq' axis
    size: int = 0


@dataclasses.dataclass
class PipelineSectionConfig:
    stages: int = 1
    micro_batches: Optional[int] = None
    activation_checkpoint_interval: int = 0


@dataclasses.dataclass
class CurriculumConfig:
    """Reference ``data_efficiency.data_sampling.curriculum_learning`` keys
    (``runtime/data_pipeline/data_sampling/curriculum_scheduler.py``).
    Real DeepSpeed JSON nests ramp parameters under ``schedule_config`` —
    both placements are accepted (``schedule_config`` wins)."""
    enabled: bool = False
    schedule_type: str = "fixed_linear"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    total_curriculum_step: int = 1000
    difficulty_step: int = 8
    root_degree: int = 2
    difficulty: list = dataclasses.field(default_factory=list)
    max_step: list = dataclasses.field(default_factory=list)
    schedule_config: dict = dataclasses.field(default_factory=dict)

    def scheduler_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d.update(d.pop("schedule_config") or {})
        return d


@dataclasses.dataclass
class DynamicBatchingConfig:
    """Reference ``variable_batch_size_and_lr.py`` (492 LoC): token-budget
    batching of variable-length samples with LR scaling."""
    enabled: bool = False
    max_tokens: int = 8192
    lr_scaling_method: str = "linear"   # linear | sqrt | none
    min_batch_size: int = 1
    max_batch_size: int = 0             # 0 → unlimited
    sentence_picking_order: str = "dataloader"  # dataloader | random | seqlen


@dataclasses.dataclass
class RandomLTDConfig:
    """Reference ``data_efficiency.data_routing.random_ltd``."""
    enabled: bool = False
    total_layer_num: int = 0            # 0 → all middle layers
    random_ltd_layer_num: int = 0
    max_value: int = 1024
    random_ltd_schedule: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DataSamplingConfig:
    enabled: bool = False
    curriculum_learning: CurriculumConfig = dataclasses.field(
        default_factory=CurriculumConfig)
    dynamic_batching: DynamicBatchingConfig = dataclasses.field(
        default_factory=DynamicBatchingConfig)


@dataclasses.dataclass
class DataRoutingConfig:
    enabled: bool = False
    random_ltd: RandomLTDConfig = dataclasses.field(
        default_factory=RandomLTDConfig)


@dataclasses.dataclass
class DataEfficiencyConfig:
    """Reference ``data_efficiency`` section (``runtime/data_pipeline/``)."""
    enabled: bool = False
    seed: int = 1234
    data_sampling: DataSamplingConfig = dataclasses.field(
        default_factory=DataSamplingConfig)
    data_routing: DataRoutingConfig = dataclasses.field(
        default_factory=DataRoutingConfig)


@dataclasses.dataclass
class ProgressiveLayerDropConfig:
    """Reference ``progressive_layer_drop`` section
    (``runtime/progressive_layer_drop.py``; engine hook engine.py:430)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


# CUDA-only reference sections accepted and ignored (keeps real DeepSpeed JSON
# configs loadable); each logs once when present. "autotuning" left this
# list in PR 16 (TPU-native plan engine); "elasticity" in PR 17 (it now
# configures the world-elastic agent — ElasticitySectionConfig).
_IGNORED_SECTIONS = (
    "amp", "aio", "hybrid_engine", "compression_training",
    "sparse_attention", "zero_allow_untested_optimizer", "communication_data_type",
)


@dataclasses.dataclass
class DeepSpeedTPUConfig:
    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None
    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    dump_state: bool = False
    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = dataclasses.field(default_factory=FP16Config)
    bf16: BF16Config = dataclasses.field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = dataclasses.field(default_factory=ZeroConfig)
    comms_logger: CommsLoggerConfig = dataclasses.field(default_factory=CommsLoggerConfig)
    telemetry: TelemetryConfig = dataclasses.field(default_factory=TelemetryConfig)
    serving: ServingSectionConfig = dataclasses.field(
        default_factory=ServingSectionConfig)
    fleet: FleetSectionConfig = dataclasses.field(
        default_factory=FleetSectionConfig)
    tenancy: TenancySectionConfig = dataclasses.field(
        default_factory=TenancySectionConfig)
    slo: SloSectionConfig = dataclasses.field(
        default_factory=SloSectionConfig)
    hlolint: HlolintSectionConfig = dataclasses.field(
        default_factory=HlolintSectionConfig)
    memlint: MemlintSectionConfig = dataclasses.field(
        default_factory=MemlintSectionConfig)
    autotuning: AutotuningSectionConfig = dataclasses.field(
        default_factory=AutotuningSectionConfig)
    elasticity: ElasticitySectionConfig = dataclasses.field(
        default_factory=ElasticitySectionConfig)
    activation_checkpointing: ActivationCheckpointingConfig = dataclasses.field(
        default_factory=ActivationCheckpointingConfig)
    flops_profiler: FlopsProfilerConfig = dataclasses.field(default_factory=FlopsProfilerConfig)
    tensorboard: MonitorBackendConfig = dataclasses.field(default_factory=MonitorBackendConfig)
    csv_monitor: MonitorBackendConfig = dataclasses.field(default_factory=MonitorBackendConfig)
    wandb: MonitorBackendConfig = dataclasses.field(default_factory=MonitorBackendConfig)
    comet: MonitorBackendConfig = dataclasses.field(default_factory=MonitorBackendConfig)
    data_types: DataTypesConfig = dataclasses.field(default_factory=DataTypesConfig)
    mesh: MeshSectionConfig = dataclasses.field(default_factory=MeshSectionConfig)
    tensor_parallel: TensorParallelConfig = dataclasses.field(default_factory=TensorParallelConfig)
    sequence_parallel: SequenceParallelConfig = dataclasses.field(
        default_factory=SequenceParallelConfig)
    pipeline: PipelineSectionConfig = dataclasses.field(default_factory=PipelineSectionConfig)
    seed: int = 1234
    zero_force_ds_cpu_optimizer: bool = False
    checkpoint_tag_validation: str = "Warn"  # Ignore | Warn | Fail
    checkpoint_writer: str = "orbax"  # orbax | fast (checkpoint_engine.py)
    checkpoint: CheckpointSectionConfig = dataclasses.field(
        default_factory=CheckpointSectionConfig)
    fault_tolerance: FaultToleranceConfig = dataclasses.field(
        default_factory=FaultToleranceConfig)
    guardian: GuardianSectionConfig = dataclasses.field(
        default_factory=GuardianSectionConfig)
    data_efficiency: DataEfficiencyConfig = dataclasses.field(
        default_factory=DataEfficiencyConfig)
    # legacy top-level section (reference supports both placements)
    curriculum_learning: CurriculumConfig = dataclasses.field(
        default_factory=CurriculumConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = dataclasses.field(
        default_factory=ProgressiveLayerDropConfig)

    @property
    def curriculum(self) -> CurriculumConfig:
        """Active curriculum config: the data_efficiency placement applies
        when its parent gates are on (reference semantics); the legacy
        top-level section needs no parent."""
        de = self.data_efficiency
        cur = de.data_sampling.curriculum_learning
        if cur.enabled and de.enabled and de.data_sampling.enabled:
            return cur
        return self.curriculum_learning

    # resolved fields (filled by _resolve_batch_size)
    _dp_world_size: int = 1

    @property
    def zero_enabled(self) -> bool:
        return self.zero_optimization.stage > 0

    @property
    def effective_checkpoint_writer(self) -> str:
        """``checkpoint.writer`` when set, else the legacy top-level
        ``checkpoint_writer`` (both spellings stay valid)."""
        return self.checkpoint.writer or self.checkpoint_writer

    @property
    def precision_dtype(self) -> str:
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        if self.fp16.enabled:
            return "float16"
        if self.bf16.enabled:
            return "bfloat16"
        return "float32"

    def resolve_batch_size(self, dp_world_size: int) -> None:
        """Batch-size triad resolution: train = micro × GAS × dp (reference
        ``runtime/config.py`` ``_batch_assertion``)."""
        self._dp_world_size = dp_world_size
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} != micro {mb} × gas {gas} × dp {dp_world_size}")
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by micro {mb} × dp {dp_world_size}")
            self.gradient_accumulation_steps = tb // (mb * dp_world_size)
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by gas {gas} × dp {dp_world_size}")
            self.train_micro_batch_size_per_gpu = tb // (gas * dp_world_size)
        elif tb is not None:
            self.gradient_accumulation_steps = 1
            if tb % dp_world_size != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by dp {dp_world_size}")
            self.train_micro_batch_size_per_gpu = tb // dp_world_size
        elif mb is not None:
            self.gradient_accumulation_steps = gas or 1
            self.train_batch_size = mb * self.gradient_accumulation_steps * dp_world_size
        else:
            raise DeepSpeedConfigError(
                "config must set train_batch_size or train_micro_batch_size_per_gpu")


def load_config(config) -> DeepSpeedTPUConfig:
    """Accepts a dict, a JSON file path, or an existing config object."""
    if isinstance(config, DeepSpeedTPUConfig):
        return config
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise DeepSpeedConfigError(f"config must be dict or path, got {type(config)}")
    config = dict(config)
    for section in _IGNORED_SECTIONS:
        if section in config:
            logger.warning(f"config section {section!r} is not applicable on TPU — ignored")
            config.pop(section)
    cfg = config_from_dict(DeepSpeedTPUConfig, config)
    # which zero_optimization knobs the USER spelled out, verbatim — the
    # plan engine's apply/stale logic needs "explicitly set" vs "left at
    # default", and a dataclass can't tell the difference after the fact
    zo = config.get("zero_optimization")
    cfg._explicit_zero_keys = frozenset(zo) if isinstance(zo, dict) \
        else frozenset()
    # launcher/env defaults (deepspeed_tpu.launcher --resume_dir /
    # --auto_resume): explicit JSON settings always win
    import os as _os

    env_dir = _os.environ.get("DSTPU_RESUME_DIR")
    if env_dir and cfg.fault_tolerance.resume_dir is None:
        cfg.fault_tolerance.resume_dir = env_dir
    if cfg.fault_tolerance.auto_resume is None and \
            _os.environ.get("DSTPU_AUTO_RESUME", "").lower() in \
            ("1", "true", "yes"):
        cfg.fault_tolerance.auto_resume = True
    return cfg


# Back-compat alias matching the reference class name.
DeepSpeedConfig = DeepSpeedTPUConfig
