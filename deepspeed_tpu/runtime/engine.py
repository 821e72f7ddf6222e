"""DeepSpeedTPUEngine — the core training runtime.

Parity: reference ``runtime/engine.py:235`` (``DeepSpeedEngine``: ``forward``
:2675, ``backward`` :3066, ``step`` :3241, ``train_batch`` via pipe engine,
``save_checkpoint`` :4557, ``load_checkpoint`` :4079) and its ZeRO optimizers
(``stage_1_and_2.py``, ``stage3.py``).

TPU-native architecture: instead of an ``nn.Module`` wrapper with per-param
hooks, the engine owns a **sharded train state** (fp32 master params + optimizer
moments + loss-scale state) and a **single jitted train step** that fuses the
reference's forward → backward → allreduce/reduce-scatter → optimizer-step →
allgather flow into one XLA program over the device mesh:

* gradient accumulation = ``lax.scan`` over the micro-batch axis *inside* jit
  (the IPG-bucket flow, ``stage_1_and_2.py:1125``, becomes a loop-carried sum);
* ZeRO stages = sharding constraints (see ``parallel/partitioning.py``) — XLA
  emits the reduce-scatter/all-gather schedule the reference hand-manages, with
  overlap from the latency-hiding scheduler;
* mixed precision = cast-on-use from fp32 master (``bf16_optimizer.py:37`` /
  ``fp16/fused_optimizer.py:33`` semantics) with dynamic loss scaling as a
  ``lax.cond`` skip-update branch.

The eager ``forward()/backward()/step()`` triple is preserved for API parity:
``forward`` computes loss+grads in one jitted call, ``backward`` accumulates into
a sharded buffer, ``step`` applies the (jitted) update at the GAS boundary.
"""
from __future__ import annotations

import json
import os
import threading
import time
import weakref
from functools import partial
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu import telemetry
from deepspeed_tpu.analysis.racelint.sanitizer import make_lock
from deepspeed_tpu.comm.mesh import MeshManager, get_mesh_manager
from deepspeed_tpu.models.api import ModelSpec
from deepspeed_tpu.ops.optimizer import TPUOptimizer, get_optimizer
from deepspeed_tpu.parallel.partitioning import ShardingPolicy
from deepspeed_tpu.runtime.config import DeepSpeedTPUConfig, load_config
from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigError
from deepspeed_tpu.runtime.dataloader import (
    DeepSpeedTPUDataLoader,
    RepeatingLoader,
    shard_host_batch,
)
from deepspeed_tpu.runtime.loss_scaler import (
    DynamicLossScaler,
    clip_by_global_norm,
    global_grad_norm,
)
from deepspeed_tpu.runtime.lr_schedules import LRSchedule, get_lr_schedule
from deepspeed_tpu.testing.chaos import chaos_point
from deepspeed_tpu.utils.compile_cache import ensure_compile_cache
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
    TRAIN_BATCH_TIMER,
)

PyTree = Any


class DeepSpeedTPUEngine:
    # what this process traces, lowers, loads and compiles from here on is
    # accounted by program (telemetry/host.py); the constructor is the span
    # ``engine_init`` and the parts of it that set-up is suspected of are
    # spans inside it
    @telemetry.engine_init()
    def __init__(
        self,
        model: ModelSpec,
        config: Any,
        optimizer: Optional[TPUOptimizer] = None,
        lr_scheduler: Optional[LRSchedule] = None,
        mesh_manager: Optional[MeshManager] = None,
        seed: Optional[int] = None,
    ):
        ensure_compile_cache()
        self.model_spec = model
        self.config: DeepSpeedTPUConfig = load_config(config)
        # MiCS / ZeRO++ hpZ: replica-group sharding resolves onto the 'zshard'
        # mesh axis (shard within the subgroup, replicate across 'data').
        # zero_hpz_partition_size is VALIDATED like the bucket keys (PR 8):
        # type/spelling normalization lives in ZeroConfig.validate(); the
        # mesh-dependent checks — the subgroup must divide the device world
        # and must not contradict an explicit mesh.zshard — are here, and
        # they RAISE: a mis-sized subgroup silently degrading to exact
        # full-world collectives is the config no-op class of bug
        zcfg = self.config.zero_optimization
        # cached autotune plan ("autotuning" config section): applied HERE,
        # before ANY knob is consumed — zero_hpz_partition_size feeds the
        # subgroup resolution just below, the bucket/overlap keys feed
        # _setup_overlap_scheduler — so a loaded plan covers all of them
        self._load_autotune_plan(zcfg)
        subgroup = zcfg.mics_shard_size or (
            zcfg.zero_hpz_partition_size if zcfg.zero_hpz_partition_size > 1 else 0)
        if subgroup:
            key = ("mics_shard_size" if zcfg.mics_shard_size
                   else "zero_hpz_partition_size")
            if self.config.mesh.zshard not in (1, subgroup):
                raise DeepSpeedConfigError(
                    f"zero_optimization.{key}={subgroup} conflicts with "
                    f"mesh.zshard={self.config.mesh.zshard} — the subgroup IS "
                    "the 'zshard' axis; set one of them, or make them agree")
            n_dev = jax.device_count()
            if n_dev % subgroup != 0:
                raise DeepSpeedConfigError(
                    f"zero_optimization.{key}={subgroup} must divide the "
                    f"device world ({n_dev} devices) — a non-dividing hpZ "
                    "subgroup cannot form replica groups, and falling back "
                    "to exact full-world collectives would silently drop "
                    "the secondary partition")
            self.config.mesh.zshard = subgroup
            try:
                # dividing the raw device count is necessary, not
                # sufficient: other fixed mesh axes (tensor/pipe/seq)
                # consume devices too — resolve the full mesh NOW so a
                # non-fitting subgroup names the config key instead of
                # failing later with a generic mesh-shape error
                self.config.mesh.to_mesh_config().resolve(n_dev)
            except ValueError as e:
                raise DeepSpeedConfigError(
                    f"zero_optimization.{key}={subgroup} does not fit the "
                    f"mesh: {e}") from None
        # the first touch of the devices in a process that had none (the
        # runtime's start: seconds on a TPU host) and the mesh over them
        with telemetry.span("device_attach"):
            if not dist.is_initialized():
                dist.init_distributed(
                    mesh_config=self.config.mesh.to_mesh_config())
            if mesh_manager is None:
                from deepspeed_tpu.comm.mesh import initialize_mesh

                mesh_manager = get_mesh_manager()
                want = self.config.mesh.to_mesh_config().resolve(
                    jax.device_count())
                have = {a: mesh_manager.axis_size(a)
                        for a in mesh_manager.axis_names()}
                if want != have:
                    # config disagrees with the live mesh (e.g. a second
                    # engine with a different layout) — rebuild rather
                    # than silently reuse
                    mesh_manager = initialize_mesh(
                        self.config.mesh.to_mesh_config())
            self.mesh_manager = mesh_manager
            self.mesh = self.mesh_manager.mesh

        # batch triad: dp width = replicas of the model over the batch dim
        self.dp_world_size = (self.mesh_manager.axis_size("data")
                              * self.mesh_manager.axis_size("zshard")
                              * self.mesh_manager.axis_size("expert"))
        self.config.resolve_batch_size(self.dp_world_size)

        self.zero_stage = self.config.zero_optimization.stage
        self.policy = ShardingPolicy(self.mesh, self.zero_stage)

        # AutoSP: config-driven sequence-parallel pass over the spec
        # (reference compile_autosp engine hook, engine.py:1160)
        self.sp_plan = None
        sp_cfg = self.config.sequence_parallel
        if sp_cfg.size and sp_cfg.size != self.mesh_manager.axis_size("seq"):
            raise DeepSpeedConfigError(
                f"sequence_parallel.size {sp_cfg.size} != mesh seq axis "
                f"{self.mesh_manager.axis_size('seq')}"
                + ("" if sp_cfg.auto else
                   " (note: sequence_parallel.size alone does not enable SP; "
                   "set \"auto\": true and a mesh 'seq' axis)"))
        if sp_cfg.auto:
            from deepspeed_tpu.sequence.auto_sp import auto_sp

            model, self.sp_plan = auto_sp(model)
            self.model_spec = model

        # activation_checkpointing.policy → the spec's remat policy
        # (reference runtime/activation_checkpointing config; also what the
        # autotuner's remat dimension tunes). Applied via the spec's own
        # builder so customizations survive.
        ac_policy = self.config.activation_checkpointing.policy
        if ac_policy and ac_policy != "none":
            spec_cfg = getattr(model, "config", None)
            if spec_cfg is not None and getattr(spec_cfg, "remat", None) == ac_policy:
                pass   # already built with this policy
            elif getattr(model, "builder", None) is not None:
                model = model.builder(remat=ac_policy)
                self.model_spec = model
            else:
                logger.warning(
                    f"activation_checkpointing.policy={ac_policy!r} ignored: "
                    "the model spec carries no builder to rebuild with")

        # precision
        self.precision = self.config.precision_dtype  # float32|float16|bfloat16
        self.fp16_enabled = self.precision == "float16"
        self.scaler = DynamicLossScaler.from_config(self.config.fp16) \
            if self.fp16_enabled else None

        # optimizer + schedule
        if optimizer is None:
            opt_cfg = self.config.optimizer
            if opt_cfg is None:
                raise ValueError("config must define an optimizer (or pass one in)")
            optimizer = get_optimizer(opt_cfg.type, opt_cfg.params)
        # ZenFlow: importance-split hot/cold updates (runtime/zenflow.py)
        from deepspeed_tpu.runtime.zenflow import maybe_wrap_zenflow

        optimizer = maybe_wrap_zenflow(optimizer, zcfg.zenflow)
        # frozen params (LoRA etc.): optimizer state only for trainable leaves
        self._trainable_mask = None
        if model.trainable_fn is not None:
            from deepspeed_tpu.ops.optimizer import MaskedOptimizer

            self._trainable_mask = model.trainable_fn()
            optimizer = MaskedOptimizer(inner=optimizer,
                                        mask=self._trainable_mask)
        self.optimizer = optimizer
        _inner_opt = optimizer
        while hasattr(_inner_opt, "inner"):   # MaskedOptimizer/ZenFlow wrap
            _inner_opt = _inner_opt.inner
        if (self.precision == "bfloat16"
                and not self.config.bf16.fp32_master
                and not getattr(_inner_opt, "stochastic_rounding", False)):
            # without an fp32 master, updates below bf16's 8-bit-mantissa
            # step (~0.4% relative) round to zero and training silently
            # stalls — only stochastic-rounding optimizers can absorb them
            raise ValueError(
                "bf16.fp32_master=false requires a stochastic-rounding "
                "optimizer (adafactor); "
                f"{type(optimizer).__name__} would silently stall")
        if lr_scheduler is None and self.config.scheduler and self.config.scheduler.type:
            lr_scheduler = get_lr_schedule(
                self.config.scheduler.type, self.config.scheduler.params,
                base_lr=self.optimizer.lr)
        self.lr_scheduler = lr_scheduler

        dist.configure(self.config)

        # sharding spec trees (the model's shapes are read off an abstract
        # trace of its ``init_fn``)
        with telemetry.span("state_init"):
            self._axes = model.axes_fn()
            seed = self.config.seed if seed is None else seed
            self._init_rng = jax.random.PRNGKey(seed)
            self._shapes = jax.eval_shape(model.init_fn, self._init_rng)
            self.master_spec = self.policy.state_spec(self._axes,
                                                      self._shapes)
            self.param_spec = self.policy.param_spec(self._axes,
                                                     self._shapes)
            self.grad_spec = self.policy.grad_spec(self._axes, self._shapes)
            self.batch_spec = self.policy.batch_spec()

        # ZeRO-Offload: optimizer state lives in host memory between steps
        # (reference runtime/zero/offload_config.py + swap_tensor swappers;
        # the device↔host moves bracket the jitted step like the reference's
        # swap-in/step/swap-out flow, stage_1_and_2.py initialize/step)
        if self.config.zero_optimization.super_offload:
            # SuperOffload alias → host-executed optimizer with overlap.
            # Explicit user settings win: an explicit overlap_step=False is
            # honored (no silent staleness) and a conflicting device raises.
            off = self.config.zero_optimization.offload_optimizer
            if off.device not in ("none", "cpu"):
                raise DeepSpeedConfigError(
                    f"super_offload conflicts with offload_optimizer.device="
                    f"{off.device!r}; it implies device='cpu'")
            off.device, off.host_step = "cpu", True
            if off.overlap_step is None:
                off.overlap_step = True
        offload_dev = self.config.zero_optimization.offload_optimizer.device
        if (self.config.zero_optimization.offload_optimizer.host_step
                and offload_dev != "cpu"):
            raise DeepSpeedConfigError(
                "offload_optimizer.host_step requires device='cpu' (got "
                f"{offload_dev!r}) — the host CPU backend runs the update")
        self._host_step = (offload_dev == "cpu" and
                           self.config.zero_optimization.offload_optimizer.host_step)
        self._offload_opt = offload_dev == "cpu" and not self._host_step
        # NVMe tier: optimizer state swapped to local disk around the step
        # (reference swap_tensor/partitioned_optimizer_swapper.py:27)
        self._offload_nvme = offload_dev == "nvme"
        self._opt_swapper = None   # built lazily (needs self.state)

        # ZeRO-Infinity PARAMETER tier (reference swap_tensor/
        # partitioned_param_swapper.py:37 AsyncPartitionedParameterSwapper +
        # zero/offload_config.py:19-41): at stage 3 the fp32 master shards
        # are PINNED-HOST resident — the jitted step's layer scan streams
        # each layer's slice H2D on use and the update writes back to host,
        # so HBM holds only the transient 16-bit working copies (verified
        # via compiled memory_analysis: device argument bytes for the
        # master drop to 0). The NVMe variant additionally round-trips the
        # host master through TensorSwapper files between steps.
        pcfg = self.config.zero_optimization.offload_param
        self._offload_param = False
        self._offload_param_nvme = False
        if pcfg.device not in ("none", None):
            if pcfg.device not in ("cpu", "nvme"):
                raise DeepSpeedConfigError(
                    f"offload_param.device must be none|cpu|nvme, got "
                    f"{pcfg.device!r}")
            if self.zero_stage < 3:
                logger.warning(
                    "offload_param is a ZeRO-3 tier (reference "
                    "zero/offload_config.py) but zero_optimization.stage="
                    f"{self.zero_stage} — parameter offload is DISABLED. "
                    "Set stage: 3 to enable it.")
            else:
                self._offload_param = True
                self._offload_param_nvme = pcfg.device == "nvme"
        self._param_swapper = None  # built lazily (NVMe variant)
        # In-step H2D streaming (host-resident master INPUTS + in-program
        # device_put per use) needs XLA memories support in the SPMD
        # partitioner — present on the TPU backend, absent on CPU (both
        # host-input and device-output placement annotations fail to
        # partition there). CPU falls back to jit-boundary swaps: master
        # parked pinned-host between steps, moved whole to device around
        # the step (the ZeRO-Offload pattern _opt_swap also uses).
        # ZeRO++ compressed collectives (qwZ/qgZ) + 1-bit optimizer transport
        self._resolve_compressed_modes(zcfg)
        # the compressed/1-bit step builders are not host-input aware
        # (their shard_map state layouts assume device memory) — those
        # combos use the boundary-swap mode
        self._offload_param_stream = (
            self._offload_param and jax.default_backend() == "tpu"
            and not self._compressed and not self._onebit_wire)

        # training-run guardian (config "guardian"; README "Training
        # guardian"): device-side non-finite skip for bf16/fp32 — the fp16
        # loss-scaler's lax.cond branch, minus the scaler. Resolved BEFORE
        # _init_state so the state tree carries the `skips` counter.
        gcfg = self.config.guardian
        self._nonfinite_guard = bool(
            gcfg.enabled and gcfg.nonfinite_guard and not self.fp16_enabled)
        if self._nonfinite_guard and self._host_step:
            logger.warning(
                "guardian.nonfinite_guard is unavailable with "
                "offload_optimizer.host_step (the host-executed update has "
                "no device-side skip branch) — host-side anomaly detection "
                "still runs")
            self._nonfinite_guard = False
        self._guardian = None          # attached by TrainingGuardian
        self._gc_protect_tags: set = set()   # rollback anchors keep_n must keep
        self._gc_protect_root: Optional[str] = None
        self._gc_pin_stale = False   # superseded by an in-flight async commit
        self._restored_client_state: Optional[Dict] = None
        self._tm_skips_lock = make_lock("engine._tm_skips_lock")

        # bucketed compute/collective overlap scheduler (ROADMAP item 2;
        # parallel/overlap.py): chunk the layer scan at the prefetch-bucket
        # granularity and emit each chunk's gradient sync mid-backward so
        # XLA's async-collective pass can hide it under remaining compute
        self._setup_overlap_scheduler(zcfg)

        # data-efficiency features (reference runtime/data_pipeline/ +
        # progressive_layer_drop.py — config-driven, engine-injected)
        self._setup_data_efficiency()

        # ONE program draws the parameters (``init_fn``, in float32) and
        # the optimizer's state from them and places both
        with telemetry.span("params_init"):
            self.state = self._init_state()
        self._compiled: Dict[Any, Any] = {}
        # step-phase overlap: seed the double-buffered param publish so
        # the FIRST step's forward has a buffer to consume
        self._refresh_param_buffer()
        if self._offload_opt:
            self._opt_swap("out")
        self._host_runner = None
        if self._host_step:
            from deepspeed_tpu.runtime.host_step import HostStepRunner

            if self._compressed or self._onebit_wire:
                raise DeepSpeedConfigError(
                    "host_step cannot combine with compressed collectives")
            self._host_runner = HostStepRunner(self)
            self._host_runner.adopt_state()

        # eager-API accumulation
        self._grad_buffer: Optional[PyTree] = None
        self._pending_grads: Optional[PyTree] = None
        self._micro_in_window = 0

        # bookkeeping
        self.global_steps = 0
        self.micro_steps = 0
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.config.train_batch_size or 1,
            steps_per_output=self.config.steps_per_print)
        self._last_metrics_dev: Dict[str, jax.Array] = {}
        self.monitor = None  # attached by initialize() when configured

        # fault tolerance (config "fault_tolerance"; README "Fault
        # tolerance"): preemption flag checked at step boundaries, a lock
        # serializing emergency saves (watchdog thread vs signal handler),
        # and the last save_checkpoint dir as the emergency fallback root
        self._preempt_requested = False
        self._in_step = False
        self._guard_busy = False   # defer_preemption scope (guardian)
        self._saving = False
        self._ft_lock = make_lock("engine._ft_lock")
        self._last_save_dir: Optional[str] = None
        self._prev_sig_handlers: Dict[int, Any] = {}
        # a step's meters, kept for the next step to observe
        # (``_observe_meters``; the registry's collector reads them too)
        self._meters_lock = make_lock("engine._meters_lock")
        self._meters_pending: Optional[Dict[str, jax.Array]] = None   # guarded-by: self._meters_lock
        self._metered = False
        self._setup_telemetry()

        # EP-dispatch drop visibility: under an 'expert' mesh axis the ragged
        # MoE path can overflow its fixed all-to-all buffer on router skew;
        # the overflowed choices silently fall through to the residual, so a
        # degrading router would otherwise hurt training quality invisibly.
        self._moe_drop_frac = 0.0
        if self.mesh_manager.axis_size("expert") > 1:
            import weakref

            from deepspeed_tpu.moe.layer import set_drop_monitor

            # weakref: the module-global monitor must not pin a dead engine
            # (params + compiled steps) for the life of the process
            ref = weakref.ref(self)

            def _sink(frac):
                eng = ref()
                if eng is not None:
                    eng._record_moe_drops(frac)

            set_drop_monitor(_sink)

        # a model that holds a SHARE of each expert layer (expert
        # parallelism's unit): each layer's rows leave the compiled step as
        # one of its outputs (``metrics["moe_held"]``) and reach the
        # registry a step late, with no fence and no host callback
        # (``_observe_meters``)
        self._metered = self._tm is not None and bool(getattr(
            getattr(model, "config", None), "moe_router_experts", 0))
        if self._metered:
            self._tm_held = (
                telemetry.histogram(
                    "train_moe_held_expert_rows",
                    "calls of an expert layer that holds a share of its "
                    "experts: mean rows a held expert got",
                    buckets=tuple(2.0 ** i for i in range(4, 18))),
                telemetry.histogram(
                    "train_moe_load_imbalance",
                    "calls of an expert layer that holds a share of its "
                    "experts: the busiest held expert's rows over the mean "
                    "(1 = even routing)",
                    buckets=(1.0, 1.05, 1.1, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0,
                             16.0)),
                telemetry.histogram(
                    "train_moe_held_pair_share",
                    "calls of an expert layer that holds a share of its "
                    "experts: (row, expert) pairs on held experts over all "
                    "the router chose",
                    buckets=tuple(i / 16 for i in range(1, 17))),
                telemetry.histogram(
                    "train_moe_moved_row_share",
                    "calls of an expert layer that holds a share of its "
                    "experts: sorted rows its dispatch, activation and "
                    "combine covered (the held pairs, to a tile of rows) "
                    "over the (row, expert) pairs; 1 = the movers did not "
                    "engage",
                    buckets=tuple(i / 16 for i in range(1, 17))))

        n_params = model.num_params
        log_dist(
            f"engine up: model={model.name} params={n_params or '?'} "
            f"zero_stage={self.zero_stage} precision={self.precision} "
            f"mesh={self.mesh_manager} micro_bs={self.train_micro_batch_size()} "
            f"gas={self.gradient_accumulation_steps()}")
        self._enforce_hlolint()
        self._enforce_memlint()

    def _enforce_hlolint(self) -> None:
        """Compiled-program contract enforcement at initialize (the
        ``"hlolint"`` config section): lower the REAL fused step once
        (the observatory cache keeps it — ledger/report calls reuse the
        same lowering) and lint it; with ``fail_on_violation`` a
        violation refuses the job BEFORE chip time is spent."""
        hlolint_cfg = self.config.hlolint
        if not hlolint_cfg.enabled:
            return
        findings = self.lint_step(contract=hlolint_cfg.contract or None)
        if not findings:
            log_dist("hlolint: compiled train step clean"
                     + (f" (contract {hlolint_cfg.contract})"
                        if hlolint_cfg.contract else ""))
            return
        for f in findings:
            log_dist(f"hlolint: {f.render()}")
        if hlolint_cfg.fail_on_violation:
            from deepspeed_tpu.analysis.hlolint import HloLintViolation

            raise HloLintViolation(
                f"hlolint: {len(findings)} compiled-program contract "
                f"violation(s) in the lowered train step — first: "
                f"{findings[0].render()} (set hlolint.fail_on_violation "
                "false to proceed anyway)")

    def _memlint_budget_bytes(self) -> Optional[float]:
        """The OOM pre-flight budget: the explicit
        ``memlint.hbm_budget_bytes`` when set, else the chip's datasheet
        HBM capacity (``utils/chip_specs``). None on the datasheet-less
        CPU tier without an explicit budget — the gate stays disarmed
        there rather than inheriting a TPU part's capacity."""
        explicit = self.config.memlint.hbm_budget_bytes
        if explicit:
            return float(explicit)
        from deepspeed_tpu.utils.chip_specs import chip_hbm_bytes

        try:
            kind = getattr(jax.devices()[0], "device_kind", "")
        except (RuntimeError, IndexError):
            return None
        cap = chip_hbm_bytes(kind)
        return float(cap) if cap else None

    def _enforce_memlint(self) -> None:
        """Memory-contract enforcement at initialize (the ``"memlint"``
        config section — hlolint's memory-side sibling): donation/
        aliasing verification, residency vs the ZeRO prediction, the
        committed memory contract, and the OOM pre-flight gate. Reuses
        the SAME cached lowering hlolint/the ledger read; with
        ``fail_on_violation`` a violation — including a predicted peak
        over the HBM budget — refuses the job BEFORE any chip time is
        spent."""
        mcfg = self.config.memlint
        if not mcfg.enabled:
            return
        findings = self.lint_memory(contract=mcfg.contract or None,
                                    hbm_budget_bytes=self._memlint_budget_bytes())
        if not findings:
            log_dist("memlint: compiled train step memory clean"
                     + (f" (contract {mcfg.contract})"
                        if mcfg.contract else ""))
            return
        for f in findings:
            log_dist(f"memlint: {f.render()}")
        if mcfg.fail_on_violation:
            from deepspeed_tpu.analysis.memlint import MemLintViolation

            raise MemLintViolation(
                f"memlint: {len(findings)} memory contract violation(s) "
                f"in the lowered train step — first: "
                f"{findings[0].render()} (set memlint.fail_on_violation "
                "false to proceed anyway)")

    # ------------------------------------------------------------------ #
    # compressed collectives (ZeRO++ qwZ/qgZ, 1-bit transport)
    # ------------------------------------------------------------------ #
    def _resolve_compressed_modes(self, zcfg) -> None:
        """Decide whether the train step uses wire-compressed collectives.

        qwZ/qgZ (reference ``zero/config.py:309-330``,
        ``runtime/comm/coalesced_collectives.py``): int8 parameter all-gather /
        gradient reduce-scatter inside a shard_map manual over the ZeRO axes.
        1-bit transport (reference ``runtime/comm/nccl.py:52``): packed-sign
        momentum allreduce — stage 0 only, as in the reference (1-bit
        optimizers are incompatible with ZeRO partitioning there too).
        Every accepted-but-inapplicable flag warns loudly (round-1 verdict:
        silent config no-ops are bugs)."""
        from deepspeed_tpu.comm.mesh import DATA_AXIS as _D, ZSHARD_AXIS as _Z

        shape = self.mesh.shape
        self._dp_manual_axes = tuple(
            a for a in (_D, _Z) if shape.get(a, 1) >= 1)
        self._dp_manual_world = int(
            np.prod([shape.get(a, 1) for a in self._dp_manual_axes]))
        # expert>1 is allowed: the MoE batch/weight shardings over 'expert'
        # stay GSPMD-auto inside the dp-manual shard_map (the reference's
        # loudest qgZ win is exactly MoE gradients, BASELINE.md #9); hpZ
        # (zshard>1) composes via per-leaf subgroup gathers — the full
        # ZeRO++ trio (zero/config.py:309-330)
        eligible = (self._dp_manual_world > 1
                    and shape.get("seq", 1) == 1
                    and shape.get("pipe", 1) == 1)

        quant_w = bool(zcfg.zero_quantized_weights
                       or zcfg.zero_quantized_nontrainable_weights)
        quant_g = bool(zcfg.zero_quantized_gradients)
        self._compressed: Optional[Dict[str, bool]] = None
        if quant_w or quant_g:
            if self.zero_stage < 1:
                logger.warning(
                    "zero_quantized_weights/gradients require ZeRO stage >= 1 "
                    f"(got stage {self.zero_stage}) — exact collectives used")
            elif not eligible:
                logger.warning(
                    "zero_quantized_weights/gradients need data-parallel width "
                    "> 1 and seq=pipe=1 in the mesh — exact collectives "
                    f"used (mesh={dict(shape)})")
            else:
                self._compressed = {"quant_weights": quant_w,
                                    "quant_grads": quant_g}
                log_dist(f"ZeRO++ compressed collectives active: qwZ={quant_w} "
                         f"qgZ={quant_g} over axes {self._dp_manual_axes}")
        if zcfg.loco_error_feedback:
            if self._compressed is not None \
                    and self._compressed["quant_grads"]:
                self._compressed["loco"] = True
                log_dist("LoCo error feedback active for the qgZ reduce "
                         "(reference coalesced_collectives.py:81)")
            else:
                logger.warning(
                    "loco_error_feedback requires an ACTIVE "
                    "zero_quantized_gradients path — ignored")

        opt_type = (self.config.optimizer.type if self.config.optimizer
                    else "").lower().replace("_", "")
        self._onebit_wire = False
        if opt_type.startswith("zeroone"):
            # ZeroOneAdam's post-freeze variance REFRESH consumes the raw
            # gradient; with wire transport gradients stay unreduced per-rank
            # after freeze, so v (and then params) would silently diverge
            # across ranks — local compression only for this optimizer.
            logger.warning(
                "ZeroOneAdam runs with LOCAL compression only (its variance "
                "refresh consumes raw gradients, which stay per-rank under "
                "wire transport); use onebit_adam/onebit_lamb for the "
                "compressed-transport path")
        elif opt_type.startswith("onebit"):
            # fp16 excluded: the overflow skip decision would be taken on
            # per-rank (unreduced) grad norms — divergent control flow around
            # the transport collectives. expert=1 stays required HERE (qgZ
            # composes with MoE; the 1-bit momentum transport's per-rank
            # error buffers under expert sharding are untested territory).
            onebit_ok = eligible and shape.get("expert", 1) == 1
            if self.zero_stage == 0 and onebit_ok and not self.fp16_enabled \
                    and hasattr(self.optimizer, "transport"):
                self._onebit_wire = True
                log_dist("1-bit optimizer wire transport active: packed-sign "
                         f"momentum allreduce over {self._dp_manual_axes}")
            else:
                logger.warning(
                    "1-bit optimizer running with LOCAL compression only "
                    "(convergence parity, no wire saving): transport needs "
                    "ZeRO stage 0 (reference parity: 1-bit optimizers are "
                    "incompatible with ZeRO partitioning), dp width > 1 and "
                    f"expert=seq=pipe=1 (stage={self.zero_stage}, "
                    f"mesh={dict(shape)})")
        if self._compressed and self._onebit_wire:
            logger.warning("qwZ/qgZ and 1-bit transport are mutually "
                           "exclusive — using 1-bit transport")
            self._compressed = None

    # ------------------------------------------------------------------ #
    # autotune plan cache ("autotuning" section; autotuning/planner.py)
    # ------------------------------------------------------------------ #
    def _load_autotune_plan(self, zcfg) -> None:
        """Load and apply the cached autotune plan for this engine's
        ``(model_fingerprint, mesh_shape, wire_format, platform)`` key.

        Called at the TOP of ``__init__`` — before the hpZ subgroup
        resolution and the overlap scheduler consume any of the planned
        knobs. A knob the user ALSO set explicitly (tracked via
        ``_explicit_zero_keys`` from ``load_config``) is never
        overwritten: agreement is a hit, contradiction is a STALE plan —
        refused outright under ``autotuning.fail_on_stale``, else the
        explicit value wins with a loud warning. ``self._plan_status``
        ∈ {disabled, miss, hit, stale} for bench/report consumers.
        """
        self._plan_status = "disabled"
        self._plan_key: Optional[str] = None
        self._plan_doc: Optional[Dict] = None
        acfg = self.config.autotuning
        if acfg is None or not acfg.enabled:
            return
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.autotuning import planner as _planner

        key, _fields = _planner.plan_key_for_config(self.config,
                                                    self.model_spec)
        self._plan_key = key
        path = _planner.plan_path(acfg.plan_cache_dir, key)
        miss = telemetry.counter(
            "autotune_plan_cache_misses_total",
            "engine initializations with no usable cached plan")
        if not os.path.exists(path):
            self._plan_status = "miss"
            miss.inc()
            return
        try:
            doc = _planner.load_plan(path)
        except _planner.PlanError as e:
            if acfg.fail_on_stale:
                raise DeepSpeedConfigError(
                    f"autotuning.fail_on_stale: cached plan {path} is "
                    f"unreadable or schema-invalid ({e}) — regenerate it "
                    "with tools/plan or unset fail_on_stale") from None
            logger.warning(f"cached autotune plan {path} invalid — "
                           f"ignored ({e})")
            self._plan_status = "miss"
            miss.inc()
            return
        explicit = getattr(self.config, "_explicit_zero_keys", None)
        from deepspeed_tpu.runtime.config import ZeroConfig as _ZC

        defaults = _ZC()
        conflicts, applied = [], []
        for k, v in doc["knobs"].items():
            cur = getattr(zcfg, k, None)
            is_explicit = (k in explicit if explicit is not None
                           else cur != getattr(defaults, k, None))
            if is_explicit:
                if cur != v:
                    conflicts.append(f"{k}: config={cur!r} plan={v!r}")
                continue
            if k == "zero_hpz_partition_size" and v and int(v) > 1:
                # the subgroup IS the zshard axis: the planner's hpZ
                # candidates shrink 'data' by the subgroup width (same
                # device world, data x zshard layout) — mirror that, or
                # skip the knob when this mesh can't host it (an
                # explicit data axis not divisible by the subgroup)
                mesh = self.config.mesh
                if mesh.zshard == 1 and mesh.data > 1 \
                        and mesh.data % int(v) == 0:
                    mesh.data //= int(v)
                elif mesh.data > 0 and mesh.zshard == 1:
                    logger.warning(
                        f"autotune plan knob zero_hpz_partition_size={v} "
                        f"does not divide mesh.data={mesh.data} — knob "
                        "skipped")
                    continue
            setattr(zcfg, k, v)
            applied.append(k)
        if conflicts:
            self._plan_status = "stale"
            detail = "; ".join(conflicts)
            if acfg.fail_on_stale:
                raise DeepSpeedConfigError(
                    f"autotuning.fail_on_stale: engine config contradicts "
                    f"cached plan {path} ({detail}) — re-run tools/plan "
                    "for this config or drop the conflicting explicit "
                    "keys")
            logger.warning(
                f"cached autotune plan {path} is STALE against explicit "
                f"config keys ({detail}) — explicit values kept; planned "
                f"values applied only to: {applied or 'none'}")
            return
        self._plan_status = "hit"
        self._plan_doc = doc
        telemetry.counter(
            "autotune_plan_cache_hits_total",
            "engine initializations that applied a cached plan").inc()
        log_dist(f"autotune plan {key} applied "
                 f"(winner={doc.get('winner')}, knobs={applied})")

    # ------------------------------------------------------------------ #
    # overlap scheduler (parallel/overlap.py — README "Overlap scheduler")
    # ------------------------------------------------------------------ #
    def _setup_overlap_scheduler(self, zcfg) -> None:
        """Resolve the bucketed overlap scheduler and (when applicable)
        rebuild the model spec with a chunked layer scan + mid-backward
        grad-sync points.

        Honors the reference bucket keys WITH the reference's units
        (element counts): ``reduce_bucket_size`` bounds gradient-sync
        buckets, ``stage3_prefetch_bucket_size`` (stage 3) /
        ``allgather_bucket_size`` (stages 1-2) bound the layer-chunk
        parameter elements. Gated by ``overlap_comm`` at stage >= 1.

        Wire format and overlap are ORTHOGONAL axes of the step-builder
        pipeline (ISSUE 10): the qwZ/qgZ step composes — its chunk sync
        point is the manual-region-safe ordering fence
        (``overlap.manual_chunk_sync``; named sharding constraints don't
        exist inside shard_map), its grad buckets fence the int8
        reduces (``compressed.reduce_tree_bucketed``) and its ZeRO-3
        chunk gathers follow the same chunk plan on the quantized wire
        (``compressed.chunked_gather_tree_fn``). Only the 1-bit
        transport stays outside the scheduler, structurally: it is a
        stage-0 optimizer-side transport and the scheduler gates at
        stage >= 1."""
        from deepspeed_tpu.parallel.overlap import (
            OverlapConfig,
            chunk_layers,
            manual_chunk_sync,
        )

        self._overlap = OverlapConfig.from_zero_config(zcfg, self.zero_stage)
        # step-phase overlap (ROADMAP item 2; Automatic Cross-Replica
        # Sharding of Weight Update, 2004.13336): bucketed weight update
        # under the fence chain + the post-update param publish deferred
        # into a double buffer the NEXT step's forward consumes. Rides
        # the scheduler gate; the param buffer additionally needs a
        # fused device step that owns both the forward and the update
        # (no pipeline loss_and_grads_fn, no host-resident master, no
        # host-executed update; the 1-bit transport is stage 0 and never
        # reaches here with the scheduler on).
        ub = zcfg.update_bucket_size
        self._update_bucket_elems = (self._overlap.reduce_bucket_elems
                                     if ub == "auto" else int(ub))
        # dp world 1 has NO update-phase collectives to hide (GSPMD
        # elides them — the same reason hlolint's fence-defeat floor
        # only arms at dp > 1): the fences would only perturb fusion on
        # a program with nothing to overlap, so the serial step is kept
        # bit-identical there (incl. the single-chip CPU bench tier)
        self._step_overlap = bool(zcfg.overlap_step) \
            and self._overlap.enabled and self._dp_manual_world > 1
        # a pipe mesh activates the spec's explicit-backward
        # loss_and_grads_fn path, which bypasses the buffered forward
        pipelined = self.mesh_manager.axis_size("pipe") > 1
        self._param_buffer = (self._step_overlap and not pipelined
                              and not self._offload_param
                              and not self._host_step
                              and not self._onebit_wire)
        self._publish_fn = None     # lazy _publish_tree_fn cache
        self._consume_fn = None     # lazy _consume_param_buffer cache
        self._overlap_plan: Dict[str, Any] = {
            "enabled": self._overlap.enabled, "scan_chunks": 1,
            "chunk_bounds": [], "grad_sync_points": False,
            "step_overlap": self._step_overlap,
            "param_buffer": self._param_buffer,
            "update_bucket_elems": self._update_bucket_elems,
            "wire_format": self._wire_format()}
        if not self._overlap.enabled:
            return
        wire = self._compressed is not None
        model = self.model_spec
        spec_cfg = getattr(model, "config", None)
        n_layers = getattr(spec_cfg, "num_layers", 0) or 0
        can_chunk = (model.builder is not None and spec_cfg is not None
                     and hasattr(spec_cfg, "scan_chunks") and n_layers > 1
                     # a stack of layer kinds scans a period at a time
                     # (``scan_periods``): no chunked scan to hang the
                     # gathers and the gradient sync points on
                     and not getattr(spec_cfg, "layer_kinds", ())
                     and self.mesh_manager.axis_size("pipe") == 1)
        bounds = []
        if can_chunk:
            per_layer = self._blocks_elems_per_layer(n_layers)
            # stage 3: the prefetch bucket IS the gather granularity;
            # stages 1-2: allgather_bucket_size alone (the README
            # contract — reduce_bucket_size governs grad buckets only)
            chunk_elems = (self._overlap.prefetch_bucket_elems
                           if self.zero_stage >= 3
                           else self._overlap.allgather_bucket_elems)
            bounds = chunk_layers(n_layers, per_layer, chunk_elems)
        n_chunks = max(len(bounds), 1)
        # mid-backward sync points need a sharded gradient layout to pin
        # (stage >= 2); at stage 1 the chunked scan alone supplies the
        # gather granularity
        sync_fn = None
        if can_chunk and self.zero_stage >= 2:
            sync_fn = manual_chunk_sync() if wire \
                else self._make_chunk_grad_sync()
        if can_chunk and (n_chunks > 1 or sync_fn is not None):
            self.model_spec = model.builder(scan_chunks=n_chunks,
                                            param_sync_fn=sync_fn)
            self._overlap_plan.update(
                scan_chunks=n_chunks, chunk_bounds=bounds,
                grad_sync_points=sync_fn is not None)
            log_dist(f"overlap scheduler active: {n_chunks} layer chunk(s), "
                     f"wire={self._overlap_plan['wire_format']}, "
                     f"grad sync {'per chunk mid-backward' if sync_fn else 'bucketed at step level'}, "
                     f"reduce_bucket={self._overlap.reduce_bucket_elems} "
                     f"prefetch_bucket={self._overlap.prefetch_bucket_elems}")

    def _blocks_elems_per_layer(self, n_layers: int) -> int:
        """Per-layer parameter ELEMENTS (what a ZeRO-3 chunk gather
        moves per layer, in the bucket keys' reference unit)."""
        from deepspeed_tpu.parallel.overlap import leaf_count

        shapes = self._shapes.get("blocks") \
            if isinstance(self._shapes, dict) else None
        if shapes is None:
            return 0
        total = sum(leaf_count(s.shape) for s in jax.tree.leaves(shapes))
        return max(total // max(n_layers, 1), 1)

    def _make_chunk_grad_sync(self):
        """Closure for ``parallel/overlap.make_grad_sync``: constrain a
        layer-chunk's COTANGENT to its ZeRO gradient sharding so XLA
        emits the chunk's reduce as soon as its backward completes.
        Captures mesh/policy/axes — not the engine (no cycle)."""
        from deepspeed_tpu.parallel.overlap import make_grad_sync
        from deepspeed_tpu.parallel.partitioning import (
            _is_axes_leaf,
            logical_to_spec,
        )

        axes_blocks = self._axes.get("blocks") \
            if isinstance(self._axes, dict) else None
        if axes_blocks is None:
            return None
        mesh, policy = self.mesh, self.policy

        def _norm(spec):
            parts = list(spec)
            while parts and parts[-1] is None:
                parts.pop()
            return tuple(parts)

        def constrain(cotangent: PyTree) -> PyTree:
            def one(axes, g):
                spec = policy.leaf_grad_spec(axes, g.shape)
                if _norm(spec) == _norm(logical_to_spec(axes,
                                                        policy.tp_rules)):
                    # the chunk slice has no zero-divisible dim at this
                    # granularity — constraining would PIN a replicated
                    # layout mid-backward (a full all-reduce plus a
                    # reshard against the step-level sharded spec);
                    # leave the leaf to the step-end constraint instead
                    return g
                return jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, spec))

            return jax.tree.map(one, axes_blocks, cotangent,
                                is_leaf=_is_axes_leaf)

        return make_grad_sync(constrain)

    def overlap_plan(self) -> Dict[str, Any]:
        """The resolved overlap-scheduler plan (chunk bounds, bucket
        sizes, sync-point installation, step-phase overlap + param
        double buffer) — step-report / test hook."""
        plan = dict(self._overlap_plan)
        plan.update(reduce_bucket_elems=self._overlap.reduce_bucket_elems,
                    allgather_bucket_elems=self._overlap.allgather_bucket_elems,
                    prefetch_bucket_elems=self._overlap.prefetch_bucket_elems)
        return plan

    # ------------------------------------------------------------------ #
    # step-phase overlap: bucketed update + double-buffered params
    # (ROADMAP item 2; 2004.13336 — README "Overlap scheduler")
    # ------------------------------------------------------------------ #
    def _buffer_shardings(self) -> Any:
        """Shardings of the double-buffered gathered-params state leaf:
        the wire step's buffer is the per-rank FULL param tree
        (replicated — the persistent form of the stage-2-like transient
        the reduce-outside-vjp formulation already materialized); the
        exact step's buffer is the compute-param layout
        (``param_spec`` — stages 1-2 replicated, stage 3 sharded with
        per-use gathers staying in the forward)."""
        if self._compressed is not None:
            rep = NamedSharding(self.mesh, P())
            return jax.tree.map(lambda _: rep, self.master_spec,
                                is_leaf=lambda x: isinstance(x, P))
        return self.policy.to_shardings(self.param_spec)

    def _publish_tree_fn(self):
        """The tree-level deferred publish: new master → the gathered
        compute-param buffer the NEXT forward consumes. Wire steps run
        the SAME (chunk-fenced) qwZ/hpZ gather the forward used to
        issue at step start (``compressed.publish_gather_tree_fn`` —
        the wire rides the deferral unchanged); exact steps run the
        ``_compute_params`` cast/constrain, which at stages 1-2 IS the
        post-update all-gather. Traced under the ``zero_param_update``
        name scope so the observatory prices it as the update phase.
        Also the ``_refresh_param_buffer`` recompute — publish values
        are deterministic in the master, so a recomputed buffer is
        bit-equal to the in-step one."""
        if self._publish_fn is not None:
            return self._publish_fn
        if self._compressed is not None:
            from jax import shard_map

            from deepspeed_tpu.parallel import compressed as C

            axes = self._dp_manual_axes
            world = self._dp_manual_world
            dtype = jnp.dtype(self.precision)
            bounds = (self._overlap_plan.get("chunk_bounds") or [])
            gather = C.publish_gather_tree_fn(
                self.master_spec, axes, world, dtype,
                quant_weights=self._compressed["quant_weights"],
                chunk_bounds=bounds, axis_sizes=dict(self.mesh.shape))
            master_manual = jax.tree.map(
                lambda s: C.manual_spec(s, axes), self.master_spec,
                is_leaf=lambda x: isinstance(x, P))
            rep_specs = jax.tree.map(
                lambda _: P(), self.master_spec,
                is_leaf=lambda x: isinstance(x, P))
            mesh = self.mesh

            def publish(master):
                fn = shard_map(gather, mesh=mesh,
                               in_specs=(master_manual,),
                               out_specs=rep_specs,
                               axis_names=set(axes), check_vma=False)
                return fn(master)
        else:
            def publish(master):
                with jax.named_scope("zero_param_update"):
                    return self._compute_params(master)
        self._publish_fn = publish
        return publish

    def _publish_leaf_fns(self):
        """Per-leaf exact-path publish (master flatten order) — the
        ``_compute_params`` cast/constrain leaf-by-leaf
        (``_compute_param_leaf`` — the shared implementation), so each
        update bucket's publish can chain ONE fence behind its update
        in ``fenced_update_chain`` instead of waiting for the whole
        tree."""
        param_sh = jax.tree.leaves(
            self.policy.to_shardings(self.param_spec),
            is_leaf=lambda x: isinstance(x, NamedSharding))
        return [lambda m, sh=sh: self._compute_param_leaf(m, sh)
                for sh in param_sh]

    def _fence_update_buckets(self, new_master: PyTree, new_opt: Dict
                              ) -> Tuple[PyTree, Dict]:
        """Restructure the tree-wide optimizer update into per-bucket
        fenced groups (``update_bucket_size`` elements, reversed-flatten
        backward-completion order — the SAME plan the grad-sync buckets
        use, so update bucket k consumes grad bucket k). Optimizer
        moment trees that mirror the master tree ride the same fences;
        auxiliary state of other structures (factored Adafactor
        moments, per-layer scalars) is left to data dependence. Values
        are bit-identical to the unfenced update. The deferred publish
        consumes these FENCED leaves per bucket (``_publish_fenced``),
        so publish bucket k still launches the moment update bucket k
        lands — it runs outside this call (and outside the skip cond,
        see ``_apply_update``)."""
        from deepspeed_tpu.parallel.overlap import (
            fenced_update_chain,
            leaf_count,
            plan_buckets,
        )

        m_leaves, m_def = jax.tree.flatten(new_master)
        if not m_leaves:
            return new_master, new_opt
        sizes = [leaf_count(x.shape) for x in m_leaves]
        buckets = plan_buckets(sizes, self._update_bucket_elems)
        aux_names, aux_lists = [], []
        if isinstance(new_opt, dict):
            for name in getattr(self.optimizer, "moment_names", ()):
                sub = new_opt.get(name)
                if sub is None:
                    continue
                leaves, sdef = jax.tree.flatten(sub)
                if sdef == m_def:
                    aux_names.append(name)
                    aux_lists.append(leaves)
        out_m, out_aux, _ = fenced_update_chain(
            m_leaves, aux_lists, buckets)
        new_master = m_def.unflatten(out_m)
        if aux_names:
            new_opt = dict(new_opt)
            for name, leaves in zip(aux_names, out_aux):
                new_opt[name] = m_def.unflatten(leaves)
        return new_master, new_opt

    def _publish_fenced(self, master: PyTree) -> PyTree:
        """The deferred publish on the (fenced) post-update master:
        exact path — per-leaf cast/constrain grouped into the SAME
        bucket plan as the update fences and chained behind
        ``optimization_barrier`` tokens (``fenced_bucket_apply``), so
        each bucket's publish all-gather launches as its update lands;
        wire path — the tree-level chunk-fenced qwZ/hpZ gather
        (``_publish_tree_fn``)."""
        if self._compressed is not None or not self._step_overlap:
            return self._publish_tree_fn()(master)
        from deepspeed_tpu.parallel.overlap import (
            fenced_bucket_apply,
            leaf_count,
            plan_buckets,
        )

        leaves, tdef = jax.tree.flatten(master)
        pubs = self._publish_leaf_fns()
        if not leaves or len(pubs) != len(leaves):   # defensive drift
            return self._publish_tree_fn()(master)
        buckets = plan_buckets([leaf_count(x.shape) for x in leaves],
                               self._update_bucket_elems)
        return tdef.unflatten(fenced_bucket_apply(leaves, buckets, pubs))

    def _consume_param_buffer(self):
        """Straight-through consumption of the double-buffered params:
        the forward VALUE is the buffer (published by the PREVIOUS
        step's update phase — bit-equal to ``_compute_params(master)``
        by construction, both are deterministic in the master), while
        gradients flow exactly as if the forward had computed
        ``_compute_params(master)`` in-step — so the buffered step's
        backward (and its mid-backward sync points) is identical to the
        serial step's."""
        if self._consume_fn is not None:
            return self._consume_fn

        @jax.custom_vjp
        def use_buf(master, buf):
            return buf

        def fwd(master, buf):
            return buf, master

        def bwd(master, g):
            _, vjp = jax.vjp(self._compute_params, master)
            (gm,) = vjp(g)
            return gm, jax.tree.map(jnp.zeros_like, g)

        use_buf.defvjp(fwd, bwd)
        self._consume_fn = use_buf
        return use_buf

    def _refresh_param_buffer(self) -> None:
        """(Re)compute ``state['gathered']`` from the CURRENT master —
        at initialize and after any restore that replaces the master
        out-of-band (checkpoint load, universal load). The buffer is
        deliberately NEVER checkpointed: a recompute from the committed
        master is always consistent, so no checkpoint can capture a
        buffer one step stale relative to the weights it rode with."""
        if not self._param_buffer:
            return
        if self._compressed is None:
            # exact path: eager per-leaf cast + reshard — bit-equal to
            # the in-step publish (same cast, same layout) without a
            # per-engine XLA compile of a fused publish program at init.
            # A no-op cast (fp32 model, bf16 no-master) would ALIAS the
            # master leaf — the train step donates state, and a buffer
            # appearing under two donated leaves aborts Execute() —
            # so the same-dtype branch forces a real copy.
            dtype = jnp.dtype(self.precision)
            param_sh = self.policy.to_shardings(self.param_spec)

            def one(p, sh):
                x = p.astype(dtype) if p.dtype != dtype \
                    else jnp.array(p, copy=True)
                return jax.device_put(x, sh)

            with self.mesh:
                self.state["gathered"] = jax.tree.map(
                    one, self.state["master"], param_sh)
            return
        # wire path: the publish is a shard_map'd (possibly chunk-fenced
        # quantized) gather — jit it once per engine
        if "publish" not in self._compiled:
            self._compiled["publish"] = jax.jit(
                self._publish_tree_fn(),
                out_shardings=self._buffer_shardings())
        with self.mesh:
            self.state["gathered"] = self._compiled["publish"](
                self.state["master"])

    def _checkpoint_state(self) -> Dict[str, Any]:
        """The persisted view of train-step state: everything except the
        derived ``gathered`` double buffer (see
        ``_refresh_param_buffer`` — recomputed on every restore)."""
        if self._param_buffer and "gathered" in self.state:
            return {k: v for k, v in self.state.items() if k != "gathered"}
        return self.state

    # ------------------------------------------------------------------ #
    # data efficiency (curriculum / random-LTD / PLD / variable batch)
    # ------------------------------------------------------------------ #
    def _setup_data_efficiency(self) -> None:
        from deepspeed_tpu.runtime.data_pipeline import (
            CurriculumScheduler,
            RandomLTDScheduler,
        )
        from deepspeed_tpu.runtime.progressive_layer_drop import (
            ProgressiveLayerDrop,
        )

        pipe = self.mesh_manager.axis_size("pipe") > 1
        de = self.config.data_efficiency
        self._curriculum = None
        cur = self.config.curriculum
        de_cur = de.data_sampling.curriculum_learning
        if de_cur.enabled and not cur.enabled:
            logger.warning(
                "curriculum_learning.enabled is set under data_efficiency "
                "but data_efficiency.enabled / data_sampling.enabled are "
                "not — curriculum stays OFF (reference parent-gate "
                "semantics)")
        if cur.enabled:
            self._curriculum = CurriculumScheduler(cur.scheduler_dict())
            log_dist(f"curriculum learning active: {cur.schedule_type} "
                     f"{cur.min_difficulty}→{cur.max_difficulty}")

        self._ltd = None
        ltd = de.data_routing.random_ltd
        if ltd.enabled and not (de.enabled and de.data_routing.enabled):
            logger.warning(
                "random_ltd.enabled is set but data_efficiency.enabled / "
                "data_routing.enabled are not — random-LTD stays OFF "
                "(reference parent-gate semantics)")
        elif ltd.enabled:
            if pipe:
                logger.warning("random-LTD is not supported with pipeline "
                               "parallelism — disabled")
            else:
                self._ltd = RandomLTDScheduler(
                    {"random_ltd_schedule": ltd.random_ltd_schedule,
                     "max_value": ltd.max_value})
                log_dist("random-LTD active")

        self._pld = None
        pld = self.config.progressive_layer_drop
        if pld.enabled:
            if pipe:
                logger.warning("progressive layer drop is not supported with "
                               "pipeline parallelism — disabled")
            else:
                self._pld = ProgressiveLayerDrop(pld.theta, pld.gamma)
                log_dist(f"progressive layer drop active: theta={pld.theta} "
                         f"gamma={pld.gamma}")
        self._np_rng = np.random.default_rng(self.config.seed)

    def _n_layers(self) -> int:
        cfg = getattr(self.model_spec, "config", None)
        return getattr(cfg, "num_layers", 0) or 0

    # ------------------------------------------------------------------ #
    # telemetry (deepspeed_tpu/telemetry — README "Observability")
    # ------------------------------------------------------------------ #
    def _setup_telemetry(self) -> None:
        """Attach the engine to the process-wide metrics registry.

        Hot-path cost is a few dict/float ops per optimizer step (host
        side, no device fences). Everything priced — device_get of the
        last step's metrics — runs in a registry COLLECTOR, i.e. only when
        something scrapes ``telemetry.snapshot()`` / the ``/metrics``
        endpoint or the monitor bridge publishes."""
        tcfg = self.config.telemetry
        self._tm = None
        self._watchdog = None   # racelint: single-thread — every writer (telemetry setup/teardown and the SIGTERM handler, which CPython delivers between MAIN-thread bytecodes) runs on the main thread; the watchdog thread only calls beat()/check() through its own reference
        self._tm_bridge = None
        # device-side overflow/non-finite skip counter, delta-folded into
        # the monotone train_skipped_steps_total (set before the enabled
        # gate: the guardian folds through this path too)
        self._tm_skips_seen = 0
        from deepspeed_tpu import telemetry

        # the registry gate is process-wide (last engine's config wins, as
        # with the global mesh) — without this, "enabled": false would only
        # skip the engine's own instruments while fastgen/timer/comms kept
        # recording
        telemetry.get_registry().enabled = bool(tcfg.enabled)
        # tracer gate is process-wide too (same last-engine-wins rule);
        # configuring with enabled=False keeps every span() site at its
        # one-attribute-check disabled cost
        from deepspeed_tpu.telemetry import tracing as _tracing

        _tracing.configure(
            enabled=bool(tcfg.enabled and tcfg.tracing),
            capacity=tcfg.trace_buffer_events,
            sample_rate=tcfg.trace_sample_rate,
            dump_dir=tcfg.flight_dump_dir)
        if not tcfg.enabled:
            return

        self._tm = telemetry.get_registry()
        self._tm_steps = telemetry.counter(
            "train_steps_total", "completed optimizer steps")
        self._tm_tokens = telemetry.counter(
            "train_tokens_total", "tokens consumed by completed steps "
            "(global batch, all chips)")
        self._tm_step_hist = telemetry.histogram(
            "train_step_seconds", "host wall time around each step "
            "dispatch (async backends may record enqueue-only samples: "
            "for a rate take the increase of train_tokens_total)")
        self._tm_heartbeat = telemetry.gauge(
            "train_heartbeat_timestamp_seconds",
            "unix time the last optimizer step completed")
        ref = weakref.ref(self)

        def _collect():
            eng = ref()
            if eng is None:
                return False   # engine gone — deregister (weakref idiom)
            eng._collect_telemetry()

        self._tm.add_collector(_collect)
        if tcfg.http_port >= 0 and jax.process_index() == 0:
            try:
                server = telemetry.start_metrics_server(tcfg.http_port)
                log_dist(f"telemetry /metrics endpoint: {server.url}")
            except OSError as e:
                # port in use (second run on the host) — observability must
                # never abort training; metrics stay scrapeable in-process
                logger.warning(
                    f"telemetry /metrics endpoint on port {tcfg.http_port} "
                    f"failed to start ({e}); continuing without it")
        if tcfg.stall_deadline_s > 0:
            on_stall = None
            action = self.config.fault_tolerance.on_stall
            if action in ("dump_trace", "checkpoint"):
                # escalate detection → response, both flavors leading
                # with a flight-recorder dump named after the last
                # completed span (the timeline that led INTO the stall);
                # "checkpoint" then saves the LAST COMPLETED state from
                # the watchdog thread (self.state is immutable jax
                # arrays, replaced only at step boundaries — a stalled
                # step by definition hasn't replaced it)
                wref = weakref.ref(self)

                def on_stall():
                    eng = wref()
                    if eng is None:
                        return
                    last = eng._tm.last_span if eng._tm is not None \
                        else None
                    _tracing.get_tracer().dump_flight(
                        "stall", note=last[0] if last else None)
                    if action == "checkpoint":
                        eng._emergency_save("stall")

            self._watchdog = telemetry.StallWatchdog(
                tcfg.stall_deadline_s, self._tm, on_stall=on_stall).start()

    def _fold_skipped_steps(self, skips: int, resync: bool = False) -> None:
        """Fold the device-side skip counter into the monotone
        ``train_skipped_steps_total`` (delta-based). Fed from two paths:
        the scrape-time collector (``resync=True`` — a guardian rollback
        restores an OLDER device counter, and the watermark must follow
        it down or post-rollback skips go uncounted) and the guardian's
        log-cadence observe (no resync — a skip must reach the metric
        even if a rollback rewinds the device counter before the next
        scrape)."""
        # locked: the scrape-time collector runs on the /metrics HTTP
        # thread concurrently with the guardian's training-thread fold —
        # an unlocked read-modify-write of the watermark double-counts
        with self._tm_skips_lock:
            self._fold_skips_locked(skips, resync=resync)

    def _fold_skips_locked(self, skips: int,
                           resync: bool = False) -> None:   # locked: _tm_skips_lock
        from deepspeed_tpu import telemetry

        delta = skips - self._tm_skips_seen
        if delta > 0:
            telemetry.counter(
                "train_skipped_steps_total",
                "optimizer steps skipped by the device-side "
                "non-finite guard (fp16 overflow + guardian "
                "bf16/fp32 sentinel)").inc(delta)
        if delta > 0 or resync:
            self._tm_skips_seen = skips

    def _chip_peak_flops(self) -> Optional[float]:
        from deepspeed_tpu.utils.chip_specs import chip_peak_tflops

        peak = chip_peak_tflops(
            getattr(jax.devices()[0], "device_kind", ""))
        # CPU backend etc.: no meaningful MFU referent → None
        return peak * 1e12 if peak else None

    def _collect_telemetry(self) -> None:
        """Scrape-time collector: lazily-priced gauges (loss/grad-norm from
        the device metrics of the last step, the skip counter).

        May run on the /metrics HTTP thread concurrent with training, so it
        avoids mutating engine state and never fences the device mid-step
        from another thread. A rate is the scraper's to take
        (``train_tokens_total`` over time); utilization is the benchmark's
        (``benchmarks/``: model FLOPs from shapes, no recompute)."""
        from deepspeed_tpu import telemetry

        self._observe_meters(None)
        if self._last_metrics_dev:
            try:
                host = {k: float(jax.device_get(v))
                        for k, v in self._last_metrics_dev.items()}
            except Exception as e:   # deleted buffers between steps: skip
                logger.debug(f"last-step metric device_get failed "
                             f"({type(e).__name__}: {e})")
                host = {}
            for k in ("loss", "grad_norm", "lr", "loss_scale", "overflow"):
                if k in host:
                    telemetry.gauge(f"train_{k}").set(host[k])
        if "skips" in self.state:
            # device read + fold under ONE lock acquisition: a guardian
            # rollback resyncing the watermark between an unlocked read
            # and the fold would double-count the restored skips
            with self._tm_skips_lock:
                try:
                    skips = int(jax.device_get(self.state["skips"]))
                except Exception as e:   # deleted buffers: skip this scrape
                    logger.debug(f"skip-counter device_get failed "
                                 f"({type(e).__name__}: {e})")
                    skips = None
                if skips is not None:
                    self._fold_skips_locked(skips, resync=True)

    def collective_ledger(self, fold: bool = True,
                          seq_len: Optional[int] = None):
        """Compiled-collective ledger of the live fused train step (the
        execution-observatory hook): every all-reduce / reduce-scatter /
        all-gather / all-to-all / collective-permute XLA's partitioner
        emitted for this engine's ZeRO stage, with bytes, replica groups,
        and issuing-subsystem attribution. ``fold=True`` publishes the
        ``comm_ledger_*`` metrics (README "Execution observatory").
        Cached per engine — the one-off lowering compile is priced on the
        first call only. Returns a
        :class:`~deepspeed_tpu.profiling.observatory.CollectiveLedger`.
        """
        from deepspeed_tpu.profiling.observatory import ledger_for_engine

        return ledger_for_engine(self, fold=fold, seq_len=seq_len)[0]

    def step_report(self, **kwargs) -> Dict[str, Any]:
        """Roofline step report (ledger + overlap + memory vs the ZeRO
        partitioning prediction + per-phase bound verdicts) — the
        ``tools/step-report`` CLI in library form."""
        from deepspeed_tpu.profiling.observatory import step_report

        return step_report(self, **kwargs)

    def lint_step(self, contract: Optional[str] = None,
                  seq_len: Optional[int] = None) -> List:
        """hlolint over THIS engine's lowered fused train step — the
        ``tools/hlolint --live`` path in library form. The linted
        program is the one ``_dispatch_train_step`` runs (the
        observatory's ``ledger_for_engine`` mirrors
        ``_select_step_builder`` and caches the lowering), and the lint
        config comes from the engine's resolved wire format, overlap
        plan, and bucket plan. ``contract`` names a committed contract
        JSON to enforce on top of the structural rules. Returns the
        violations (empty = clean)."""
        from deepspeed_tpu.analysis.hlolint import lint_engine

        return lint_engine(self, contract=contract, seq_len=seq_len)

    def lint_memory(self, contract: Optional[str] = None,
                    seq_len: Optional[int] = None,
                    hbm_budget_bytes: Optional[float] = None) -> List:
        """memlint over THIS engine's lowered fused train step — the
        ``tools/memlint --live`` path in library form (donation/aliasing
        verification, residency vs the ZeRO partitioning-math
        prediction, the OOM pre-flight at ``hbm_budget_bytes``, plus a
        committed memory ``contract`` when named). The linted program
        is the SAME cached lowering ``lint_step``/the ledger read — a
        memory lint never pays a second compile. Returns the
        violations (empty = clean)."""
        from deepspeed_tpu.analysis.memlint import lint_engine

        return lint_engine(self, contract=contract, seq_len=seq_len,
                           hbm_budget_bytes=hbm_budget_bytes)

    @staticmethod
    def _count_tokens(stacked: PyTree) -> int:
        """Token count of one stacked step window (global batch)."""
        arr = stacked
        if isinstance(stacked, dict):
            # engine-injected control keys (_pld_keep, _random_ltd_idx,
            # lr_scale) sort first in the leaf order and are NOT tokens —
            # prefer the conventional token keys, then any data key
            for key in ("tokens", "input_ids"):
                if key in stacked:
                    arr = stacked[key]
                    break
            else:
                data_keys = sorted(k for k in stacked
                                   if not str(k).startswith("_")
                                   and k != "lr_scale")
                arr = stacked[data_keys[0]] if data_keys else None
        if arr is None:
            return 0
        # metadata only — np.asarray on a jax array would be a full D2H copy
        size = getattr(arr, "size", None)
        return int(size) if size is not None else int(np.asarray(arr).size)

    def shutdown_telemetry(self) -> None:
        """Stop the stall watchdog thread. Called on engine GC too —
        otherwise every watchdog-armed run that simply FINISHES training
        would log a false stall (the watchdog can't distinguish 'done'
        from 'stuck'); long-lived processes that keep the engine alive
        after the last step should call this explicitly. The last step's
        meters, kept for the next step to observe, are observed now."""
        self._observe_meters(None)
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None

    def _observe_meters(self, meters: Optional[Dict[str, jax.Array]]
                        ) -> None:
        """What a step's layers metered of themselves (the non-scalar
        entries of the compiled step's ``metrics``) into the registry, a
        step late: ``meters`` are the step just dispatched, kept (their
        copy to the host started) until the next call, which observes them
        when that step has long ended; so the engine adds no fence of its
        own, and where a caller does not read its loss the host runs at
        most a step ahead of the last meter it read. ``None`` observes what
        is kept (``shutdown_telemetry``, the registry's collector: it may
        run on the scrape thread, so the swap is locked)."""
        if meters:
            for m in meters.values():
                m.copy_to_host_async()
        with self._meters_lock:
            meters, self._meters_pending = self._meters_pending, meters
        if not meters:
            return
        from deepspeed_tpu.moe.layer import read_held_meter

        rows_h, imbalance_h, share_h, moved_h = self._tm_held
        # [micro-batches, expert layers, held + 2]: a call of a layer each
        for meter in np.asarray(meters["moe_held"]).reshape(
                -1, meters["moe_held"].shape[-1]):
            rows, pairs, tile = read_held_meter(meter)
            n, mean = int(rows.sum()), float(rows.mean())
            rows_h.observe(mean)
            imbalance_h.observe(float(rows.max()) / max(mean, 1e-9))
            share_h.observe(n / pairs)
            moved_h.observe(1.0 if tile is None
                            else -(-n // tile) * tile / pairs)

    def __del__(self):
        try:
            self.shutdown_telemetry()
        # interpreter teardown: attributes may already be gone, and
        # raising from __del__ only prints noise
        except Exception:   # dslint: disable=silent-except
            pass

    def _inject_data_efficiency(self, stacked: PyTree, gas: int) -> PyTree:
        """Add per-micro PLD keep masks / random-LTD kept-token indices to
        the stacked batch dict (underscore keys — replicated, consumed by the
        model spec's loss_fn)."""
        if self._ltd is None and self._pld is None:
            return stacked
        if not isinstance(stacked, dict):
            stacked = {"tokens": stacked}
        else:
            stacked = dict(stacked)
        if self._pld is not None:
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                layer_keep_probs,
            )

            L = self._n_layers()
            theta = self._pld.update_state(self.global_steps)
            probs = np.asarray(jax.device_get(layer_keep_probs(theta, L)))
            stacked["_pld_keep"] = (
                self._np_rng.random((gas, L)) < probs[None]
            ).astype(np.float32)
        if self._ltd is not None:
            seq_len = np.asarray(stacked["tokens"]).shape[-1]
            kept = min(self._ltd.get_kept_tokens(self.global_steps), seq_len)
            idx = np.stack([
                np.sort(self._np_rng.choice(seq_len, kept, replace=False))
                for _ in range(gas)]).astype(np.int32)
            stacked["_random_ltd_idx"] = idx
        return stacked

    # ------------------------------------------------------------------ #
    # state construction
    # ------------------------------------------------------------------ #
    def _state_shardings(self) -> Dict[str, Any]:
        to_sh = self.policy.to_shardings
        master_sh = to_sh(self.master_spec)
        moment_sh = master_sh
        moment_shapes = self._shapes
        if self._trainable_mask is not None:
            from deepspeed_tpu.utils.tree import prune_tree

            moment_sh = prune_tree(master_sh, self._trainable_mask)
            moment_shapes = prune_tree(self._shapes, self._trainable_mask)
        # optimizer state leaves that mirror the param shape inherit its
        # sharding; auxiliary leaves of other shapes (e.g. OnebitLamb's
        # per-layer frozen trust scalars) are replicated.
        rep = NamedSharding(self.mesh, P())
        opt_shapes = jax.eval_shape(self.optimizer.init, self._shapes)
        moment_structure = jax.tree.structure(moment_shapes)
        opt_sh = {}
        for name in self.optimizer.moment_names:
            sub = opt_shapes[name]
            if jax.tree.structure(sub) == moment_structure:
                opt_sh[name] = jax.tree.map(
                    lambda os, sh, ms: sh if os.shape == ms.shape else rep,
                    sub, moment_sh, moment_shapes)
            else:
                # schedule scalars etc. that don't mirror the param tree
                opt_sh[name] = jax.tree.map(lambda _: rep, sub)
        opt_sh["step"] = NamedSharding(self.mesh, P())
        if self._onebit_wire:
            axes = self._dp_manual_axes
            row = axes if len(axes) > 1 else axes[0]
            opt_sh["worker_error"] = jax.tree.map(
                lambda _: NamedSharding(self.mesh, P(row)),
                opt_sh["worker_error"])
        sh = {"step": NamedSharding(self.mesh, P()), "master": master_sh, "opt": opt_sh}
        if self.fp16_enabled:
            rep = NamedSharding(self.mesh, P())
            sh["scaler"] = jax.tree.map(lambda _: rep, self.scaler.init_state())
            sh["skips"] = rep
        elif self._nonfinite_guard:
            sh["skips"] = NamedSharding(self.mesh, P())
        if self._compressed is not None and self._compressed.get("loco"):
            axes = self._dp_manual_axes
            row = axes if len(axes) > 1 else axes[0]
            sh["loco_err"] = jax.tree.map(
                lambda s: NamedSharding(
                    self.mesh, P(row, *([None] * len(s.shape)))),
                self._shapes)
        if self._param_buffer:
            # double-buffered gathered params (step-phase overlap):
            # published at step END, consumed by the NEXT forward
            sh["gathered"] = self._buffer_shardings()
        return sh

    @staticmethod
    def _to_host_shardings(sh_tree: Any) -> Any:
        """Same layout, pinned host memory (ZeRO-Offload storage tier)."""
        return jax.tree.map(
            lambda s: s.with_memory_kind("pinned_host"), sh_tree,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    def _make_state(self, rng) -> Dict[str, Any]:
        master = self.model_spec.init_fn(rng)
        if self.precision == "bfloat16" and not self.config.bf16.fp32_master:
            # no-fp32-master mode: the "master" IS the bf16 compute tree;
            # optimizer updates still compute in fp32 per-leaf (cast inside
            # the fused update — nothing fp32 is materialized tree-wide)
            master = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, master)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "master": master,
            "opt": self.optimizer.init(master),
        }
        if self._onebit_wire:
            # per-worker compression error: one row per DP rank (the
            # reference's worker_error buffers are per-rank by construction;
            # under SPMD that is a leading sharded world dim)
            state["opt"]["worker_error"] = jax.tree.map(
                lambda e: jnp.zeros((self._dp_manual_world,) + e.shape,
                                    e.dtype),
                state["opt"]["worker_error"])
        if self.fp16_enabled:
            state["scaler"] = self.scaler.init_state()
            state["skips"] = jnp.zeros((), jnp.int32)
        elif self._nonfinite_guard:
            # bf16/fp32 sentinel: same device-side skip counter as fp16
            state["skips"] = jnp.zeros((), jnp.int32)
        if self._compressed is not None and self._compressed.get("loco"):
            # per-rank LoCo residuals: leading sharded world dim (same
            # pattern as the 1-bit worker_error buffers); full-gradient
            # shape per rank, fp32
            state["loco_err"] = jax.tree.map(
                lambda s: jnp.zeros(
                    (self._dp_manual_world,) + s.shape, jnp.float32),
                self._shapes)
        return state

    def _master_host_shardings(self) -> Any:
        """The offload_param storage tier: master layout, pinned host."""
        return self._to_host_shardings(
            self.policy.to_shardings(self.master_spec))

    def _park_master(self) -> None:
        """Move the master to its pinned-host tier (offload_param).

        Runs at the JIT BOUNDARY: in-program pinned-host OUTPUT annotations
        don't partition under SPMD ("side-effect ops cannot be
        replicated"), while host-resident INPUTS do — so each step takes
        the host master in (the model streams layer slices H2D inside its
        layer scan), produces the updated master on device, and this moves
        it back out."""
        self.state["master"] = jax.device_put(self.state["master"],
                                              self._master_host_shardings())

    def _unpark_master(self) -> None:
        """Boundary-swap mode (no in-step streaming): move the parked
        master onto device before the step."""
        self.state["master"] = jax.device_put(
            self.state["master"],
            self.policy.to_shardings(self.master_spec))

    def _materialize_master(self) -> None:
        """Direct-use paths (eval/predict/eager forward/step, fp32
        consolidation) read ``state['master']`` as a plain device tree —
        restore it from whichever offload tier currently holds it
        (NVMe files and/or pinned host)."""
        if self._offload_param_nvme and self._param_swapper is not None:
            self._param_swapper.swap_in_params()
        if self._offload_param:
            from deepspeed_tpu.utils.memory import is_host_resident

            leaves = jax.tree.leaves(self.state["master"])
            if leaves and is_host_resident(leaves[0]):
                self._unpark_master()

    def _ensure_master_tier_for_step(self) -> None:
        """Put the master where the compiled step expects it: pinned host
        for the streaming step (whose in_shardings declare host inputs —
        a direct-use path may have materialized it on device), device for
        boundary-swap mode."""
        if not self._offload_param:
            return
        if self._offload_param_stream:
            from deepspeed_tpu.utils.memory import is_host_resident

            leaves = jax.tree.leaves(self.state["master"])
            if leaves and not is_host_resident(leaves[0]):
                self._park_master()
        else:
            self._unpark_master()

    def _init_state(self) -> Dict[str, Any]:
        shardings = self._state_shardings()
        # the gathered double buffer is DERIVED state — built by
        # _refresh_param_buffer right after init, never by _make_state
        shardings.pop("gathered", None)
        init = jax.jit(self._make_state, out_shardings=shardings)
        with self.mesh:
            state = init(self._init_rng)
        if self._offload_param:
            state["master"] = jax.device_put(state["master"],
                                             self._master_host_shardings())
        return state

    # ------------------------------------------------------------------ #
    # jitted step builders
    # ------------------------------------------------------------------ #
    def _compute_param_leaf(self, p, sh):
        """THE per-leaf master → compute-param math (cast + constrain).
        ``_compute_params`` and the per-bucket fenced publish
        (``_publish_leaf_fns``) must stay ONE implementation: the
        double-buffered forward consumes the publish VALUE while
        gradients flow through ``_compute_params``, so any drift
        between them silently breaks the buffer's bit-equality
        contract."""
        return jax.lax.with_sharding_constraint(
            p.astype(jnp.dtype(self.precision)), sh)

    def _compute_params(self, master: PyTree) -> PyTree:
        """Cast fp32 master → compute dtype, constrained to the param sharding
        (stage 3: sharded → XLA gathers per use; else replicated over data).

        offload_param: by the time this runs, the engine has already
        streamed the host master onto device in the sharded layout
        (``_loss_and_grads``), so the normal cast/constrain applies."""
        param_sh = self.policy.to_shardings(self.param_spec)
        return jax.tree.map(self._compute_param_leaf, master, param_sh)

    @jax.named_scope("grad_reduce")
    def _constrain_grads(self, grads: PyTree) -> PyTree:
        grad_sh = self.policy.to_shardings(self.grad_spec)
        if getattr(self, "_overlap", None) is None \
                or not self._overlap.enabled:
            return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                                grad_sh)
        return self._constrain_grads_bucketed(grads, grad_sh)

    def _constrain_grads_bucketed(self, grads: PyTree,
                                  grad_sh: PyTree) -> PyTree:
        """Bucketed gradient sync: top-level leaves grouped into
        ``reduce_bucket_size``-bounded buckets (element counts, the
        reference's unit; reversed tree-flatten order — the
        backward-completion approximation) and constrained
        bucket-by-bucket behind ``optimization_barrier`` fences, so the
        collectives stay size-bounded and ordered in the lowered program
        instead of fusing into one step-end sync. Identical values —
        the fences and constraints are identities (allclose-pinned in
        tests/unit/test_overlap.py)."""
        from deepspeed_tpu.parallel.overlap import (
            fenced_bucket_apply,
            leaf_count,
            plan_buckets,
        )

        leaves, treedef = jax.tree.flatten(grads)
        sh_leaves = jax.tree.leaves(grad_sh)
        if len(leaves) != len(sh_leaves) or not leaves:
            return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                                grad_sh)
        sizes = [leaf_count(x.shape) for x in leaves]
        buckets = plan_buckets(sizes, self._overlap.reduce_bucket_elems)
        fns = [lambda x, s=s: jax.lax.with_sharding_constraint(x, s)
               for s in sh_leaves]
        return jax.tree.unflatten(
            treedef, fenced_bucket_apply(leaves, buckets, fns))

    def _loss_and_grads(self, master: PyTree, batch: PyTree, scale,
                        params_buf: Optional[PyTree] = None,
                        with_meters: bool = False):
        """``(loss, grads)``; ``with_meters``: and, last, the dict of what
        the forward pass's layers meter of themselves
        (``transformer.collect_meters``; empty for most models): an
        auxiliary output of the differentiated function, forward only."""
        if self._offload_param:
            # H2D stream OUTSIDE the autodiff: differentiating w.r.t. the
            # host-resident master would put every cotangent in host space
            # (the device_put VJP transposes to D2H) and drag the whole
            # backward into host memory. Streaming first keeps grads on
            # device; the stream lands in the ZeRO-3 SHARDED layout (f32
            # master never replicates), and the updated master is parked
            # back to pinned host at the jit boundary (_park_master).
            from deepspeed_tpu.utils.memory import stream_to_shardings

            master = stream_to_shardings(
                master, self.policy.to_shardings(self.master_spec))
        # schedules with an explicit backward (1F1B pipeline) return grads
        # directly — autodiff over the loss would rebuild the O(M)-memory
        # GPipe reverse wavefront
        fn = getattr(self.model_spec, "loss_and_grads_fn", None)
        if fn is not None:
            with jax.named_scope("loss_and_grads"):
                out = fn(self._compute_params(master), batch, scale)
            if out is not None:
                loss, grads = out
                grads = jax.tree.map(
                    lambda g, m: g.astype(m.dtype), grads, master)
                return (loss, self._constrain_grads(grads),
                        *([{}] if with_meters else []))

        def scaled_loss(m):
            if params_buf is not None:
                # double-buffered forward (step-phase overlap): consume
                # the buffer published by the PREVIOUS step's update
                # phase; gradients still flow through _compute_params
                # (straight-through — see _consume_param_buffer)
                params = self._consume_param_buffer()(m, params_buf)
            else:
                params = self._compute_params(m)
            if with_meters:
                from deepspeed_tpu.models.transformer import collect_meters

                with collect_meters() as meters:
                    loss = self.model_spec.loss_fn(params, batch)
                return (loss * scale if scale is not None else loss), meters
            loss = self.model_spec.loss_fn(params, batch)
            return loss * scale if scale is not None else loss

        # the scope under which a device trace tells forward
        # (``loss_and_grads/jvp(..)``), backward (``transpose(jvp(..))``)
        # and recompute (``rematted_computation``) apart
        with jax.named_scope("loss_and_grads"):
            out, grads = jax.value_and_grad(
                scaled_loss, has_aux=with_meters)(master)
        loss, *meters = out if with_meters else (out,)
        if scale is not None:
            loss = loss / scale
        return (loss, self._constrain_grads(grads), *meters)

    def _lr_at(self, step):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.lr_at(step)
        return jnp.asarray(self.optimizer.lr, jnp.float32)

    @jax.named_scope("optimizer")
    def _apply_update(self, state: Dict[str, Any], grads: PyTree,
                      grad_scale, lr_mult=None
                      ) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
        """Unscale, clip, (maybe skip on overflow), optimizer update.

        Step-phase overlap (``overlap_step``; 2004.13336): the update's
        outputs are restructured into per-bucket fenced groups in
        backward-completion order (``_fence_update_buckets``) so each
        bucket's apply — and, double-buffered, its param publish —
        leaves the critical path the moment its gradients land instead
        of waiting for the whole tree; the publish lands in
        ``state['gathered']`` for the NEXT step's forward. The skip
        branch (fp16 overflow / guardian non-finite) skips every
        bucket's update coherently (ONE ``lax.cond`` around the whole
        phase) and republishes the UNCHANGED buffer."""
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / grad_scale, grads)
        lr = self._lr_at(state["step"])
        if lr_mult is not None:
            # variable-batch LR scaling (reference
            # variable_batch_size_and_lr.py scale_lr)
            lr = lr * lr_mult
        if self._trainable_mask is not None:
            from deepspeed_tpu.utils.tree import prune_tree

            norm = global_grad_norm(prune_tree(grads, self._trainable_mask))
        else:
            norm = global_grad_norm(grads)
        if self.config.gradient_clipping > 0:
            grads = clip_by_global_norm(grads, self.config.gradient_clipping, norm)

        def _stream_master(master):
            if not self._offload_param:
                return master
            from deepspeed_tpu.utils.memory import stream_to_shardings

            return stream_to_shardings(
                master, self.policy.to_shardings(self.master_spec))

        buffered = self._param_buffer and "gathered" in state
        step_fenced = self._step_overlap

        def do_update(operand):
            master, opt, g = operand
            new_master, new_opt = self.optimizer.update(
                g, opt, _stream_master(master), lr=lr)
            if step_fenced:
                with jax.named_scope("zero_param_update"):
                    new_master, new_opt = self._fence_update_buckets(
                        new_master, new_opt)
            return new_master, new_opt

        def skip_update(operand):
            master, opt, _ = operand
            # both lax.cond branches must produce the same memory space
            return _stream_master(master), opt

        if self.fp16_enabled:
            overflow = jnp.logical_not(jnp.isfinite(norm))
            new_master, new_opt = jax.lax.cond(
                overflow, skip_update, do_update,
                (state["master"], state["opt"], grads))
            new_scaler = self.scaler.update(state["scaler"], overflow)
        elif self._nonfinite_guard:
            # guardian numerics sentinel (config "guardian"): the fp16
            # skip-update lax.cond extended to bf16/fp32 — no scaler, pure
            # skip. A non-finite gradient step must never touch the
            # weights; the same device-side isfinite reduction (the norm
            # is already computed for clipping) decides, the same
            # device-side `skips` counter records it, and no host sync is
            # added to the hot path.
            overflow = jnp.logical_not(jnp.isfinite(norm))
            new_master, new_opt = jax.lax.cond(
                overflow, skip_update, do_update,
                (state["master"], state["opt"], grads))
            new_scaler = None
        else:
            overflow = jnp.asarray(False)
            new_master, new_opt = do_update((state["master"], state["opt"], grads))
            new_scaler = None
        new_gathered = None
        if buffered:
            # the deferred publish runs OUTSIDE the skip cond: the
            # publish is deterministic in the master, so a skipped step
            # republishes the UNCHANGED buffer bit-equal (master didn't
            # move) — and the guarded program keeps the unguarded one's
            # collective shape (a publish inside a cond branch forces
            # GSPMD resharding around the branch; the guardian's
            # zero-added-collectives pin forbids that)
            with jax.named_scope("zero_param_update"):
                new_gathered = self._publish_fenced(new_master)

        new_state = {"step": state["step"] + 1, "master": new_master, "opt": new_opt}
        if new_gathered is not None:
            new_state["gathered"] = new_gathered
        if new_scaler is not None:
            new_state["scaler"] = new_scaler
        if "skips" in state:
            new_state["skips"] = state["skips"] + overflow.astype(jnp.int32)
        metrics = {"grad_norm": norm, "lr": lr,
                   "overflow": overflow.astype(jnp.float32)}
        if self.fp16_enabled:
            metrics["loss_scale"] = new_state["scaler"].scale
        return new_state, metrics

    def _grad_accum_dtype(self):
        """GAS accumulator dtype: fp32 default; data_types.grad_accum_dtype
        opts into bf16 (reference data_types section, including its
        "bf16"/"fp16"/"fp32" spellings). Shared by every step builder
        but the 1-bit wire step (fp32: its warmup all-reduces the sum) —
        at multi-B params the fp32 grad buffer IS the HBM ceiling. The
        buffer exists at gas > 1 only (``accumulate_microbatches``)."""
        name = self.config.data_types.grad_accum_dtype
        alias = {"bf16": "bfloat16", "fp16": "float16", "fp32": "float32"}
        return jnp.dtype(alias.get(name, name) if name else jnp.float32)

    @staticmethod
    def accumulate_microbatches(micro_fn, like, acc_dtype, batch, gas,
                                constrain=lambda x: x, extra0=None,
                                has_meters=False):
        """Shared GAS loop: the sum, in ``acc_dtype`` (callers pass
        ``_grad_accum_dtype()``; fp32 default), of the gradients of
        ``micro_fn(mb) -> (loss, grads)`` over the leading micro-batch
        dim, and the mean loss.
        Used by every fused step builder and the host-step runner, and
        available to custom step builders — keep ONE copy of these
        semantics.

        One micro-batch has nothing to accumulate: its gradients, cast to
        ``acc_dtype`` (``0 + g`` rounds the same way) and constrained, ARE
        the sum — no accumulator exists at gas == 1. At gas > 1 the carry
        is zeros shaped like ``like`` (arrays or ShapeDtypeStructs; read
        for its shapes only, and only then) and a ``lax.scan`` adds each
        micro-batch's gradients into it.

        ``extra0``: optional extra carry threaded through the micros (LoCo
        residuals); micro_fn is then called as ``micro_fn(mb, extra) ->
        (loss, grads, extra)`` and the return gains the final extra.

        ``has_meters``: micro_fn returns one value more, LAST: a tree of
        what the micro-batch's forward pass says of itself
        (``_loss_and_grads(with_meters=True)``), and the return gains them
        last, stacked ``[gas, ...]``: a scan's outputs, no carry."""
        with_extra = extra0 is not None

        if gas == 1:
            squeezed = jax.tree.map(lambda x: x[0], batch)
            loss, grads, *rest = (micro_fn(squeezed, extra0) if with_extra
                                  else micro_fn(squeezed))
            grads = constrain(jax.tree.map(
                lambda g: g.astype(acc_dtype), grads))
            if has_meters:
                rest[-1] = jax.tree.map(lambda m: m[None], rest[-1])
            return (grads, loss, *rest)

        def micro(carry, mb):
            if with_extra:
                acc, extra = carry
                loss, grads, extra, *meters = micro_fn(mb, extra)
            else:
                acc = carry
                loss, grads, *meters = micro_fn(mb)
            with jax.named_scope("grad_accumulate"):
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), acc, grads)
            acc = constrain(acc)
            return ((acc, extra) if with_extra else acc), \
                ((loss, *meters) if has_meters else loss)

        with jax.named_scope("grad_accumulate"):
            zeros = jax.tree.map(
                lambda s: jnp.zeros(s.shape, acc_dtype), like)
        zeros = constrain(zeros)
        carry, losses = jax.lax.scan(
            micro, (zeros, extra0) if with_extra else zeros, batch)
        losses, *meters = losses if has_meters else (losses,)
        loss = jnp.mean(losses)
        if with_extra:
            grads_sum, extra = carry
            return (grads_sum, loss, extra, *meters)
        return (carry, loss, *meters)

    def _train_step_fn(self, gas: int):
        """The raw (unjitted) fused-step body — shared by the single-step
        jit and the multi-step ``lax.scan`` wrapper."""

        acc_dt = self._grad_accum_dtype()
        # a model whose layers meter themselves (a share of an expert
        # layer's rows): the meters leave the step as outputs. Every other
        # model's step is traced as it always was
        metered = self._metered

        def train_step(state, batch):
            scale = state["scaler"].scale if self.fp16_enabled else None

            def micro_fn(mb):
                # chaos train/nan_grads injection (testing/chaos.py): the
                # per-micro `_nan_grads` flag rides the batch dict only when
                # the fault is armed — absent, the traced program is
                # byte-identical to the uninjected step
                flag = None
                if isinstance(mb, dict) and "_nan_grads" in mb:
                    mb = dict(mb)
                    flag = mb.pop("_nan_grads")
                loss, grads, *meters = self._loss_and_grads(
                    state["master"], mb, scale,
                    params_buf=(state.get("gathered")
                                if self._param_buffer else None),
                    with_meters=metered)
                if flag is not None:
                    bad = jnp.where(flag > 0, jnp.nan, 1.0)
                    grads = jax.tree.map(
                        lambda g: g * bad.astype(g.dtype), grads)
                return (loss, grads, *meters)

            grads_sum, mean_loss, *meters = self.accumulate_microbatches(
                micro_fn, self._shapes, acc_dt, batch, gas,
                constrain=self._constrain_grads,
                **({"has_meters": True} if metered else {}))

            grad_scale = jnp.float32(gas) * (scale if scale is not None else 1.0)
            lr_mult = None
            if isinstance(batch, dict) and "lr_scale" in batch:
                lr_mult = jnp.mean(batch["lr_scale"].astype(jnp.float32))
            new_state, metrics = self._apply_update(state, grads_sum,
                                                    grad_scale, lr_mult)
            metrics["loss"] = mean_loss
            if metered:
                # ``[gas, ...]`` each, beside the scalars: ``_after_step``
                # takes them out (``_observe_meters``)
                metrics.update(meters[0])
            return new_state, metrics

        return train_step

    def _in_state_shardings(self) -> Dict[str, Any]:
        """Input-side state shardings: offload_param parks the master in
        pinned host BETWEEN steps, so the step's jit must be told its
        master inputs are host-resident EXPLICITLY — trace-time memory-
        space detection (is_host_resident → in-program H2D streams) only
        sees spaces declared via in_shardings, not ones inferred from
        committed arrays."""
        sh = self._state_shardings()
        if self._offload_param_stream:
            sh = dict(sh, master=self._master_host_shardings())
        return sh

    def _build_train_step(self, gas: int):
        """Fused step: scan grad accumulation over [gas, ...] batch inside jit."""
        state_sh = self._state_shardings()
        # batch shardings are committed on the inputs by _shard_batch; jit honors
        # them without an explicit in_shardings entry.
        # streaming offload: donation would alias the pinned-host master
        # input to the device-resident master output (XLA rejects the
        # cross-memory-kind alias). Cost: the moments lose donation too
        # (state donates whole) — transiently double moment buffers; when
        # that matters, compose with offload_optimizer, whose tier moves
        # them off-device entirely.
        donate = () if self._offload_param_stream else (0,)
        return jax.jit(self._train_step_fn(gas),
                       in_shardings=(self._in_state_shardings(), None),
                       out_shardings=(state_sh, None),
                       donate_argnums=donate)

    def _build_train_multi(self, gas: int, n_steps: int):
        """``n_steps`` fused steps in ONE dispatch: ``lax.scan`` over the
        step body on a [n_steps, gas, ...] batch. On TPU each dispatch pays
        host-side latency (dispatch gaps) — pipelining steps device-side
        removes it. The LR schedule
        advances inside the scan via ``state['step']``."""
        step = self._train_step_fn(gas)

        def multi(state, batches):
            if self._offload_param_stream:
                # the scan carry must keep ONE memory space: stream the
                # pinned-host master onto device before the scan (it stays
                # device-resident for the whole fused window — the between-
                # step host parking only happens at the call boundary)
                from deepspeed_tpu.utils.memory import stream_to_shardings

                state = dict(state, master=stream_to_shardings(
                    state["master"],
                    self.policy.to_shardings(self.master_spec)))
            state, ms = jax.lax.scan(step, state, batches)
            metrics = jax.tree.map(lambda x: x[-1], ms)
            metrics["loss"] = jnp.mean(ms["loss"])
            return state, metrics

        state_sh = self._state_shardings()
        donate = () if self._offload_param_stream else (0,)
        # offload_param_stream parks the master pinned-host and streams
        # slices in-program: the device state is a transient copy the
        # host master outlives, so NOT donating is the deliberate
        # double-buffer there  # dslint: disable=donation
        return jax.jit(multi,
                       in_shardings=(self._in_state_shardings(), None),
                       out_shardings=(state_sh, None),
                       donate_argnums=donate)

    # ------------------------------------------------------------------ #
    # wire-format step builders (ZeRO++ qwZ/qgZ/LoCo, 1-bit transport)
    # ------------------------------------------------------------------ #
    def _manual_batch_spec(self, ndim: int) -> P:
        axes = self._dp_manual_axes
        row = axes if len(axes) > 1 else axes[0]
        return P(None, row, *([None] * (ndim - 2)))

    def _wire_format(self) -> str:
        """The resolved wire format of the fused step — one of ``exact``
        / ``qz`` / ``qz+loco`` / ``onebit``. With the overlap scheduler
        this is the OTHER axis of the step-builder pipeline; the single
        source for builder selection (``_select_step_builder``) and the
        overlap plan's ``wire_format`` field."""
        if self._onebit_wire:
            return "onebit"
        if self._compressed:
            return "qz+loco" if self._compressed.get("loco") else "qz"
        return "exact"

    def _select_step_builder(self, gas: int):
        """ONE selection point of the step-builder pipeline: wire format
        × overlap compose inside each builder rather than forking here.
        Mirrored by the observatory's ``ledger_for_engine`` so the
        ledgered program is always the dispatched program."""
        wire = self._wire_format()
        if wire == "onebit":
            return self._build_train_step_onebit(gas)
        if wire != "exact":
            return self._build_train_step_wire(gas)
        return self._build_train_step(gas)

    def _build_train_step_wire(self, gas: int):
        """ZeRO++ wire-compressed step (qwZ/qgZ, optional LoCo).

        Two formulations share ONE wire protocol
        (``parallel/compressed.py``):

        * **straight-through** — the param gather's ``custom_vjp`` emits
          the per-leaf quantized reduce inside autodiff; lowest memory.
          Used when neither LoCo nor the overlap scheduler needs the
          reduce outside the vjp.
        * **bucketed** — grads w.r.t. the FULL gathered params, reduce
          outside the vjp through ``reduce_bucket_size``-bounded fenced
          buckets; composes with the overlap scheduler and carries the
          LoCo residuals.
        """
        if not self._compressed.get("loco") and not self._overlap.enabled:
            return self._build_train_step_qz(gas)
        return self._build_train_step_bucketed_wire(gas)

    def _build_train_step_bucketed_wire(self, gas: int):
        """The composed wire×overlap step (and the LoCo home; reference
        ``coalesced_collectives.py:31/:81`` + the PR-8 scheduler).

        Grads are taken w.r.t. the FULL gathered params (no collective
        inside autodiff) and the wire reduce runs OUTSIDE the vjp — the
        formulation LoCo already required (its residual must persist
        across reduces), now also the seam where overlap composes:

        * gradient leg: ``compressed.reduce_tree_bucketed`` — per-bucket
          qgZ int8 reduce-scatter, LoCo residual slices riding the SAME
          chained ``optimization_barrier`` fences as the exact path's
          bucketed constraints (residuals stay keyed per leaf, so
          re-bucketing never relayouts LoCo state);
        * parameter leg: ``compressed.chunked_gather_tree_fn`` — the
          qwZ all-gathers follow the layer-chunk plan one fence apart,
          so the chunked scan's next chunk can gather (int8 when qwZ,
          hpZ subgroups riding each leaf's spec) under the current
          chunk's compute;
        * mid-backward sync: the model spec was rebuilt with
          ``overlap.manual_chunk_sync`` (ordering fence — named
          constraints don't exist in a shard_map manual region).

        Memory: a transient full-gradient tree per rank (stage-2-like)
        plus the fp32 residual buffers when LoCo."""
        from jax import shard_map

        from deepspeed_tpu.parallel import compressed as C

        axes = self._dp_manual_axes
        world = self._dp_manual_world
        dtype = jnp.dtype(self.precision)
        mode = self._compressed
        loco = bool(mode.get("loco"))
        sizes = dict(self.mesh.shape)
        overlap_on = self._overlap.enabled
        bucket_elems = self._overlap.reduce_bucket_elems if overlap_on \
            else None
        bounds = (self._overlap_plan.get("chunk_bounds") or []) \
            if overlap_on else []
        buffered = self._param_buffer
        if len(bounds) > 1:
            gather_tree = C.chunked_gather_tree_fn(
                self.master_spec, axes, world, dtype,
                quant_weights=mode["quant_weights"], chunk_bounds=bounds,
                axis_sizes=sizes)
        else:
            gather_tree = C.gather_tree_fn(
                self.master_spec, axes, world, dtype,
                quant_weights=mode["quant_weights"], quant_grads=False,
                axis_sizes=sizes)  # bwd unused: grads w.r.t. FULL params
        master_manual = jax.tree.map(
            lambda s: C.manual_spec(s, axes), self.master_spec,
            is_leaf=lambda x: isinstance(x, P))
        rep_specs = jax.tree.map(lambda s: P(), self.master_spec,
                                 is_leaf=lambda x: isinstance(x, P))
        row = axes if len(axes) > 1 else axes[0]

        acc_dt = self._grad_accum_dtype()

        def core(master_local, err0, batch_local, scale,
                 params_full=None):
            # loop-invariant: ONE (possibly quantized, possibly chunk-
            # fenced) param gather per step, not per micro — and with
            # the double buffer (overlap_step) ZERO: the forward
            # consumes the params the PREVIOUS step's update phase
            # published (bit-equal: the publish runs the same wire on
            # the same master), moving the gather off this step's
            # critical path entirely
            params = params_full if params_full is not None \
                else gather_tree(master_local)

            def full_loss(pf, b):
                return self.model_spec.loss_fn(pf, b) * scale

            if loco:
                def micro(b, err):
                    loss, gfull = jax.value_and_grad(full_loss)(params, b)
                    gl, err = C.reduce_tree_bucketed(
                        gfull, self.master_spec, axes, world, sizes,
                        bucket_elems=bucket_elems, err_tree=err)
                    return loss, gl, err

                grads_sum, losses_mean, err = self.accumulate_microbatches(
                    micro, master_local, acc_dt, batch_local, gas,
                    extra0=err0)
            else:
                def micro(b):
                    loss, gfull = jax.value_and_grad(full_loss)(params, b)
                    # quant_grads honored: a qwZ-only config buckets
                    # EXACT gradient reduces, same as the straight-
                    # through path's quant_grads=False backward
                    gl, _ = C.reduce_tree_bucketed(
                        gfull, self.master_spec, axes, world, sizes,
                        bucket_elems=bucket_elems,
                        quant_grads=mode["quant_grads"])
                    return loss, gl

                grads_sum, losses_mean = self.accumulate_microbatches(
                    micro, master_local, acc_dt, batch_local, gas)
                err = None
            mean_loss = jax.lax.pmean(losses_mean, axes) / scale
            return grads_sum, err, mean_loss

        def local_loco(master_local, err_local, batch_local, scale,
                       *buf):
            err0 = jax.tree.map(lambda e: e[0], err_local)   # drop world row
            grads_sum, err, mean_loss = core(master_local, err0,
                                             batch_local, scale,
                                             buf[0] if buf else None)
            err_out = jax.tree.map(lambda e: e[None], err)
            return grads_sum, err_out, mean_loss

        def local_plain(master_local, batch_local, scale, *buf):
            grads_sum, _, mean_loss = core(master_local, None,
                                           batch_local, scale,
                                           buf[0] if buf else None)
            return grads_sum, mean_loss

        def train_step(state, batch):
            scale = state["scaler"].scale if self.fp16_enabled \
                else jnp.float32(1.0)
            b_specs = jax.tree.map(
                lambda x: self._manual_batch_spec(x.ndim), batch)
            buf_in = (rep_specs,) if buffered else ()
            buf_arg = (state["gathered"],) if buffered else ()
            if loco:
                err_specs = jax.tree.map(
                    lambda s: P(row, *([None] * len(s.shape))), self._shapes)
                fn = shard_map(
                    local_loco, mesh=self.mesh,
                    in_specs=(master_manual, err_specs, b_specs, P())
                    + buf_in,
                    out_specs=(master_manual, err_specs, P()),
                    axis_names=set(axes), check_vma=False)
                grads_sum, new_err, mean_loss = fn(
                    state["master"], state["loco_err"], batch, scale,
                    *buf_arg)
            else:
                fn = shard_map(
                    local_plain, mesh=self.mesh,
                    in_specs=(master_manual, b_specs, P()) + buf_in,
                    out_specs=(master_manual, P()),
                    axis_names=set(axes), check_vma=False)
                grads_sum, mean_loss = fn(state["master"], batch, scale,
                                          *buf_arg)
                new_err = None
            grad_scale = jnp.float32(gas) * scale
            new_state, metrics = self._apply_update(state, grads_sum,
                                                    grad_scale)
            if loco:
                # fp16 overflow: _apply_update skips the weight update, and
                # the residuals computed from inf/NaN gradients must not
                # poison the persistent state — reset them so recovery
                # matches plain qgZ
                overflow = metrics["overflow"] > 0
                new_state["loco_err"] = jax.tree.map(
                    lambda n: jnp.where(overflow, jnp.zeros_like(n), n),
                    new_err)
            metrics["loss"] = mean_loss
            return new_state, metrics

        state_sh = self._state_shardings()
        return jax.jit(train_step, out_shardings=(state_sh, None),
                       donate_argnums=(0,))

    def _build_train_step_qz(self, gas: int):
        """ZeRO++ qwZ/qgZ straight-through step: shard_map manual over the
        ZeRO axes; the parameter all-gather (fwd) and gradient
        reduce-scatter (bwd) are one straight-through primitive with an
        int8 wire format (``parallel/compressed.py``). The overlap-
        composed / LoCo variants route through
        ``_build_train_step_bucketed_wire`` instead (see
        ``_build_train_step_wire``)."""
        from jax import shard_map

        from deepspeed_tpu.parallel import compressed as C

        axes = self._dp_manual_axes
        world = self._dp_manual_world
        dtype = jnp.dtype(self.precision)
        mode = self._compressed
        gather_tree = C.gather_tree_fn(
            self.master_spec, axes, world, dtype,
            quant_weights=mode["quant_weights"],
            quant_grads=mode["quant_grads"],
            axis_sizes=dict(self.mesh.shape))
        master_manual = jax.tree.map(
            lambda s: C.manual_spec(s, axes), self.master_spec,
            is_leaf=lambda x: isinstance(x, P))

        acc_dt = self._grad_accum_dtype()

        def local(master_local, batch_local, scale):
            def scaled_loss(ml, b):
                params = gather_tree(ml)
                loss = self.model_spec.loss_fn(params, b)
                return loss * scale

            grads_sum, losses_mean = self.accumulate_microbatches(
                lambda b: jax.value_and_grad(scaled_loss)(master_local, b),
                master_local, acc_dt, batch_local, gas)
            mean_loss = jax.lax.pmean(losses_mean, axes) / scale
            return grads_sum, mean_loss

        def train_step(state, batch):
            scale = state["scaler"].scale if self.fp16_enabled \
                else jnp.float32(1.0)
            b_specs = jax.tree.map(
                lambda x: self._manual_batch_spec(x.ndim), batch)
            fn = shard_map(
                local, mesh=self.mesh,
                in_specs=(master_manual, b_specs, P()),
                out_specs=(master_manual, P()),
                axis_names=set(axes), check_vma=False)
            grads_sum, mean_loss = fn(state["master"], batch, scale)
            grad_scale = jnp.float32(gas) * scale
            new_state, metrics = self._apply_update(state, grads_sum, grad_scale)
            metrics["loss"] = mean_loss
            return new_state, metrics

        state_sh = self._state_shardings()
        return jax.jit(train_step, out_shardings=(state_sh, None),
                       donate_argnums=(0,))

    def _build_train_step_onebit(self, gas: int):
        """1-bit optimizer step with wire transport: the WHOLE step (grads +
        optimizer) runs shard_map-manual over the DP axes. Warmup steps
        exact-allreduce gradients; frozen steps skip the gradient reduction
        entirely and exchange packed-sign compressed momentum inside the
        optimizer update (reference ``runtime/fp16/onebit/adam.py`` +
        ``runtime/comm/nccl.py:52``)."""
        from jax import shard_map

        from deepspeed_tpu.parallel import compressed as C

        axes = self._dp_manual_axes
        world = self._dp_manual_world
        freeze = max(getattr(self.optimizer, "freeze_step", 0) or
                     getattr(self.optimizer, "var_freeze_step", 0), 1)
        block = 2048

        def transport(m_new, err):
            from deepspeed_tpu.ops.quantization import pad_to_block

            n = m_new.size
            fp, _ = pad_to_block(m_new.reshape(-1).astype(jnp.float32), block)
            ep, _ = pad_to_block(err.reshape(-1).astype(jnp.float32), block)
            reduced, new_err = C.packed_sign_allreduce(fp, ep, axes, world,
                                                      block)
            return (reduced[:n].reshape(m_new.shape),
                    new_err[:n].reshape(err.shape))

        self.optimizer.transport = transport

        def local(state_local, batch_local):
            opt = dict(state_local["opt"])
            opt["worker_error"] = jax.tree.map(
                lambda e: e[0], opt["worker_error"])
            st = dict(state_local, opt=opt)
            scale = st["scaler"].scale if self.fp16_enabled else None
            dtype = jnp.dtype(self.precision)

            def micro(b):
                def wrt_master(m):
                    p = jax.tree.map(lambda x: x.astype(dtype), m)
                    loss = self.model_spec.loss_fn(p, b)
                    return loss * scale if scale is not None else loss

                return jax.value_and_grad(wrt_master)(st["master"])

            # fp32 whatever data_types.grad_accum_dtype says: the warmup's
            # exact pmean reads this sum
            grads_sum, losses_mean = self.accumulate_microbatches(
                micro, st["master"], jnp.float32, batch_local, gas)

            # warmup: exact grad allreduce (identical ranks feed identical
            # momentum). frozen: gradients stay LOCAL — only the compressed
            # momentum crosses the wire (inside optimizer.update).
            frozen = st["step"] >= freeze
            grads_sum = jax.lax.cond(
                frozen, lambda g: g,
                lambda g: jax.tree.map(lambda x: jax.lax.pmean(x, axes), g),
                grads_sum)

            grad_scale = jnp.float32(gas) * (scale if scale is not None
                                             else 1.0)
            new_state, metrics = self._apply_update(st, grads_sum, grad_scale)
            new_state["opt"]["worker_error"] = jax.tree.map(
                lambda e: e[None], new_state["opt"]["worker_error"])
            metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
            metrics["loss"] = jax.lax.pmean(losses_mean, axes)
            if scale is not None:
                metrics["loss"] = metrics["loss"] / new_state["scaler"].scale
            return new_state, metrics

        row = axes if len(axes) > 1 else axes[0]
        rep = P()

        def state_specs(state):
            sp = jax.tree.map(lambda _: rep, state)
            sp["opt"]["worker_error"] = jax.tree.map(
                lambda _: P(row), state["opt"]["worker_error"])
            return sp

        def train_step(state, batch):
            b_specs = jax.tree.map(
                lambda x: self._manual_batch_spec(x.ndim), batch)
            fn = shard_map(
                local, mesh=self.mesh,
                in_specs=(state_specs(state), b_specs),
                out_specs=(state_specs(state), rep),
                axis_names=set(axes), check_vma=False)
            return fn(state, batch)

        state_sh = self._state_shardings()
        return jax.jit(train_step, out_shardings=(state_sh, None),
                       donate_argnums=(0,))

    def _batch_shardings(self, leading: int = 0):
        """``leading`` counts unsharded leading dims (1 = [gas, ...],
        2 = [n_steps, gas, ...] for the fused multi-step path)."""
        n = int(leading)

        def spec_for(ndim: int) -> NamedSharding:
            if n:
                inner = self.policy.batch_spec(ndim - n)
                return NamedSharding(self.mesh, P(*([None] * n), *inner))
            return NamedSharding(self.mesh, self.policy.batch_spec(ndim))

        return spec_for

    def _shard_batch(self, batch: PyTree, leading: int = 0) -> PyTree:
        spec_for = self._batch_shardings(leading)
        rep = NamedSharding(self.mesh, P())

        def one(path, x):
            x = np.asarray(x)
            # underscore keys (engine-injected controls: PLD masks, LTD
            # indices, lr_scale) and scalars are replicated, not batch-sharded
            keys = [getattr(p, "key", None) for p in path]
            if x.ndim == 0 or any(isinstance(k, str) and k.startswith("_")
                                  for k in keys) or "lr_scale" in keys:
                if leading and x.ndim > 0:
                    return shard_host_batch(
                        x, NamedSharding(self.mesh,
                                         P(*([None] * x.ndim))))
                return shard_host_batch(x, rep)
            return shard_host_batch(x, spec_for(x.ndim))

        return jax.tree_util.tree_map_with_path(one, batch)

    # ------------------------------------------------------------------ #
    # public batch-size queries (reference engine API)
    # ------------------------------------------------------------------ #
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def get_lr(self) -> List[float]:
        if self.lr_scheduler is not None:
            return [float(self.lr_scheduler.lr_at(jnp.asarray(self.global_steps)))]
        return [self.optimizer.lr]

    def get_global_grad_norm(self) -> Optional[float]:
        if "grad_norm" not in self._last_metrics_dev:
            return None
        return float(jax.device_get(self._last_metrics_dev["grad_norm"]))

    @property
    def skipped_steps(self) -> int:
        """Exact count of skipped optimizer steps (device-side counter):
        fp16 overflow skips, plus bf16/fp32 non-finite skips under
        ``guardian.nonfinite_guard``."""
        if "skips" not in self.state:
            return 0
        return int(jax.device_get(self.state["skips"]))

    @property
    def loss_scale(self) -> float:
        if not self.fp16_enabled:
            return 1.0
        return float(jax.device_get(self.state["scaler"].scale))

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._micro_in_window == 0

    def _opt_swap(self, direction: str) -> None:
        """Move optimizer moments host↔device around the step ('in'/'out')."""
        opt_sh = self._state_shardings()["opt"]
        target = self._to_host_shardings(opt_sh) if direction == "out" else opt_sh
        self.state["opt"] = jax.device_put(self.state["opt"], target)

    def _nvme_swapper(self):
        """Lazy NVMe optimizer-state swapper (reference
        ``swap_tensor/partitioned_optimizer_swapper.py:27``; config path
        ``offload_optimizer.device == "nvme"``)."""
        if self._opt_swapper is None:
            from deepspeed_tpu.runtime.swap_tensor import OptimizerSwapper

            self._opt_swapper = OptimizerSwapper(self)
            log_dist("NVMe optimizer offload active: "
                     f"{self._opt_swapper.swapper.swap_dir}")
        return self._opt_swapper

    def _param_nvme_swapper(self):
        """Lazy NVMe parameter swapper (reference
        ``swap_tensor/partitioned_param_swapper.py:37``; config path
        ``offload_param.device == "nvme"`` at stage 3)."""
        if self._param_swapper is None:
            from deepspeed_tpu.runtime.swap_tensor import ParamSwapper

            self._param_swapper = ParamSwapper(self)
            log_dist("NVMe parameter offload active: "
                     f"{self._param_swapper.swapper.swap_dir}")
        return self._param_swapper

    # ------------------------------------------------------------------ #
    # offload_states / reload_states (reference engine.py:5573/:5603)
    # ------------------------------------------------------------------ #
    def offload_states(self, include: Optional[List[str]] = None,
                       device: str = "cpu") -> None:
        """Move engine state tiers to host memory on demand.

        ``include`` ⊆ {'optim_states', 'hp_params'}; None = both."""
        if device != "cpu":
            raise ValueError("offload_states supports device='cpu' (host memory);"
                             " use OptimizerSwapper for the NVMe tier")
        include = include or ["optim_states", "hp_params"]
        sh = self._state_shardings()
        if "optim_states" in include:
            self.state["opt"] = jax.device_put(
                self.state["opt"], self._to_host_shardings(sh["opt"]))
        if "hp_params" in include:
            self.state["master"] = jax.device_put(
                self.state["master"], self._to_host_shardings(sh["master"]))

    def reload_states(self) -> None:
        sh = self._state_shardings()
        self.state["opt"] = jax.device_put(self.state["opt"], sh["opt"])
        self.state["master"] = jax.device_put(self.state["master"], sh["master"])

    # ------------------------------------------------------------------ #
    # fused train path
    # ------------------------------------------------------------------ #
    @staticmethod
    def _stack_micros(micros: list) -> PyTree:
        def stack(*xs):
            arrs = [np.asarray(x) for x in xs]
            if len({a.shape for a in arrs}) > 1:
                raise ValueError(
                    "micro-batches in one accumulation window have different "
                    f"shapes {[a.shape for a in arrs]} — variable/token-"
                    "budget batching requires gradient_accumulation_steps=1")
            return np.stack(arrs)

        return jax.tree.map(stack, *micros)

    def train_batch(self, data_iter: Iterator[PyTree]) -> jax.Array:
        """Pull GAS micro-batches, run the fused jitted step. Returns mean loss."""
        gas = self.gradient_accumulation_steps()
        # the caller's iterator, not the engine: its own span, so that a
        # slow input pipeline is not read as a slow step
        with self._train_span("train_batch_fetch"):
            micros = [next(data_iter) for _ in range(gas)]
        with self._train_span("train_batch_input"):
            stacked = self._stack_micros(micros)
            stacked = self._inject_data_efficiency(stacked, gas)
        return self._dispatch_train_step(stacked, gas)

    def _maybe_inject_nan_grads(self, stacked: PyTree, gas: int) -> PyTree:
        """``train/nan_grads`` chaos injection point: when the armed fault
        window covers this step, ride a per-micro poison flag into the
        batch dict — the jitted step multiplies every gradient leaf by NaN
        (``_train_step_fn``), which is exactly the shape of a real
        non-finite backward. Unarmed cost: one global-is-None check."""
        from deepspeed_tpu.testing.chaos import chaos_should_fire

        if self._wire_format() != "exact" or self._host_runner is not None \
                or not isinstance(stacked, dict):
            # only the exact-wire fused builders strip the poison flag
            # before the model's loss_fn — for wire-compressed / 1-bit /
            # host-step builders the key would leak into the model batch
            # (or silently never poison), and a NON-DICT batch can't
            # carry the flag without changing the pytree the model sees.
            # The point stays unarmed on those paths.
            return stacked
        if not chaos_should_fire("train/nan_grads"):
            return stacked
        stacked = dict(stacked)
        stacked["_nan_grads"] = np.ones((gas,), np.float32)
        logger.warning("chaos: train/nan_grads poisoning the gradients of "
                       f"step {self.global_steps + 1}")
        return stacked

    def _dispatch_train_step(self, stacked: PyTree, gas: int) -> jax.Array:
        """Run ONE fused step on an already-stacked [gas, ...] window."""
        from deepspeed_tpu import telemetry

        stacked = self._maybe_inject_nan_grads(stacked, gas)
        if self._host_runner is None:
            key = ("train_step", gas)
            if key not in self._compiled:
                self._compiled[key] = self._select_step_builder(gas)
            step_fn = self._compiled[key]

        # the batch's host-to-device copy (a second ``train_batch_input``
        # of the step: the first stacked the micro-batches)
        with self._train_span("train_batch_input"):
            batch = self._shard_batch(stacked, leading=True)
        if self.config.wall_clock_breakdown:
            self.timers(TRAIN_BATCH_TIMER).start()
        self.tput_timer.start()
        t0 = time.perf_counter()
        self._in_step = True   # a preemption signal now defers to the
        try:                   # boundary check below
            with self._train_span("train_step") as step_span:
                chaos_point("train/step")
                compiled_s = -telemetry.compile_seconds()
                if self._host_runner is not None:
                    # SuperOffload/ZenFlow host-executed update (runtime/host_step.py)
                    _, metrics = self._host_runner.train_batch(batch, gas)
                else:
                    if self._offload_opt:
                        self._opt_swap("in")
                    if self._offload_nvme:
                        self._nvme_swapper().swap_in_optimizer()
                    if self._offload_param_nvme:
                        self._param_nvme_swapper().swap_in_params()
                    self._ensure_master_tier_for_step()
                    with self.mesh:
                        self.state, metrics = step_fn(self.state, batch)
                    if self._offload_opt:
                        self._opt_swap("out")
                    if self._offload_nvme:
                        self._nvme_swapper().swap_out_optimizer()
                    if self._offload_param:
                        self._park_master()
                    if self._offload_param_nvme:
                        self._param_nvme_swapper().swap_out_params()
                # a step that traced, lowered or compiled anything says so
                # in its own span (the flight recorder's record)
                compiled_s += telemetry.compile_seconds()
                if compiled_s > 0 and step_span is not None:
                    step_span.note(compile_s=compiled_s)
            self.global_steps += 1
            self.micro_steps += gas
            self._after_step(metrics, wall_s=time.perf_counter() - t0,
                             tokens=self._count_tokens(stacked)
                             if self._tm is not None else 0)
            if self.config.wall_clock_breakdown:
                self.timers(TRAIN_BATCH_TIMER).stop()
                self.timers.log([TRAIN_BATCH_TIMER])
        except Exception:
            # crash context for an unhandled step failure: the flight
            # recorder's last N spans ARE the timeline that led here
            # (no-op unless telemetry.tracing is on); then re-raise
            self._dump_step_crash_context()
            raise
        finally:
            # even a raising step must re-enable immediate preemption
            # handling (a deferred SIGTERM would otherwise wait forever)
            self._in_step = False
        self._check_preemption_boundary()
        return metrics["loss"]

    def train_batches(self, data_iter: Iterator[PyTree],
                      n_steps: int) -> jax.Array:
        """Run ``n_steps`` optimizer steps in ONE device dispatch.

        A TPU dispatch pays fixed host latency (Python + runtime transport)
        regardless of step cost —
        ``lax.scan`` over the fused step amortizes it to once per call.
        Beyond the reference engine API (its ``train_batch`` is per-step);
        falls back to a per-step loop for variants with host-side phases
        (host-runner, 1-bit wire, compressed collectives, offload swappers).
        Returns the mean loss over the ``n_steps`` steps.
        """
        if n_steps <= 1:
            return self.train_batch(data_iter)
        if (self._host_runner is not None or self._onebit_wire
                or self._compressed or self._offload_opt
                or self._offload_nvme or self._offload_param_nvme
                or self._ltd is not None
                or self._pld is not None or self._curriculum is not None):
            # host-side per-step phases (or step-indexed host schedules):
            # the per-step path keeps their semantics exact
            losses = [self.train_batch(data_iter) for _ in range(n_steps)]
            return jnp.mean(jnp.stack(losses))  # same mean-loss contract
        gas = self.gradient_accumulation_steps()
        steps = []
        for _ in range(n_steps):
            stacked = self._stack_micros(
                [next(data_iter) for _ in range(gas)])
            steps.append(self._inject_data_efficiency(stacked, gas))
        try:
            big = jax.tree.map(lambda *xs: np.stack(xs), *steps)
        except ValueError:
            # variable shapes across steps (token-budget batching at gas=1):
            # run the already-built windows through the per-step path
            losses = [self._dispatch_train_step(s, gas) for s in steps]
            return jnp.mean(jnp.stack(losses))
        key = ("train_multi", gas, n_steps)
        if key not in self._compiled:
            self._compiled[key] = self._build_train_multi(gas, n_steps)
        batch = self._shard_batch(big, leading=2)
        self.tput_timer.start()
        t0 = time.perf_counter()
        self._in_step = True
        try:
            with self._train_span("train_window"):
                chaos_point("train/step")
                self._ensure_master_tier_for_step()
                with self.mesh:
                    self.state, metrics = self._compiled[key](self.state, batch)
                if self._offload_param:
                    self._park_master()
            self.global_steps += n_steps
            self.micro_steps += gas * n_steps
            self._after_step(metrics, n_steps=n_steps,
                             wall_s=time.perf_counter() - t0,
                             tokens=self._count_tokens(big)
                             if self._tm is not None else 0)
        except Exception:
            self._dump_step_crash_context()   # then re-raise unchanged
            raise
        finally:
            self._in_step = False
        self._check_preemption_boundary()
        return metrics["loss"]

    def _record_moe_drops(self, frac) -> None:
        """Async jax.debug.callback sink (moe.layer.set_drop_monitor) — keeps
        the worst dropped-choice fraction seen since the last print window."""
        self._moe_drop_frac = max(self._moe_drop_frac, float(frac))

    def _dump_step_crash_context(self) -> None:
        """Flight-recorder dump for an unhandled train-step exception
        (no-op unless ``telemetry.tracing`` is on). Must never raise —
        it runs on the exception path it exists to explain."""
        try:
            from deepspeed_tpu.telemetry import tracing

            tracing.get_tracer().dump_flight(
                "engine_step_exception", note=f"step={self.global_steps}")
        except Exception as e:   # the original exception must win
            logger.warning(f"flight dump on step failure failed too: {e}")

    def _train_span(self, name: str):
        """telemetry.span when enabled, with the step it belongs to as an
        attribute; inert otherwise."""
        if self._tm is None:
            import contextlib

            return contextlib.nullcontext()
        from deepspeed_tpu import telemetry

        return telemetry.span(name, attrs={"step": self.global_steps + 1})

    def _after_step(self, metrics: Dict[str, jax.Array],
                    n_steps: int = 1, wall_s: Optional[float] = None,
                    tokens: int = 0) -> None:
        self.tput_timer.stop(global_step=True, steps=n_steps)
        if self._metered:
            # the arrays among the scalars (``_train_step_fn``)
            self._observe_meters({k: metrics.pop(k) for k in ("moe_held",)
                                  if k in metrics})
        self._last_metrics_dev = metrics  # lazy: no host sync off the print path
        if self._tm is not None:
            self._tm_steps.inc(n_steps)
            if tokens:
                self._tm_tokens.inc(tokens)
            if wall_s is not None:
                # amortize a fused window over its steps so the histogram
                # stays per-step comparable across dispatch modes
                self._tm_step_hist.observe(wall_s / n_steps, n=n_steps)
            # exported unix timestamp (train_heartbeat_timestamp_seconds is
            # compared against scrape-side wall clocks, not used as an
            # interval here)  # dslint: disable=wall-clock
            self._tm_heartbeat.set(time.time())
            if self._watchdog is not None:
                self._watchdog.beat()
            # the process's CPU seconds and context switches, as a serving
            # tick refreshes them (telemetry/host.py)
            from deepspeed_tpu import telemetry

            telemetry.refresh_host_counters()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step(self.global_steps)
        if self.global_steps % max(1, self.config.steps_per_print) == 0:
            host = {k: float(jax.device_get(v)) for k, v in metrics.items()}
            if self._guardian is not None:
                # host-side numerics sentinel: the guardian's anomaly
                # detector rides THIS device_get — the one the log cadence
                # already pays — so detection adds zero hot-path syncs
                self._guardian.observe(self.global_steps, host)
            if self._moe_drop_frac > 0:
                logger.warning(
                    f"MoE expert-parallel dispatch dropped "
                    f"{self._moe_drop_frac:.2%} of token-choices (EP buffer "
                    "overflow — router skew); dropped choices fall through "
                    "to the residual. Consider a larger capacity headroom "
                    "or rebalancing (aux loss weight).")
                host["moe_drop_frac"] = self._moe_drop_frac
                self._moe_drop_frac = 0.0
            log_dist(
                f"step={self.global_steps} loss={host.get('loss', float('nan')):.4f} "
                f"lr={host.get('lr', 0):.3e} grad_norm={host.get('grad_norm', 0):.3f}"
                + (f" loss_scale={host.get('loss_scale', 0):.0f}" if self.fp16_enabled else ""))
            # (train_loss/grad_norm/... gauges are set by the registry
            # collector from _last_metrics_dev on every read path — no
            # duplicate update here)
            if self.monitor is not None and self.monitor.enabled:
                events = [(f"Train/{k}", v, self.global_steps) for k, v in host.items()]
                self.monitor.write_events(events)
            if self._tm is not None and self.config.telemetry.monitor_bridge \
                    and self.monitor is not None and self.monitor.enabled:
                if self._tm_bridge is None:
                    from deepspeed_tpu import telemetry

                    self._tm_bridge = telemetry.MonitorBridge(
                        self.monitor, self._tm)
                self._tm_bridge.publish(self.global_steps)

    # ------------------------------------------------------------------ #
    # eager forward/backward/step (API parity path)
    # ------------------------------------------------------------------ #
    def forward(self, batch: PyTree) -> jax.Array:
        """Compute loss (and cache grads) for one micro-batch."""
        if self._onebit_wire:
            raise NotImplementedError(
                "the eager forward()/backward()/step() path is unavailable "
                "with 1-bit wire transport (per-rank error buffers live "
                "inside the fused step's shard_map) — use train_batch()")
        if self._offload_nvme:
            raise NotImplementedError(
                "the eager forward()/backward()/step() path is unavailable "
                "with offload_optimizer.device='nvme' (moments are swapped "
                "around the fused step) — use train_batch()")
        if self._host_runner is not None:
            raise NotImplementedError(
                "the eager forward()/backward()/step() path is unavailable "
                "with offload_optimizer.host_step — use train_batch()")
        self._materialize_master()
        if "fwd_bwd" not in self._compiled:
            def fwd_bwd(state, b):
                scale = state["scaler"].scale if self.fp16_enabled else None
                # the eager path consumes the double buffer too — its
                # step() republishes after every update, so the publish
                # is never wasted work on this path either
                return self._loss_and_grads(
                    state["master"], b, scale,
                    params_buf=(state.get("gathered")
                                if self._param_buffer else None))

            # state is READ-ONLY here (returns loss+grads; the eager
            # path's apply() owns the state donation); donating would
            # invalidate self.state mid-window  # dslint: disable=donation
            self._compiled["fwd_bwd"] = jax.jit(fwd_bwd)
        batch = self._shard_batch(batch)
        if self.config.wall_clock_breakdown:
            self.timers(FORWARD_GLOBAL_TIMER).start()
        with self.mesh:
            loss, grads = self._compiled["fwd_bwd"](self.state, batch)
        if self.config.wall_clock_breakdown:
            self.timers(FORWARD_GLOBAL_TIMER).stop()
        self._pending_grads = grads
        return loss

    def backward(self, loss: jax.Array = None) -> None:
        """Accumulate the cached grads (autograd already ran fused in forward)."""
        if self._pending_grads is None:
            raise RuntimeError("backward() called before forward()")
        if self.config.wall_clock_breakdown:
            self.timers(BACKWARD_GLOBAL_TIMER).start()
        if self._grad_buffer is None:
            self._grad_buffer = self._pending_grads
        else:
            if "grad_add" not in self._compiled:
                self._compiled["grad_add"] = jax.jit(
                    lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
            with self.mesh:
                self._grad_buffer = self._compiled["grad_add"](
                    self._grad_buffer, self._pending_grads)
        self._pending_grads = None
        self.micro_steps += 1
        self._micro_in_window = (self._micro_in_window + 1) % \
            self.gradient_accumulation_steps()
        if self.config.wall_clock_breakdown:
            self.timers(BACKWARD_GLOBAL_TIMER).stop()

    def step(self) -> None:
        """Apply the optimizer at the GAS boundary (no-op otherwise)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._grad_buffer is None:
            raise RuntimeError("step() called with no accumulated gradients")
        gas = self.gradient_accumulation_steps()
        if "apply" not in self._compiled:
            state_sh = self._state_shardings()

            def apply(state, grads):
                scale = state["scaler"].scale if self.fp16_enabled else jnp.float32(1.0)
                return self._apply_update(state, grads, jnp.float32(gas) * scale)

            self._compiled["apply"] = jax.jit(
                apply, out_shardings=(state_sh, None), donate_argnums=(0, 1))
        if self.config.wall_clock_breakdown:
            self.timers(STEP_GLOBAL_TIMER).start()
        self._in_step = True   # preemption defers to the boundary check
        try:
            if self._offload_opt:
                self._opt_swap("in")
            self._materialize_master()
            with self.mesh:
                self.state, metrics = self._compiled["apply"](self.state, self._grad_buffer)
            if self._offload_opt:
                self._opt_swap("out")
            if self._offload_param:
                self._park_master()
            if self._offload_param_nvme:
                self._param_nvme_swapper().swap_out_params()
            self._grad_buffer = None
            self.global_steps += 1
            self._after_step(metrics)
            if self.config.wall_clock_breakdown:
                self.timers(STEP_GLOBAL_TIMER).stop()
                self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                                 STEP_GLOBAL_TIMER])
        finally:
            self._in_step = False
        self._check_preemption_boundary()

    def eval_batch(self, batch: PyTree) -> jax.Array:
        if self._host_runner is not None:
            # host-step mode: evaluate on the device 16-bit params
            self._host_runner._apply_pending()
            if "eval" not in self._compiled:
                self._compiled["eval"] = jax.jit(self.model_spec.loss_fn)
            batch = self._shard_batch(batch)
            with self.mesh:
                return self._compiled["eval"](
                    self._host_runner.device_params, batch)
        self._materialize_master()
        if "eval" not in self._compiled:
            def ev(state, b):
                params = self._compute_params(state["master"])
                return self.model_spec.loss_fn(params, b)

            # eval reads state and returns a scalar loss — donating
            # would destroy the live train state  # dslint: disable=donation
            self._compiled["eval"] = jax.jit(ev)
        batch = self._shard_batch(batch)
        with self.mesh:
            return self._compiled["eval"](self.state, batch)

    def predict(self, batch: PyTree):
        """Model outputs (logits) — the reference's module __call__ analog."""
        if self.model_spec.apply_fn is None:
            raise ValueError("model spec has no apply_fn")
        if self._host_runner is not None:
            self._host_runner._apply_pending()
            if "predict" not in self._compiled:
                self._compiled["predict"] = jax.jit(self.model_spec.apply_fn)
            batch = self._shard_batch(batch)
            with self.mesh:
                return self._compiled["predict"](
                    self._host_runner.device_params, batch)
        self._materialize_master()
        if "predict" not in self._compiled:
            def pr(state, b):
                params = self._compute_params(state["master"])
                return self.model_spec.apply_fn(params, b)

            # predict reads state and returns logits — donating would
            # destroy the live train state  # dslint: disable=donation
            self._compiled["predict"] = jax.jit(pr)
        batch = self._shard_batch(batch)
        with self.mesh:
            return self._compiled["predict"](self.state, batch)

    # ------------------------------------------------------------------ #
    # dataloader
    # ------------------------------------------------------------------ #
    def deepspeed_io(self, source, repeat: bool = True) -> Iterator[PyTree]:
        """Wrap a host numpy batch source (reference ``deepspeed_io`` engine.py:2486).

        Re-iterable sources are wrapped in RepeatingLoader when ``repeat``;
        one-shot iterators/generators pass through unchanged (make them infinite
        if you need repetition). With ``curriculum_learning`` enabled in the
        config, batches are difficulty-truncated per step (reference
        ``data_pipeline/data_sampling/curriculum_scheduler.py``). With
        ``data_efficiency.data_sampling.dynamic_batching`` enabled, ``source``
        must be a SEQUENCE OF SAMPLES (variable-length 1-D token arrays) and
        is regrouped into token-budget batches with per-batch LR scaling
        (reference ``variable_batch_size_and_lr.py``; requires gas=1)."""
        de = self.config.data_efficiency
        dyn = de.data_sampling.dynamic_batching
        if dyn.enabled and de.enabled and de.data_sampling.enabled:
            from deepspeed_tpu.runtime.data_pipeline.variable_batch import (
                variable_batch_dataloader,
            )

            samples = list(source)
            if not samples or np.asarray(samples[0]).ndim != 1:
                raise ValueError(
                    "dynamic_batching needs a sequence of 1-D token samples")
            if self.gradient_accumulation_steps() != 1:
                raise ValueError("dynamic_batching requires "
                                 "gradient_accumulation_steps=1")
            return variable_batch_dataloader(
                samples, max_tokens=dyn.max_tokens,
                base_batch_size=self.train_micro_batch_size(),
                lr_scaling_method=dyn.lr_scaling_method,
                min_batch_size=dyn.min_batch_size,
                max_batch_size=dyn.max_batch_size,
                order=dyn.sentence_picking_order,
                seed=de.seed, batch_multiple=self.dp_world_size,
                loop=repeat)
        elif dyn.enabled:
            logger.warning(
                "dynamic_batching.enabled is set but data_efficiency.enabled "
                "/ data_sampling.enabled are not — dynamic batching stays OFF")
        loader = source
        if repeat and hasattr(source, "__iter__") and iter(source) is not source:
            loader = RepeatingLoader(source)
        it = iter(loader)
        if self._curriculum is not None:
            from deepspeed_tpu.runtime.data_pipeline import (
                curriculum_dataloader,
            )

            it = curriculum_dataloader(it, self._curriculum,
                                       lambda: self.global_steps)
        return it

    # ------------------------------------------------------------------ #
    # fault tolerance: preemption handling + emergency checkpoints
    # (config "fault_tolerance"; README "Fault tolerance")
    # ------------------------------------------------------------------ #
    def enable_preemption_handler(self, signals=None) -> bool:
        """Install the graceful-preemption signal handler (SIGTERM by
        default — what GCE/GKE send a preempted VM). On delivery the
        engine drains any in-flight async save, writes an emergency
        checkpoint, and exits 0; a signal landing mid-step defers to the
        step boundary (interrupting a dispatched XLA program to do I/O
        from the handler frame is not safe). Returns False off the main
        thread (signal.signal would raise there)."""
        import signal

        signals = signals or (signal.SIGTERM,)
        try:
            for s in signals:
                self._prev_sig_handlers[s] = signal.signal(
                    s, self._on_preempt_signal)
        except ValueError:   # not the main thread
            logger.warning("preemption handler not installed (not on the "
                           "main thread)")
            return False
        log_dist(f"graceful-preemption handler armed for "
                 f"{[signal.Signals(s).name for s in signals]}")
        return True

    def _on_preempt_signal(self, signum, frame) -> None:
        self._preempt_requested = True
        busy = self._in_step or self._saving or self._guard_busy
        logger.warning(
            f"received signal {signum}: preemption imminent — will drain "
            "saves, write an emergency checkpoint, and exit cleanly"
            + (" (deferred to the step/save boundary)" if busy else ""))
        # a signal-handler frame interrupting a dispatched step or an
        # in-flight save must not reenter checkpoint I/O (same-thread
        # reentrancy into save_state) — defer to the boundary checks
        if not busy:
            self._preemption_exit()

    def _preemption_exit(self) -> None:
        """Drain → emergency save → clean exit (SystemExit(0) unwinds the
        training loop; preemption is a normal lifecycle event, not a
        failure)."""
        self._preempt_requested = False   # the exit is running — don't recurse
        from deepspeed_tpu.checkpoint.engine import finalize_async

        try:
            finalize_async()
        except Exception as e:
            logger.warning(f"async-save drain during preemption failed: {e}")
        self._emergency_save("preemption")
        # the last seconds of timeline ride along with the emergency
        # checkpoint — what WAS the run doing when the VM was reclaimed
        # (no-op unless telemetry.tracing is on)
        from deepspeed_tpu.telemetry import tracing

        tracing.get_tracer().dump_flight("preemption")
        self.shutdown_telemetry()
        log_dist("preemption: emergency checkpoint committed — exiting 0")
        raise SystemExit(0)

    def preemption_requested(self) -> bool:
        """Cooperative check for training loops that manage their own
        shutdown (the handler already exits at the next step boundary)."""
        return self._preempt_requested

    def _emergency_save(self, reason: str) -> Optional[str]:
        """Synchronous committed checkpoint into the fault-tolerance
        resume dir (fallback: the last ``save_checkpoint`` dir). Non-
        blocking lock: a second trigger while one save runs (watchdog
        thread vs signal handler) is dropped, not deadlocked."""
        if not self._ft_lock.acquire(blocking=False):
            return None
        try:
            ftc = self.config.fault_tolerance
            save_dir = ftc.resume_dir or self._last_save_dir
            if not save_dir:
                logger.error(
                    f"emergency checkpoint ({reason}) skipped: no "
                    "fault_tolerance.resume_dir and no prior save dir")
                return None
            tag = f"{ftc.emergency_tag_prefix}_step{self.global_steps}"
            from deepspeed_tpu import telemetry

            telemetry.counter(
                "checkpoint_emergency_saves_total",
                "emergency checkpoints by trigger (preemption/stall)"
            ).inc(reason=reason)
            try:
                self.save_checkpoint(save_dir, tag=tag, async_save=False)
            except Exception as e:
                logger.error(f"emergency checkpoint ({reason}) FAILED: {e}")
                return None
            return tag
        finally:
            self._ft_lock.release()

    def maybe_auto_resume(self) -> bool:
        """``fault_tolerance.auto_resume``: restore the newest committed
        checkpoint from ``resume_dir`` (called by ``initialize``). A
        missing/empty dir is a cold start, not an error."""
        ftc = self.config.fault_tolerance
        if not ftc.auto_resume:
            return False
        if not ftc.resume_dir:
            logger.warning("auto_resume=true but no fault_tolerance."
                           "resume_dir — cold start")
            return False
        from deepspeed_tpu.checkpoint.engine import read_latest_tag
        from deepspeed_tpu.checkpoint.fault_tolerance import find_restore_tag

        ckcfg = self.config.checkpoint
        has_ckpt = (find_restore_tag(
            ftc.resume_dir, checksums=ckcfg.verify_checksums) is not None
            or read_latest_tag(ftc.resume_dir) is not None)
        if not has_ckpt:
            log_dist(f"auto_resume: no checkpoint in {ftc.resume_dir} — "
                     "cold start")
            return False
        self.load_checkpoint(ftc.resume_dir)
        log_dist(f"auto_resume: restored step {self.global_steps} from "
                 f"{ftc.resume_dir}")
        return True

    # ------------------------------------------------------------------ #
    # training-run guardian hooks (runtime/guardian.py; config "guardian")
    # ------------------------------------------------------------------ #
    def attach_guardian(self, guardian) -> Optional[Dict]:
        """Register a :class:`~deepspeed_tpu.runtime.guardian.
        TrainingGuardian`: its loader/detector state rides every
        checkpoint's client state, ``load_checkpoint`` restores it, and
        the log-cadence metrics device_get feeds its anomaly detector.
        Returns the client state of a checkpoint restored BEFORE the
        guardian existed (``auto_resume`` at initialize), if any."""
        self._guardian = guardian
        return self._restored_client_state

    def defer_preemption(self):
        """Context manager deferring SIGTERM handling to scope exit while
        the caller holds un-checkpointable in-flight state — the guardian
        wraps each pull+step+containment cycle so an emergency checkpoint
        can never capture a loader that advanced past a batch the step
        hasn't trained (the offset/global_steps replay contract)."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            # a separate flag, not _in_step: the wrapped engine.train_batch
            # sets and CLEARS _in_step itself, which would re-open the
            # window mid-scope
            self._guard_busy = True
            try:
                yield
            finally:
                # boundary check INSIDE the finally: a body that raises
                # (e.g. the guardian's RestartableFailure escalation) must
                # still honor a deferred SIGTERM — preemption outranks the
                # in-flight exception (emergency save + exit 0)
                self._guard_busy = False
                self._check_preemption_boundary()

        return _scope()

    def protect_checkpoint_tag(self, tag: Optional[str],
                               root: Optional[str] = None) -> None:
        """Pin ``tag`` (in checkpoint dir ``root``) against ``keep_n``
        retention GC — the guardian's rollback anchor must survive until
        a newer anchor commits. ``None`` clears the pins;
        ``save_checkpoint`` clears them automatically once a newer tag
        commits to the same dir (the walk-back then prefers that tag, so
        the old anchor is obsolete)."""
        if tag is None:
            self._gc_protect_tags.clear()
            self._gc_protect_root = None
        else:
            self._gc_protect_tags = {tag}
            # normalized: supersession compares this to later save dirs —
            # a different SPELLING of the same dir must still clear the pin
            self._gc_protect_root = os.path.abspath(root) if root else None
        self._gc_pin_stale = False

    def probe_microbatch(self, micro: PyTree) -> Dict[str, float]:
        """Replay ONE microbatch against the numerics sentinel WITHOUT
        touching engine state — the guardian's bisect primitive. Runs a
        jitted loss+grad pass (compiled once, cached; strictly off the
        hot path) and returns host floats: ``loss``, ``grad_norm`` (fp16:
        unscaled), ``finite``."""
        if "probe" not in self._compiled:
            def probe(state, b):
                scale = state["scaler"].scale if self.fp16_enabled else None
                loss, grads = self._loss_and_grads(state["master"], b, scale)
                norm = global_grad_norm(grads)
                if scale is not None:
                    norm = norm / scale
                return {"loss": loss, "grad_norm": norm}

            # probe_microbatch is side-effect-free BY CONTRACT (the
            # guardian bisect replays batches against it) — donation
            # would mutate the state it promises to leave untouched
            self._compiled["probe"] = jax.jit(probe)  # dslint: disable=donation
        self._materialize_master()
        batch = self._shard_batch(micro)
        with self.mesh:
            out = self._compiled["probe"](self.state, batch)
        host = {k: float(jax.device_get(v)) for k, v in out.items()}
        host["finite"] = float(np.isfinite(host["loss"])
                               and np.isfinite(host["grad_norm"]))
        return host

    def _check_preemption_boundary(self) -> None:
        """Step/save-boundary half of the deferred preemption handshake.
        Main thread only: SystemExit from a worker thread (e.g. a
        watchdog-thread save that finished while preemption was pending)
        would kill that thread, not the process."""
        if self._preempt_requested and not self._guard_busy and \
                threading.current_thread() is threading.main_thread():
            self._preemption_exit()

    # ------------------------------------------------------------------ #
    # checkpointing (reference engine.py:4557 / :4079)
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True,
                        async_save: bool = False) -> None:
        from deepspeed_tpu.checkpoint.engine import save_state

        if self._offload_nvme:
            self._nvme_swapper().swap_in_optimizer()
        if self._offload_param_nvme and self._param_swapper is not None:
            self._param_swapper.swap_in_params()
        tag = tag or f"global_step{self.global_steps}"
        if self._gc_pin_stale:
            # an async save superseded the anchor earlier; its commit has
            # drained by now (save_state finalizes in-flight saves first)
            self.protect_checkpoint_tag(None)
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler else None,
            "curriculum": (self._curriculum.state_dict()
                           if self._curriculum else None),
            # host RNG (data-efficiency sampling: PLD masks, LTD indices) —
            # auto_resume must not replay or skip sampled randomness
            "np_rng": self._np_rng.bit_generator.state,
            # the world this checkpoint was written at — a fresh elastic
            # agent process compares it against the acquired world to
            # decide native reload vs universal resharding
            "world_size": int(self.dp_world_size),
        })
        if self._guardian is not None:
            # loader position + quarantine list + detector bands ride every
            # checkpoint — including the SIGTERM emergency tag — so resume
            # replays the exact batch sequence (README "Training guardian")
            client_state.update(self._guardian.client_state())
        ck = self.config.checkpoint
        self._saving = True   # a preemption signal mid-save defers here
        try:
            # _checkpoint_state: the gathered double buffer is derived
            # state, excluded from every checkpoint (incl. SIGTERM
            # emergency tags) and recomputed on restore — a checkpoint
            # can never capture a buffer stale relative to its master
            save_state(save_dir, tag, self._checkpoint_state(), client_state,
                       save_latest=save_latest, async_save=async_save,
                       writer=self.config.effective_checkpoint_writer,
                       keep_n=ck.keep_n, fsync=ck.fsync,
                       checksums=ck.verify_checksums, retries=ck.save_retries,
                       retry_backoff_s=ck.retry_backoff_s,
                       retry_jitter_s=ck.retry_jitter_s,
                       protect=tuple(self._gc_protect_tags))
        finally:
            self._saving = False
        self._last_save_dir = save_dir
        if (self._gc_protect_tags and tag not in self._gc_protect_tags
                and self._gc_protect_root in (None,
                                              os.path.abspath(save_dir))):
            if async_save:
                # the superseding tag's COMMIT is still in flight — mark
                # the pin stale and clear it at the next save, whose
                # finalize_async will have drained this commit first
                self._gc_pin_stale = True
            else:
                # a NEWER tag just committed to the anchor's dir: the
                # walk-back now prefers it, so the pinned rollback anchor
                # is obsolete — let the next save's keep_n GC reclaim it
                self.protect_checkpoint_tag(None)
        log_dist(f"saved checkpoint {save_dir}/{tag}"
                 + (" (async, commit in flight)" if async_save else ""))
        self._check_preemption_boundary()

    def save_16bit_model(self, save_dir: str,
                         save_filename: str = "pytorch_model.npz") -> None:
        """Gather params and export in the compute dtype (reference
        ``save_16bit_model`` engine.py:5355 / ``_zero3_consolidated_16bit_state_dict``
        :5285 — the live-consolidation path)."""
        import ml_dtypes
        import numpy as np_

        os.makedirs(save_dir, exist_ok=True)
        params = self.get_fp32_params()
        # bf16 is stored AS bf16 (ml_dtypes registers it with numpy; fp16
        # would silently drop bf16's exponent range — |x| > 65504 → inf)
        # bf16 → ml_dtypes bf16; fp16 → fp16; fp32 engines export fp32
        # unchanged (downcasting would overflow-to-inf above 65504)
        dtype = (ml_dtypes.bfloat16 if self.precision == "bfloat16"
                 else np_.dtype(self.precision))
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            key = "/".join(p.key if hasattr(p, "key") else str(p.idx) for p in path)
            flat[key] = np_.asarray(jax.device_get(leaf)).astype(dtype)
        if jax.process_index() == 0:
            np_.savez(os.path.join(save_dir, save_filename), **flat)
            # npz round-trips bf16 bytes but loses the dtype name (numpy
            # reads it back as raw V2); the sidecar manifest restores it —
            # consumed by checkpoint.engine.load_16bit_model
            with open(os.path.join(save_dir, save_filename + ".dtypes.json"),
                      "w") as f:
                json.dump({k: str(np_.dtype(v.dtype)) for k, v in flat.items()},
                          f)
        log_dist(f"saved 16-bit model to {save_dir}/{save_filename} "
                 f"(dtype={np_.dtype(dtype)})")

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True):
        from deepspeed_tpu.checkpoint.engine import load_state

        if (self._offload_nvme and self._opt_swapper is not None
                and not load_optimizer_states):
            # the checkpoint will NOT supply moments, so the live (NVMe-swapped)
            # ones must be materialized before `state["opt"]` is carried over;
            # on the default path the restore overwrites them anyway and the
            # placeholders suffice as the orbax target template — swapping in
            # there would transiently double optimizer-state HBM
            self._opt_swapper.swap_in_optimizer()
        load_sh = self._state_shardings()
        load_sh.pop("gathered", None)   # derived buffer: never persisted
        state, client_state = load_state(
            load_dir, tag, self._checkpoint_state(), load_sh,
            verify_checksums=self.config.checkpoint.verify_checksums)
        if not load_optimizer_states:
            state["opt"] = self.state["opt"]
        self.state = state
        # republish the double buffer from the RESTORED master — the
        # next forward must consume exactly the restored weights
        self._refresh_param_buffer()
        if self._offload_opt:
            self._opt_swap("out")
        if (self._offload_nvme and self._opt_swapper is not None
                and load_optimizer_states):
            # the restore put real moments in state['opt'] but the swapper
            # still thinks its (stale) swap files are authoritative
            # (_swapped=True) — the next step's swap_in would clobber the
            # restored moments. Re-swap-out: fresh files, consistent state,
            # HBM freed again.
            self._opt_swapper.swap_out_optimizer()
        if self._offload_param:
            self._park_master()   # restored master → pinned-host tier
        if self._offload_param_nvme and self._param_swapper is not None:
            # same reload-clobber hazard as the optimizer swapper: the
            # restored master must supersede the stale swap files
            self._param_swapper.swap_out_params()
        if self._host_runner is not None:
            self._host_runner.adopt_state()   # re-home master/opt + params
        self.global_steps = int(client_state.get("global_steps", 0))
        self.micro_steps = int(client_state.get("micro_steps", 0))
        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                client_state.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        if self._curriculum is not None and client_state.get("curriculum"):
            self._curriculum.load_state_dict(client_state["curriculum"])
        if client_state.get("np_rng"):
            try:
                self._np_rng.bit_generator.state = client_state["np_rng"]
            except (TypeError, ValueError) as e:
                logger.warning(f"host RNG state in checkpoint not "
                               f"restorable ({e}) — fresh stream")
        # guardian/loader state: restore through an attached guardian, and
        # keep the raw client state so a guardian attached AFTER this load
        # (auto_resume runs at initialize, before TrainingGuardian exists)
        # can still pick it up (TrainingGuardian.__init__ does)
        self._restored_client_state = client_state
        if self._guardian is not None:
            self._guardian.restore_client_state(client_state)
        log_dist(f"loaded checkpoint from {load_dir} (tag={tag or 'latest'})")
        return load_dir, client_state

    def load_universal_checkpoint(self, universal_dir: str,
                                  load_optimizer_states: bool = True) -> None:
        """Load a universal (per-param atom) checkpoint at ANY topology
        (reference ``load_universal_checkpoint``; converter:
        ``deepspeed_tpu.checkpoint.universal``): the world-elastic resume
        path. Master weights and optimizer moments land on this engine's
        mesh whatever world they were saved at; per-rank residual trees
        (LoCo ``loco_err``, onebit ``worker_error``) are re-partitioned
        sum-preservingly onto ``_dp_manual_world``; the guardian/loader
        exact-resume client state rides along so the batch sequence
        continues where the old world left off."""
        from deepspeed_tpu.checkpoint.universal import load_universal_into_engine

        load_universal_into_engine(self, universal_dir, load_optimizer_states)
        log_dist(f"loaded universal checkpoint from {universal_dir} "
                 f"(world {self._dp_manual_world})")

    # ------------------------------------------------------------------ #
    def get_fp32_params(self) -> PyTree:
        """Gathered fp32 master params (the zero_to_fp32 consolidation analog)."""
        self._materialize_master()
        rep = jax.tree.map(lambda _: NamedSharding(self.mesh, P()), self._shapes)
        with self.mesh:
            return jax.jit(lambda m: m, out_shardings=rep)(self.state["master"])
