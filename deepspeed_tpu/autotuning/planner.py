"""Plan engine — observatory-driven autotuning over the overlap knobs.

The classic :class:`~deepspeed_tpu.autotuning.autotuner.Autotuner`
measures every candidate it cannot analytically prune: compile, run,
time, repeat. This module inverts that economy around the observability
stack the repo already trusts:

1. **enumerate** (``enumerate_candidates``) — the ~8-knob overlap space:
   ``reduce_bucket_size`` / ``allgather_bucket_size`` /
   ``stage3_prefetch_bucket_size`` ladders derived from the model's
   parameter count, ``update_bucket_size``, ``overlap_step``, the hpZ
   subgroup (``zero_hpz_partition_size``), the qgZ quantization block
   size, and the scan chunk count (derived from the prefetch bucket and
   recorded per candidate, not set directly);
2. **refuse** (``refuse_candidate``) — each candidate's analytic HBM
   need (``memory_model.estimate``) runs through memlint's REAL
   ``oom-preflight`` rule against ``hbm_budget_bytes`` BEFORE anything
   compiles; an infeasible candidate is refused with the rule named,
   never lowered. A ``preflight_canary`` candidate priced against a
   deliberately-impossible 1-byte budget rides in every run so the
   refusal leg itself is exercised (a canary that is NOT refused is an
   internal error, CLI exit 2);
3. **price** — survivors are lowered ONCE each and priced through the
   shared :func:`~deepspeed_tpu.profiling.observatory.pricing
   .price_program` (compiled-collective ledger + roofline legs → total
   predicted step seconds). ``--dry-run`` stops before lowering and
   ranks on the closed-form analytic estimate instead;
4. **confirm** — the predicted top-K get short measured windows in
   bench.py's one-JSON-line child processes (``bench/subproc.py``);
   ``predicted_vs_measured_rel_err`` is the calibration figure;
5. **emit** — the winning plan is cached per ``(model_fingerprint,
   mesh_shape, wire_format, platform)`` in a versioned ``plan.json``
   the engine loads at initialize (``"autotuning"`` config section),
   optionally alongside a committed hlolint + memlint contract pair
   pinning the planned program (``--write-contracts``).

Self-observability: ``autotune_candidates_total{verdict=priced|
oom_refused|confirmed|rejected}``, ``autotune_plan_cache_hits_total`` /
``..._misses_total`` (engine side), the
``autotune_predicted_vs_measured_rel_err`` gauge, and a trace span per
candidate.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu import telemetry
from deepspeed_tpu.autotuning import memory_model as mm
from deepspeed_tpu.comm import bandwidth as BW
from deepspeed_tpu.utils.logging import logger

PLAN_VERSION = 1
CANARY_NAME = "preflight_canary"
CANARY_BUDGET_BYTES = 1

#: candidate verdicts, in lifecycle order
VERDICT_OOM_REFUSED = "oom_refused"
VERDICT_PRICED = "priced"
VERDICT_CONFIRMED = "confirmed"
VERDICT_REJECTED = "rejected"

#: zero_optimization keys a plan may set on the engine at initialize.
#: zero_hpz_partition_size IS applied — the engine loads the plan before
#: the hpZ subgroup resolution consumes it (engine.__init__ ordering).
APPLIED_KNOBS = (
    "reduce_bucket_size", "allgather_bucket_size",
    "stage3_prefetch_bucket_size", "update_bucket_size",
    "overlap_comm", "overlap_step", "zero_hpz_partition_size",
)

#: top-level plan.json keys — ``validate_plan`` refuses documents
#: missing any of these (schema-valid is a CLI acceptance gate)
PLAN_REQUIRED_KEYS = (
    "plan_version", "key", "key_fields", "knobs", "predicted",
    "candidates", "counters", "seq_len", "micro_batch",
)

_int8_overhead = 1.0  # int8 payload bytes per element on the qz wire


class PlanError(Exception):
    """Unreadable / schema-invalid / version-mismatched plan document."""


@dataclasses.dataclass
class Candidate:
    """One point in the knob space, with its verdict trail."""
    name: str
    knobs: Dict[str, Any]                 # zero_optimization overrides
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)
    verdict: str = "pending"
    refusal: Optional[str] = None         # oom-preflight finding text
    est_hbm_bytes: Optional[int] = None   # analytic memory-model need
    analytic: Optional[Dict[str, Any]] = None
    predicted: Optional[Dict[str, Any]] = None  # PredictedCost.to_dict()
    measured: Optional[Dict[str, Any]] = None
    rel_err: Optional[float] = None

    def rank_cost(self) -> float:
        """Predicted step seconds used for ranking — the lowered price
        when available, else the analytic estimate, else +inf."""
        if self.predicted and self.predicted.get("total_s") is not None:
            return float(self.predicted["total_s"])
        if self.analytic and self.analytic.get("total_s") is not None:
            return float(self.analytic["total_s"])
        return float("inf")

    def to_row(self) -> Dict[str, Any]:
        row: Dict[str, Any] = {"name": self.name, "knobs": self.knobs,
                               "verdict": self.verdict}
        if self.info:
            row["info"] = self.info
        if self.refusal:
            row["refusal"] = self.refusal
        if self.est_hbm_bytes is not None:
            row["est_hbm_bytes"] = int(self.est_hbm_bytes)
        if self.analytic is not None:
            row["analytic"] = self.analytic
        if self.predicted is not None:
            row["predicted"] = self.predicted
        if self.measured is not None:
            row["measured"] = self.measured
        if self.rel_err is not None:
            row["rel_err"] = round(self.rel_err, 4)
        return row


# --------------------------------------------------------------------- #
# plan identity — the cache key both the planner and the engine compute
# from config alone (the engine loads the plan BEFORE the mesh exists)
# --------------------------------------------------------------------- #
def model_fingerprint(model_spec, seq_len: Optional[int] = None) -> str:
    """Stable short hash of the model's analytic identity (param count,
    width/depth/vocab, trained seq len) — what the plan's predicted
    costs actually depend on."""
    info = mm.ModelInfo.from_spec(model_spec, seq_len=seq_len)
    blob = json.dumps({
        "num_params": info.num_params, "hidden": info.hidden_size,
        "layers": info.num_layers, "ffn": info.ffn_size,
        "vocab": info.vocab_size, "seq_len": info.seq_len,
        "experts": info.n_experts,
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def mesh_shape_token(mesh_shape: Dict[str, int]) -> str:
    """``{'data': 8}`` → ``"data8"``; multi-axis meshes join sorted
    non-trivial axes with ``.`` (``"data4.tensor2"``); a single device
    is ``"single"``."""
    parts = [f"{a}{int(n)}" for a, n in sorted(mesh_shape.items())
             if int(n) > 1]
    return ".".join(parts) or "single"


def wire_format_from_config(cfg, mesh_shape: Dict[str, int]) -> str:
    """Pure mirror of ``engine._wire_format()`` from config + the
    resolved mesh shape — the plan-key leg that must be computable
    BEFORE the engine builds its mesh or resolves compressed modes.
    Keyed the same way on both sides (planner writes, engine looks up),
    so an edge-case divergence from the live resolution can only cost a
    cache miss, never a wrong plan applied."""
    z = cfg.zero_optimization
    dp_w = (mesh_shape.get("data", 1) * mesh_shape.get("zshard", 1)
            * mesh_shape.get("expert", 1))
    eligible = (mesh_shape.get("data", 1) * mesh_shape.get("zshard", 1) > 1
                and mesh_shape.get("seq", 1) == 1
                and mesh_shape.get("pipe", 1) == 1)
    opt_type = (cfg.optimizer.type if cfg.optimizer else "")
    opt_type = opt_type.lower().replace("_", "")
    if (opt_type.startswith("onebit") and z.stage == 0 and eligible
            and mesh_shape.get("expert", 1) == 1
            and not cfg.fp16.enabled):
        return "onebit"
    quant = (z.zero_quantized_weights or z.zero_quantized_gradients
             or z.zero_quantized_nontrainable_weights)
    if quant and z.stage >= 1 and eligible:
        if z.loco_error_feedback and z.zero_quantized_gradients:
            return "qz+loco"
        return "qz"
    return "exact"


def plan_key_for_config(cfg, model_spec,
                        seq_len: Optional[int] = None,
                        platform: Optional[str] = None
                        ) -> Tuple[str, Dict[str, str]]:
    """The ``(model_fingerprint, mesh_shape, wire_format, platform)``
    cache key, as the flat filename stem plus its fields. Shared by the
    planner (write side) and ``engine._load_autotune_plan`` (read side)
    so the two can never disagree on identity."""
    import jax

    shape = cfg.mesh.to_mesh_config().resolve(jax.device_count())
    fields = {
        "model_fingerprint": model_fingerprint(model_spec, seq_len=seq_len),
        "mesh_shape": mesh_shape_token(shape),
        "wire_format": wire_format_from_config(cfg, shape),
        "platform": platform or jax.default_backend(),
    }
    key = "-".join(fields[k] for k in ("model_fingerprint", "mesh_shape",
                                       "wire_format", "platform"))
    return key, fields


def plan_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"{key}.plan.json")


# --------------------------------------------------------------------- #
# plan document I/O
# --------------------------------------------------------------------- #
def validate_plan(doc: Any) -> List[str]:
    """Schema errors for a plan document (empty list = valid)."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return [f"plan must be a JSON object, got {type(doc).__name__}"]
    for k in PLAN_REQUIRED_KEYS:
        if k not in doc:
            errors.append(f"missing required key {k!r}")
    if errors:
        return errors
    if doc["plan_version"] != PLAN_VERSION:
        errors.append(f"plan_version {doc['plan_version']!r} != "
                      f"supported {PLAN_VERSION}")
    kf = doc["key_fields"]
    if not isinstance(kf, dict) or set(kf) != {
            "model_fingerprint", "mesh_shape", "wire_format", "platform"}:
        errors.append("key_fields must name exactly model_fingerprint/"
                      "mesh_shape/wire_format/platform")
    if not isinstance(doc["knobs"], dict) or not doc["knobs"]:
        errors.append("knobs must be a non-empty object")
    else:
        unknown = [k for k in doc["knobs"] if k not in APPLIED_KNOBS]
        if unknown:
            errors.append(f"unknown applied knob(s) {unknown} — plan "
                          f"knobs are limited to {list(APPLIED_KNOBS)}")
    if not isinstance(doc["candidates"], list) or not doc["candidates"]:
        errors.append("candidates must be a non-empty list")
    else:
        refused = [c for c in doc["candidates"]
                   if isinstance(c, dict)
                   and c.get("verdict") == VERDICT_OOM_REFUSED]
        if not refused:
            errors.append("no oom_refused candidate — the pre-flight "
                          "refusal leg did not run (canary missing?)")
    if not isinstance(doc["counters"], dict):
        errors.append("counters must be an object")
    return errors


def load_plan(path: str) -> Dict[str, Any]:
    """Read + schema-validate a committed plan; raises PlanError."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise PlanError(f"cannot read plan {path}: {e}")
    errors = validate_plan(doc)
    if errors:
        raise PlanError(f"invalid plan {path}: " + "; ".join(errors))
    return doc


def write_plan(path: str, doc: Dict[str, Any]) -> str:
    errors = validate_plan(doc)
    if errors:
        raise PlanError("refusing to write invalid plan: "
                        + "; ".join(errors))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------------- #
# the plan engine
# --------------------------------------------------------------------- #
class PlanEngine:
    """Enumerate → refuse → price → confirm → emit, over one model spec
    and base config.

    ``base_config`` plays the Autotuner role: everything except the
    planned knobs (optimizer, precision, mesh, batch) is taken as
    given. ``hbm_budget_bytes`` defaults to the live probe
    (``memory_model.hbm_capacity_bytes``)."""

    def __init__(self, model_spec, base_config: Dict[str, Any], *,
                 seq_len: int = 32, vocab_size: int = 512,
                 hbm_budget_bytes: Optional[int] = None,
                 link_gbps: Optional[float] = None,
                 max_candidates: int = 64, confirm_top_k: int = 2,
                 steps: int = 3, warmup: int = 1,
                 confirm_timeout: float = 300.0):
        self.model_spec = model_spec
        self.base_config = base_config
        self.seq_len = seq_len
        self.vocab_size = vocab_size
        self.hbm_budget_bytes = hbm_budget_bytes or mm.hbm_capacity_bytes()
        self.max_candidates = max(1, int(max_candidates))
        self.confirm_top_k = max(0, int(confirm_top_k))
        self.steps = steps
        self.warmup = warmup
        self.confirm_timeout = confirm_timeout
        self.info = mm.ModelInfo.from_spec(model_spec, seq_len=seq_len)
        self._link_gbps = link_gbps
        self._tm_candidates = telemetry.counter(
            "autotune_candidates_total",
            "plan-engine candidates by lifecycle verdict")

    # ------------------------------------------------------------ shape
    def _world(self) -> int:
        mesh = self.base_config.get("mesh", {}) or {}
        data = max(1, int(mesh.get("data", 1)))
        zshard = max(1, int(mesh.get("zshard", 1)))
        return data * zshard

    def _stage(self) -> int:
        z = self.base_config.get("zero_optimization", {}) or {}
        return int(z.get("stage", 0))

    def _micro_batch(self) -> int:
        return int(self.base_config.get(
            "train_micro_batch_size_per_gpu", 1))

    def _quantized(self) -> bool:
        z = self.base_config.get("zero_optimization", {}) or {}
        return bool(z.get("zero_quantized_gradients")
                    or z.get("zero_quantized_weights"))

    def link_gbps(self) -> float:
        if self._link_gbps:
            return float(self._link_gbps)
        try:
            import jax

            kind = getattr(jax.devices()[0], "device_kind", "")
        # no live backend is an expected state here (the CLI prices
        # before jax initializes) — fall to the nominal datasheet rate
        except Exception:   # dslint: disable=silent-except
            kind = ""
        return BW.chip_link_gbps(kind)

    # ------------------------------------------------------- enumerate
    def bucket_ladder(self) -> List[int]:
        """Three bucket sizes (ELEMENT counts, the PR-8 contract)
        bracketing the model: an eighth, half, and twice the parameter
        count — fine-grained fencing, a balanced middle, and one big
        bucket that approaches unfenced behavior."""
        p = max(int(self.info.num_params), 1024)
        ladder = sorted({max(1024, p // 8), max(1024, p // 2), 2 * p})
        return ladder

    def enumerate_candidates(self) -> List[Candidate]:
        """The knob grid, capped at ``max_candidates``, plus the
        refusal canary. hpZ subgroups enumerate only where they can
        form (world divisible, stage 3, >= 4 devices); qgZ block sizes
        only on a quantized wire (informational — the block is a kernel
        default, priced analytically and recorded, not a config key)."""
        stage = self._stage()
        world = self._world()
        cands: List[Candidate] = []
        for b in self.bucket_ladder():
            for overlap_step in (False, True):
                knobs: Dict[str, Any] = {
                    "overlap_comm": True,
                    "reduce_bucket_size": b,
                    "update_bucket_size": "auto",
                    "overlap_step": overlap_step,
                }
                if stage >= 3:
                    knobs["stage3_prefetch_bucket_size"] = 2 * b
                else:
                    knobs["allgather_bucket_size"] = 2 * b
                cands.append(Candidate(
                    name=f"b{b}_step{'1' if overlap_step else '0'}",
                    knobs=knobs))
        if stage >= 3 and world >= 4 and world % 2 == 0:
            base = dict(cands[len(cands) // 2].knobs)
            base["zero_hpz_partition_size"] = world // 2
            cands.append(Candidate(name=f"hpz{world // 2}", knobs=base))
        if self._quantized():
            mid = dict(cands[len(cands) // 2].knobs)
            for block in (1024, 4096):
                cands.append(Candidate(
                    name=f"qgz_block{block}", knobs=dict(mid),
                    info={"qgz_block": block}))
        if len(cands) > self.max_candidates:
            logger.info(f"plan engine: capping {len(cands)} candidates "
                        f"at max_candidates={self.max_candidates}")
            cands = cands[: self.max_candidates]
        # the refusal canary rides every run: same knobs as the first
        # candidate, priced against an impossible budget, MUST refuse
        canary = Candidate(name=CANARY_NAME, knobs=dict(cands[0].knobs),
                           info={"canary_budget_bytes": CANARY_BUDGET_BYTES})
        cands.append(canary)
        return cands

    # --------------------------------------------------------- refuse
    def refuse_candidate(self, cand: Candidate,
                         budget: Optional[int] = None) -> Optional[str]:
        """Run the candidate's analytic HBM need through memlint's
        ``oom-preflight`` rule. Returns the finding text (refusal) or
        None (feasible). Nothing compiles on this path."""
        from deepspeed_tpu.analysis.memlint import (
            MemLintConfig,
            MemObservations,
            iter_rule_findings,
            select_rules,
        )

        z = self.base_config.get("zero_optimization", {}) or {}
        hpz = int(cand.knobs.get("zero_hpz_partition_size", 0) or 0)
        dp = hpz if hpz > 1 else self._world()
        est = mm.estimate(
            self.info, zero_stage=self._stage(), dp_shards=dp,
            micro_batch=self._micro_batch(), seq_len=self.seq_len,
            precision=self._precision(),
            offload_optimizer=bool((z.get("offload_optimizer") or {})
                                   .get("device", "none") != "none"))
        cand.est_hbm_bytes = int(est.total)
        obs = MemObservations(model_estimate_bytes=float(est.total))
        cfg = MemLintConfig(
            program=cand.name,
            hbm_budget_bytes=float(budget or self.hbm_budget_bytes))
        findings = iter_rule_findings(obs, cfg,
                                      rules=select_rules(["oom-preflight"]))
        if findings:
            return "; ".join(f"{f.rule}: {f.message}" for f in findings)
        return None

    def _precision(self) -> str:
        if (self.base_config.get("fp16", {}) or {}).get("enabled"):
            return "float16"
        if (self.base_config.get("bf16", {}) or {}).get("enabled"):
            return "bfloat16"
        return "float32"

    # ---------------------------------------------------------- price
    def analytic_price(self, cand: Candidate) -> Dict[str, Any]:
        """Closed-form cost with no lowering (the ``--dry-run`` leg):
        grad-sync / param-gather wire bytes from the wire format (exact
        fp32 grads = 4 B/elem; the qz wire = int8 + one fp32 scale per
        block), bucketed into ``predicted_seconds`` calls, against a
        6·P·tokens FLOPs compute leg at the chip peak. Coarser than the
        lowered ledger — good enough to rank survivors for lowering
        order and to stand in when ``--dry-run`` skips compilation."""
        world = self._world()
        stage = self._stage()
        p = int(self.info.num_params)
        link = self.link_gbps()
        quant = self._quantized()
        block = int(cand.info.get("qgz_block", 2048) or 2048)
        grad_b = (_int8_overhead + 4.0 / block) if quant else 4.0
        hpz = int(cand.knobs.get("zero_hpz_partition_size", 0) or 0)
        comm_s = 0.0
        wire_bytes = 0
        if world > 1:
            n_red = max(1, math.ceil(
                p / int(cand.knobs["reduce_bucket_size"])))
            red_bytes = int(p * grad_b)
            wire_bytes += red_bytes
            kind = "reduce_scatter" if stage >= 2 else "all_reduce"
            comm_s += n_red * BW.predicted_seconds(
                kind, red_bytes // n_red, world, link)
            if stage >= 3:
                gather_group = hpz if hpz > 1 else world
                gw_b = (_int8_overhead + 4.0 / block) if quant else 2.0
                gat_bytes = int(p * gw_b)
                wire_bytes += gat_bytes
                n_gat = max(1, math.ceil(
                    p / int(cand.knobs.get("stage3_prefetch_bucket_size",
                                           p))))
                comm_s += n_gat * BW.predicted_seconds(
                    "all_gather", gat_bytes // n_gat, gather_group, link)
        tokens = self._micro_batch() * world * self.seq_len
        peak = self._chip_peak_flops()
        compute_s = (6.0 * p * tokens / peak) if peak else 0.0
        total = (max(compute_s, comm_s)
                 if cand.knobs.get("overlap_comm", True)
                 else compute_s + comm_s)
        return {"total_s": round(total, 6), "comm_s": round(comm_s, 6),
                "compute_s": round(compute_s, 6), "wire_bytes": wire_bytes,
                "link_gbps": link, "model": "analytic"}

    def _chip_peak_flops(self) -> Optional[float]:
        try:
            import jax

            from deepspeed_tpu.utils.chip_specs import chip_peak_tflops

            peak = chip_peak_tflops(
                getattr(jax.devices()[0], "device_kind", ""))
            return peak * 1e12 if peak else None
        # no backend / no datasheet entry = no compute leg (CPU tier);
        # the analytic price then ranks on the comm legs alone
        except Exception:   # dslint: disable=silent-except
            return None

    def candidate_config(self, cand: Candidate) -> Dict[str, Any]:
        config = json.loads(json.dumps(self.base_config))
        z = config.setdefault("zero_optimization", {})
        for k, v in cand.knobs.items():
            z[k] = v
        hpz = int(cand.knobs.get("zero_hpz_partition_size", 0) or 0)
        if hpz > 1:
            # the subgroup IS the zshard axis: data × zshard must cover
            # the same device world the flat-data base config used
            mesh = config.setdefault("mesh", {})
            world = self._world()
            mesh["zshard"] = hpz
            mesh["data"] = max(1, world // hpz)
        config.setdefault("steps_per_print", 10 ** 9)
        return config

    def lowered_price(self, cand: Candidate) -> Optional[Dict[str, Any]]:
        """Initialize an engine for the candidate, lower its step ONCE
        (``ledger_for_engine``'s cached lowering), and price the HLO
        through the shared ``price_program``. Returns the cost dict or
        None (init/lower failure → candidate stays analytic)."""
        import jax

        import deepspeed_tpu as dst
        from deepspeed_tpu.comm import mesh as mesh_mod
        from deepspeed_tpu.profiling.observatory.ledger import (
            ledger_for_engine,
        )
        from deepspeed_tpu.profiling.observatory.pricing import (
            price_program,
        )

        config = self.candidate_config(cand)
        try:
            mesh_mod.reset_mesh()
            engine, *_ = dst.initialize(model=self.model_spec,
                                        config=config)
            ledger, mem = ledger_for_engine(engine, fold=False,
                                            seq_len=self.seq_len)
            elems = sum(
                int(math.prod(getattr(s, "shape", ()) or ()))
                for s in jax.tree.leaves(engine._shapes))
            opt_type = (config.get("optimizer", {}) or {}).get(
                "type", "adam").lower()
            plan = engine.overlap_plan()
            cand.info.setdefault("scan_chunks", plan.get("scan_chunks"))
            cost = price_program(ledger.hlo_text, {
                "program": cand.name,
                "world": ledger.world,
                "zero_stage": engine.zero_stage,
                "link_gbps": self.link_gbps(),
                "cost_flops": ledger.cost_flops,
                "peak_flops": engine._chip_peak_flops(),
                "update_elems": elems,
                "update_shard": max(int(engine.dp_world_size), 1),
                "n_moments": 2 if "adam" in opt_type or "lamb" in opt_type
                else None,
                "overlap_comm": bool(cand.knobs.get("overlap_comm", True)),
                "overlap_step": bool(plan.get("step_overlap")),
                "memory_stats": mem,
            })
            return cost.to_dict()
        except Exception as e:  # noqa: BLE001 — compile/OOM per candidate
            logger.warning(f"plan engine: lowering {cand.name} failed "
                           f"({type(e).__name__}: {e})")
            return None

    # -------------------------------------------------------- confirm
    def confirm(self, cand: Candidate) -> Dict[str, Any]:
        """Measured window in a one-JSON-line child process (the bench
        entry isolation contract): an OOM in a mis-predicted candidate
        kills ITS process, not the plan run."""
        from deepspeed_tpu.bench.subproc import run_json_subprocess

        payload = {
            "model": getattr(self.model_spec, "preset", None)
            or getattr(self.model_spec, "name", "tiny"),
            "seq_len": self.seq_len, "vocab_size": self.vocab_size,
            "steps": self.steps, "warmup": self.warmup,
            "config": self.candidate_config(cand),
        }
        return run_json_subprocess(
            [sys.executable, "-m", "deepspeed_tpu.autotuning",
             "--entry", "confirm", "--spec-json", json.dumps(payload)],
            timeout=self.confirm_timeout)

    # ------------------------------------------------------------ run
    def run(self, dry_run: bool = False) -> Dict[str, Any]:
        """The full plan pass; returns the (schema-valid) plan doc."""
        counters = {VERDICT_PRICED: 0, VERDICT_OOM_REFUSED: 0,
                    VERDICT_CONFIRMED: 0, VERDICT_REJECTED: 0}

        def count(verdict: str) -> None:
            counters[verdict] += 1
            self._tm_candidates.inc(verdict=verdict)

        if not dry_run and self.confirm_top_k:
            import jax

            if jax.default_backend() == "tpu":
                # this process lowers the candidates, so it holds the
                # chip; the confirming child needs the same chip and a
                # chip belongs to one process at a time — the child would
                # fail or hang and the plan would fall back to the
                # unconfirmed ranking without saying so
                raise PlanError(
                    "the confirm leg measures candidates in a child "
                    "process, which cannot get the TPU this process "
                    "holds; rerun with --top-k 0 (priced, unconfirmed "
                    "plan) until confirm runs in-process")
        cands = self.enumerate_candidates()
        log_n = len(cands)
        logger.info(f"plan engine: {log_n} candidates "
                    f"(budget {self.hbm_budget_bytes / 2**30:.2f} GiB, "
                    f"link {self.link_gbps():.1f} GB/s)")
        survivors: List[Candidate] = []
        for cand in cands:
            with telemetry.span("autotune_candidate", candidate=cand.name):
                budget = (CANARY_BUDGET_BYTES
                          if cand.name == CANARY_NAME else None)
                refusal = self.refuse_candidate(cand, budget=budget)
                if refusal:
                    cand.verdict = VERDICT_OOM_REFUSED
                    cand.refusal = refusal
                    count(VERDICT_OOM_REFUSED)
                    continue
                if cand.name == CANARY_NAME:
                    raise PlanError(
                        "preflight canary was NOT refused — the "
                        "oom-preflight analytic gate is not running; "
                        "refusing to emit a plan that never exercised "
                        "its refusal leg")
                cand.analytic = self.analytic_price(cand)
                survivors.append(cand)
        # lower in analytic-cost order so an interrupted run priced the
        # most promising candidates first
        survivors.sort(key=lambda c: c.rank_cost())
        for cand in survivors:
            if not dry_run:
                with telemetry.span("autotune_price",
                                    candidate=cand.name):
                    cand.predicted = self.lowered_price(cand)
            cand.verdict = VERDICT_PRICED
            count(VERDICT_PRICED)
        ranked = sorted(survivors, key=lambda c: c.rank_cost())
        if not ranked:
            raise PlanError("no feasible candidate — every point in the "
                            "knob space was refused by the OOM pre-flight")
        if not dry_run and self.confirm_top_k:
            gauge = telemetry.gauge(
                "autotune_predicted_vs_measured_rel_err",
                "|predicted - measured| / measured per confirmed candidate")
            for cand in ranked[: self.confirm_top_k]:
                with telemetry.span("autotune_confirm",
                                    candidate=cand.name):
                    res = self.confirm(cand)
                if res.get("error") or not res.get("step_time_s"):
                    cand.measured = {"error": res.get("error",
                                                      "no measurement")}
                    continue
                cand.measured = {
                    "step_time_s": res["step_time_s"],
                    "throughput": res.get("throughput"),
                }
                pred = cand.rank_cost()
                meas = float(res["step_time_s"])
                cand.rel_err = abs(pred - meas) / meas if meas else None
                if cand.rel_err is not None:
                    gauge.set(cand.rel_err, candidate=cand.name)
                cand.verdict = VERDICT_CONFIRMED
                count(VERDICT_CONFIRMED)
            confirmed = [c for c in ranked[: self.confirm_top_k]
                         if c.verdict == VERDICT_CONFIRMED]
            if confirmed:
                confirmed.sort(
                    key=lambda c: c.measured["step_time_s"])
                winner = confirmed[0]
                for c in confirmed[1:]:
                    c.verdict = VERDICT_REJECTED
                    count(VERDICT_REJECTED)
            else:
                winner = ranked[0]
        else:
            winner = ranked[0]
        return self._plan_doc(winner, cands, counters, dry_run)

    def _plan_doc(self, winner: Candidate, cands: List[Candidate],
                  counters: Dict[str, int],
                  dry_run: bool) -> Dict[str, Any]:
        from deepspeed_tpu.runtime.config import load_config

        # keyed off the BASE config, never the winner's: the engine
        # computes its lookup key BEFORE the plan's knobs (hpZ mutates
        # the mesh) are applied, so both sides must hash the same thing
        # seq_len deliberately NOT passed: both sides fingerprint the
        # spec's own nominal sequence length (the engine knows no other)
        cfg = load_config(json.loads(json.dumps(self.base_config)))
        key, fields = plan_key_for_config(cfg, self.model_spec)
        knobs = {k: v for k, v in winner.knobs.items()
                 if k in APPLIED_KNOBS}
        doc: Dict[str, Any] = {
            "plan_version": PLAN_VERSION,
            "key": key,
            "key_fields": fields,
            "seq_len": self.seq_len,
            "micro_batch": self._micro_batch(),
            "hbm_budget_bytes": int(self.hbm_budget_bytes),
            "dry_run": bool(dry_run),
            "winner": winner.name,
            "knobs": knobs,
            "informational": winner.info or {},
            "predicted": winner.predicted or winner.analytic or {},
            "measured": winner.measured,
            "rel_err": winner.rel_err,
            "counters": counters,
            "candidates": [c.to_row() for c in cands],
        }
        return doc

    # ---------------------------------------------------- contracts
    def emit_contracts(self, doc: Dict[str, Any],
                       out_dir: str) -> Dict[str, str]:
        """Re-initialize the winning engine and commit its program as an
        enforceable hlolint + memlint contract pair (``engine_contract``
        on both packages, ``write_contract`` shrink-only semantics) —
        the plan is a CONTRACT, not a suggestion."""
        import deepspeed_tpu as dst
        from deepspeed_tpu.analysis import hlolint, memlint
        from deepspeed_tpu.comm import mesh as mesh_mod

        winner = next(c for c in doc["candidates"]
                      if c["name"] == doc["winner"])
        cand = Candidate(name=winner["name"], knobs=winner["knobs"])
        mesh_mod.reset_mesh()
        engine, *_ = dst.initialize(model=self.model_spec,
                                    config=self.candidate_config(cand))
        stem = doc["key"]
        os.makedirs(out_dir, exist_ok=True)
        paths: Dict[str, str] = {}
        for pkg, suffix in ((hlolint, "hlolint"), (memlint, "memlint")):
            contract = pkg.engine_contract(engine, seq_len=self.seq_len,
                                           hlo_name=f"{stem}.hlo.txt")
            path = os.path.join(out_dir, f"{stem}.{suffix}.json")
            pkg.write_contract(path, contract, allow_loosen=True)
            paths[suffix] = path
        return paths
