"""MoE gating: top-1 / top-2 / top-k with capacity and load-balancing loss.

Parity: reference ``deepspeed/moe/sharded_moe.py`` (``top1gating`` :184,
``top2gating`` :291, ``topkgating`` :375, ``TopKGate`` :452). The reference
builds the same GShard-style dense dispatch/combine tensors; here the whole
gate is a handful of jnp ops with **static capacity** (shape-stable under jit —
XLA requirement, SURVEY.md §7 "Dynamic shapes").

Conventions (GShard/Switch):
* capacity C = max(min_capacity, ceil(T * k * capacity_factor / E))
* choices beyond an expert's capacity are dropped (token falls through the
  residual connection — same semantics as the reference with drop_tokens=True)
* aux (load-balancing) loss = E * Σ_e mean_t(gate_prob_e) * mean_t(mask1_e),
  the Switch/GShard l_aux over the FIRST choice (reference :269).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    combine: jax.Array    # [T, E, C] fp32 — combine weights
    dispatch: jax.Array   # [T, E, C] bool — dispatch mask
    aux_loss: jax.Array   # scalar fp32 — load-balancing loss
    probs: jax.Array      # [T, E] fp32 — softmax gate probabilities
    counts: jax.Array     # [E] int32 — tokens routed per expert (pre-capacity)


class IndexGateOutput(NamedTuple):
    """Index-form gate for the dropless (sort + ragged matmul) dispatch —
    no [T,E,C] one-hot tensors, just who-goes-where and with what weight."""
    weights: jax.Array    # [T, k] fp32 — combine weights per choice
    experts: jax.Array    # [T, k] int32 — selected expert per choice
    aux_loss: jax.Array   # scalar fp32 — load-balancing loss
    probs: jax.Array      # [T, E] fp32 — gate probabilities


def gate_capacity(num_tokens: int, num_experts: int, k: int,
                  capacity_factor: float, min_capacity: int = 4) -> int:
    cap = int(math.ceil(num_tokens * k * capacity_factor / num_experts))
    return max(min_capacity, cap)


def _group_limited_mask(sel: jax.Array, n_group: int, topk_group: int
                        ) -> jax.Array:
    """DeepSeek-V3 node-limited routing (HF ``DeepseekV3TopkRouter.
    get_topk_indices``): score each group by the sum of its top-2 selection
    scores, keep the best ``topk_group`` groups, zero the rest."""
    T, E = sel.shape
    g = sel.reshape(T, n_group, E // n_group)
    group_scores = jnp.sum(jax.lax.top_k(g, 2)[0], axis=-1)        # [T, G]
    thresh = jax.lax.top_k(group_scores, topk_group)[0][:, -1:]     # [T, 1]
    group_mask = (group_scores >= thresh).astype(sel.dtype)         # [T, G]
    return (g * group_mask[:, :, None]).reshape(T, E)


def _gate_scores(logits: jax.Array, score_func: str,
                 select_bias: Optional[jax.Array], n_group: int,
                 topk_group: int, rng: Optional[jax.Array],
                 noise_std: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared gate math → (gate_source [T,E], probs [T,E], sel_logits [T,E]).

    ``gate_source`` feeds combine weights; ``sel_logits`` feeds SELECTION only
    (bias / group limitation / noise never leak into combine weights)."""
    logits = logits.astype(jnp.float32)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = scores / jnp.maximum(
            jnp.sum(scores, axis=-1, keepdims=True), 1e-9)
        gate_source = scores
    elif score_func == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_source = probs
    else:
        raise ValueError(f"score_func must be softmax|sigmoid, got {score_func!r}")
    sel_logits = logits
    if select_bias is not None or n_group > 1:
        sel = gate_source
        if select_bias is not None:
            sel = sel + select_bias.astype(jnp.float32)[None, :]
        if n_group > 1:
            sel = _group_limited_mask(sel, n_group, topk_group)
        sel_logits = sel
    if noise_std > 0.0 and rng is not None:
        # reference top1gating noisy_gate_policy='RSample' analog
        sel_logits = sel_logits + jax.random.normal(rng, logits.shape) * noise_std
    return gate_source, probs, sel_logits


def _normalized(gates: jax.Array, total: jax.Array, eps: float) -> jax.Array:
    """A token's chosen scores over their sum. Published routers guard the
    division differently: ``eps`` 0 divides by ``max(sum, 1e-9)``, a
    value by ``sum + eps`` (``lfm2_moe``: 1e-6)."""
    return gates / (total + eps if eps else jnp.maximum(total, 1e-9))


def _iter_topk(sel_logits: jax.Array, gate_source: jax.Array, k: int):
    """Iterative argmax top-k (k small + static — unrolled).
    Returns (gates_list: k×[T], idx_list: k×[T] int32, masks: k×[T,E])."""
    masked = sel_logits
    gates_list, idx_list, masks = [], [], []
    E = sel_logits.shape[-1]
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                    # [T]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # [T, E]
        gates_list.append(jnp.sum(gate_source * mask, axis=-1))  # [T]
        idx_list.append(idx.astype(jnp.int32))
        masks.append(mask)
        masked = jnp.where(mask.astype(bool), -jnp.inf, masked)
    return gates_list, idx_list, masks


def _aux_loss(probs: jax.Array, mask1: jax.Array) -> jax.Array:
    """Switch/GShard l_aux over the FIRST choice (reference :269)."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask1, axis=0)
    return jnp.sum(me * ce) * probs.shape[-1]


def topk_gating_indices(logits: jax.Array, k: int = 2,
                        rng: Optional[jax.Array] = None,
                        noise_std: float = 0.0,
                        normalize: bool = True,
                        score_func: str = "softmax",
                        select_bias: Optional[jax.Array] = None,
                        n_group: int = 1, topk_group: int = 1,
                        normalize_eps: float = 0.0) -> IndexGateOutput:
    """Index-form top-k gate for DROPLESS dispatch — identical selection math
    to :func:`topk_gating` but no capacity and no [T,E,C] tensors.

    Since nothing is dropped, ``normalize`` renormalizes the k selected scores
    directly (same value the dense path produces when capacity is generous).
    """
    gate_source, probs, sel_logits = _gate_scores(
        logits, score_func, select_bias, n_group, topk_group, rng, noise_std)
    gates_list, idx_list, masks = _iter_topk(sel_logits, gate_source, k)
    aux = _aux_loss(probs, masks[0])
    gates = jnp.stack(gates_list, axis=1)                    # [T, k]
    experts = jnp.stack(idx_list, axis=1)                    # [T, k]
    if normalize:
        gates = _normalized(gates, jnp.sum(gates, axis=1, keepdims=True),
                            normalize_eps)
    return IndexGateOutput(gates, experts, aux, probs)


def topk_gating(logits: jax.Array, k: int = 2, capacity_factor: float = 1.25,
                min_capacity: int = 4,
                rng: Optional[jax.Array] = None,
                noise_std: float = 0.0,
                normalize: bool = True,
                score_func: str = "softmax",
                select_bias: Optional[jax.Array] = None,
                n_group: int = 1, topk_group: int = 1,
                normalize_eps: float = 0.0) -> GateOutput:
    """Generic top-k gate (k=1 → top1gating, k=2 → top2gating semantics).

    ``score_func``: 'softmax' (GShard/Mixtral/Qwen-MoE) or 'sigmoid'
    (DeepSeek-V3-style: per-expert sigmoid affinities; ``normalize``
    renormalizes the selected scores to sum 1). The aux loss always uses a
    distribution over experts (sigmoid scores are sum-normalized for it).

    DeepSeek-V3 extras: ``select_bias`` [E] (e_score_correction_bias —
    biases expert SELECTION only; combine weights stay the raw scores) and
    ``n_group``/``topk_group`` node-limited routing (selection restricted to
    the best groups).
    """
    T, E = logits.shape
    C = gate_capacity(T, E, k, capacity_factor, min_capacity)
    gate_source, probs, sel_logits = _gate_scores(
        logits, score_func, select_bias, n_group, topk_group, rng, noise_std)

    combine = jnp.zeros((T, E, C), jnp.float32)
    counts_total = jnp.zeros((E,), jnp.int32)
    gates_list, idx_list, masks = _iter_topk(sel_logits, gate_source, k)
    aux = _aux_loss(probs, masks[0])

    # capacity assignment in choice-priority order (1st choices fill first)
    denom = jnp.zeros((T,), jnp.float32)
    per_choice = []
    for i in range(k):
        mask = masks[i]
        locations = jnp.cumsum(mask, axis=0) - 1 + counts_total[None, :].astype(jnp.float32)
        counts_total = counts_total + jnp.sum(mask, axis=0).astype(jnp.int32)
        keep = (locations < C) & (mask > 0)
        mask = jnp.where(keep, mask, 0.0)
        gate_i = gates_list[i] * jnp.sum(mask, axis=-1)      # zero if dropped
        denom = denom + gate_i
        per_choice.append((mask, locations, gates_list[i]))

    for mask, locations, gate_raw in per_choice:
        gate = _normalized(gate_raw, denom, normalize_eps) if normalize \
            else gate_raw
        loc_oh = jax.nn.one_hot(locations.astype(jnp.int32), C, dtype=jnp.float32)
        combine = combine + gate[:, None, None] * mask[:, :, None] * loc_oh

    dispatch = combine > 0.0
    counts = jnp.sum(masks[0], axis=0).astype(jnp.int32)
    return GateOutput(combine, dispatch, aux, probs, counts)


def top1_gating(logits: jax.Array, capacity_factor: float = 1.0,
                min_capacity: int = 4, rng: Optional[jax.Array] = None,
                noise_std: float = 0.0) -> GateOutput:
    """Switch-transformer gate (reference ``top1gating`` :184)."""
    return topk_gating(logits, k=1, capacity_factor=capacity_factor,
                       min_capacity=min_capacity, rng=rng, noise_std=noise_std,
                       normalize=False)


def top2_gating(logits: jax.Array, capacity_factor: float = 1.0,
                min_capacity: int = 4) -> GateOutput:
    """GShard top-2 gate (reference ``top2gating`` :291)."""
    return topk_gating(logits, k=2, capacity_factor=capacity_factor,
                       min_capacity=min_capacity, normalize=True)
