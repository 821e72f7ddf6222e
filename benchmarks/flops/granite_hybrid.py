"""Model FLOPs per trained token of the ``granitemoehybrid`` family (a stack
of PAIRED blocks: a Mamba-2 mixer or grouped-query attention, then routed
experts beside a shared gated MLP, in every layer), from its shapes: only
the parameters that take part in a matmul *for a given token* count: a
``mamba2`` mixer's projection in and out (its taps, decay, skip and gated
norm are elementwise); an attention mixer's four projections; in EVERY
layer the router, the shared MLP and the ``moe_top_k`` routed experts a
token is sent to (three matrices each: gated); and the tied output head (the
input embedding is a gather). Beyond the matrices: the recurrence a token a
head (decay, the rank-one update, the read-out: ``6 P N``), an attention
layer's scores and values over ``seq_len / 2`` positions on average.
Recomputation is not counted; the four scalars multiply nothing a matrix
does not.

No training cell runs this configuration (ISSUE 60: it fits no cut at 16
bytes a parameter, and ``mamba2`` has no backward kernel here); the file is
named by the configuration so that a cell that will has its counter, and a
test holds it to a count by hand.
"""
from __future__ import annotations

from typing import Dict


def layer_matmul_params(model) -> Dict[str, int]:
    """Matrix entries a token multiplies in one layer: its mixer's, by
    kind, and (``experts``) its second half's, the same in every layer."""
    h = model.hidden_size
    nh, p = model.mamba2_heads, model.mamba2_head_dim
    di, bc = nh * p, 2 * model.mamba2_groups * model.mamba2_state
    qdim, kv = model.num_heads * model.head_dim, \
        model.kv_heads * model.head_dim
    return {"mamba2": h * (2 * di + bc + nh) + di * h,
            "full": 2 * h * qdim + 2 * h * kv,
            "experts": h * model.router_experts
            + 3 * h * model.moe_shared_size
            + model.moe_top_k * 3 * h * model.moe_ffn}


def active_matmul_params(model) -> int:
    """Every layer's mixer and second half as a token meets them, and the
    head."""
    per = layer_matmul_params(model)
    return model.vocab_size * model.hidden_size \
        + sum(per[kind] + per["experts"] for kind in model.layer_kinds)


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored."""
    kinds = model.layer_kinds
    scan = 6.0 * model.mamba2_heads * model.mamba2_head_dim \
        * model.mamba2_state * kinds.count("mamba2")
    attn = 4.0 * model.num_heads * model.head_dim * (seq_len / 2.0) \
        * kinds.count("full")
    return 6.0 * active_matmul_params(model) + 3.0 * (scan + attn)
