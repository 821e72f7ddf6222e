"""Model FLOPs per trained token of the ``nemotron_h`` family (a stack of
single sublayers: Mamba-2 mixers, grouped-query attention layers and expert
layers whose routed experts work in a latent of the row), from its shapes:
only the parameters that take part in a matmul *for a given token* count,
by kind of layer: an ``M`` layer's projection in and out (its taps, decay,
skip and gated norm are elementwise); a ``*`` layer's four projections; of
an ``E`` layer the router, the two latent projections, the shared expert
and the ``moe_top_k`` routed experts a token is sent to (two matrices each:
not gated); and the output head (the input embedding is a gather). Beyond
the matrices: the recurrence a token a head (decay, the rank-one update,
the read-out: ``6 P N``), an attention layer's scores and values over
``seq_len / 2`` positions on average. Recomputation is not counted.

No training cell runs this configuration (ISSUE 53: it fits no cut at 16
bytes a parameter, and ``mamba2`` has no backward kernel here); the file is
named by the configuration so that a cell that will has its counter, and a
test holds it to a count by hand.
"""
from __future__ import annotations

from typing import Dict


def layer_matmul_params(model) -> Dict[str, int]:
    """Matrix entries a token multiplies in one layer, by kind."""
    h = model.hidden_size
    nh, p = model.mamba2_heads, model.mamba2_head_dim
    di, bc = nh * p, 2 * model.mamba2_groups * model.mamba2_state
    qdim, kv = model.num_heads * model.head_dim, \
        model.kv_heads * model.head_dim
    lat = model.moe_latent_size or h
    return {"mamba2": h * (2 * di + bc + nh) + di * h,
            "full": 2 * h * qdim + 2 * h * kv,
            "ffn": h * model.router_experts
            + (2 * h * lat if model.moe_latent_size else 0)
            + 2 * h * model.moe_shared_size
            + model.moe_top_k * 2 * lat * model.moe_ffn}


def active_matmul_params(model) -> int:
    """Every layer's one sublayer as a token meets it, and the head."""
    per = layer_matmul_params(model)
    return model.vocab_size * model.hidden_size \
        + sum(per[kind] for kind in model.layer_kinds)


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored."""
    kinds = model.layer_kinds
    scan = 6.0 * model.mamba2_heads * model.mamba2_head_dim \
        * model.mamba2_state * kinds.count("mamba2")
    attn = 4.0 * model.num_heads * model.head_dim * (seq_len / 2.0) \
        * kinds.count("full")
    return 6.0 * active_matmul_params(model) + 3.0 * (scan + attn)
