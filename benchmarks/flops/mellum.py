"""Model FLOPs per trained token of the ``mellum`` family (the Qwen3-MoE
block over window and full attention layers), from its shapes, for ONE
CHIP of a group that shares each layer: only the parameters that take part
in a matmul *for a given token on this chip* count. The four attention
projections, the router (over its whole width) and the output head's slice
multiply every token; of a token's ``moe_top_k`` experts this chip holds
``n_experts / router_experts`` of them on average, so ``moe_top_k x
n_experts / router_experts`` experts' matrices (2 of the model's 8 where 16
of 64 are held; all 8 where every expert is). Counting the model's eight
for a share would read ``train_mfu_pct`` four times too high on the expert
part, and could cross 100 %. The input embedding is a gather. Attention:
scores and values are ``2 * heads * head_dim`` each per attended position,
a full layer's mean ``seq_len / 2``, a window layer's capped at the window.
Recomputation is not counted.
"""
from __future__ import annotations


def experts_met(model) -> float:
    """Experts whose matrices a token multiplies ON THIS CHIP, a layer."""
    return model.moe_top_k * model.n_experts / model.router_experts


def active_matmul_params(model) -> float:
    h = model.hidden_size
    q, kv = model.num_heads * model.head_dim, model.kv_heads * model.head_dim
    attn = h * q + 2 * h * kv + q * h
    layer = attn + h * model.router_experts \
        + 3 * h * model.moe_ffn * experts_met(model)
    return model.num_layers * layer + model.vocab_size * h


def attended_positions(model, seq_len: int) -> float:
    """Mean positions a token scores, summed over the layers."""
    full = seq_len / 2.0
    kinds = model.layer_kinds or ("full",) * model.num_layers
    return sum(min(model.attn_window, full) if k == "window" else full
               for k in kinds)


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (every expert held) is not what a
    token meets and is ignored."""
    attn = 4.0 * model.num_heads * model.head_dim \
        * attended_positions(model, seq_len)
    return 6.0 * active_matmul_params(model) + 3.0 * attn
