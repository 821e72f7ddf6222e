"""Model FLOPs per trained token of the ``afmoe`` family (grouped-query
attention with an output gate under window and full layers, a leading run
of dense layers, then routed experts beside a shared one), from its
shapes: only the parameters that take part in a matmul *for a given token*
count, so of a layer's routed experts the ``moe_top_k`` a token is sent
to, beside the shared expert, the router (over its whole width), the four
attention projections and the gate's, the leading dense layers and the
output head (the input embedding is a gather). Attention: scores and
values are ``2 * heads * head_dim`` each per attended position, a window
layer's positions capped at the window. Recomputation is not counted.

Where the program holds a SHARE of the experts (``moe_router_experts``),
a token's ``moe_top_k`` experts are the model's: what one chip of the
deployment multiplies is the share's part of that, which a training cell
for a share would have to say; none exists (one expert layer's share is
16 GB of training state). The file is named by the configuration so that a
cell that will has its counter, and a test holds it to a count by hand.
"""
from __future__ import annotations


def active_matmul_params(model) -> int:
    """Parameters that multiply every token, by ``model``'s segments
    (``TransformerConfig.segments``: leading dense layers, expert layers)."""
    h = model.hidden_size
    q, kv = model.num_heads * model.head_dim, model.kv_heads * model.head_dim
    attn = h * q + 2 * h * kv + q * h + (h * q if model.attn_gate else 0)
    total = model.vocab_size * h                         # the output head
    for _, seg in model.segments:
        if seg.n_experts:
            ffn = 3 * h * (seg.moe_top_k * seg.moe_ffn + seg.moe_shared_size) \
                + h * seg.router_experts
        else:
            ffn = 3 * h * seg.ffn_size
        total += seg.num_layers * (attn + ffn)
    return total


def attended_positions(model, seq_len: int) -> float:
    """Mean cache positions a token scores, summed over the layers."""
    full = seq_len / 2.0
    kinds = model.layer_kinds or ("full",) * model.num_layers
    return sum(min(model.attn_window, full) if k == "window" else full
               for k in kinds)


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored."""
    attn = 4.0 * model.num_heads * model.head_dim \
        * attended_positions(model, seq_len)
    return 6.0 * active_matmul_params(model) + 3.0 * attn
