"""Model FLOPs per trained token of the ``lfm2_moe`` family (gated
short-convolution layers among grouped-query attention layers, a leading
run of dense layers, then routed experts without a shared one), from its
shapes: only the parameters that take part in a matmul *for a given token*
count, by kind of layer: a ``conv`` layer's in and out projections (its
three taps a channel are elementwise: counted beside attention's scores,
not among the matrices), an attention layer's four projections, a dense
layer's SwiGLU, of an expert layer's experts the ``moe_top_k`` a token is
sent to beside the router, and the output head (the input embedding is a
gather; tied, it counts once, as the head). Attention: scores and values
are ``2 * heads * head_dim`` each per attended position. Recomputation is
not counted.

No training cell runs this configuration (ISSUE 37: its mechanisms do
their work in serving); the file is named by the configuration so that a
cell that will has its counter, and a test holds it to a count by hand.
"""
from __future__ import annotations

from typing import Dict


def mixer_matmul_params(model) -> Dict[str, int]:
    """Matrix entries a token multiplies in one layer's mixer, by kind."""
    h = model.hidden_size
    q, kv = model.num_heads * model.head_dim, model.kv_heads * model.head_dim
    return {"conv": 3 * h * h + h * h,
            "full": h * q + 2 * h * kv + q * h}


def active_matmul_params(model) -> int:
    """Every layer's mixer and the FFN a token meets in it, by
    ``model``'s segments (``TransformerConfig.segments``: leading dense
    layers, expert layers), and the output head."""
    h, per = model.hidden_size, mixer_matmul_params(model)
    total = model.vocab_size * h
    for _, seg in model.segments:
        ffn = 3 * h * seg.moe_top_k * seg.moe_ffn + h * seg.router_experts \
            if seg.n_experts else 3 * h * seg.ffn_size
        total += sum(per[kind] + ffn for kind in seg.layer_kinds)
    return total


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored. Beyond the matrices: an attention layer's
    scores and values over ``seq_len / 2`` positions on average, a
    ``conv`` layer's taps and two gates a channel."""
    kinds = model.layer_kinds
    attn = 4.0 * model.num_heads * model.head_dim * (seq_len / 2.0) \
        * kinds.count("full")
    conv = 2.0 * (model.conv_taps + 2) * model.hidden_size \
        * kinds.count("conv")
    return 6.0 * active_matmul_params(model) + 3.0 * (attn + conv)
