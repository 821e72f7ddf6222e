"""Model FLOPs per trained token of a decoder with sparse experts and
latent attention (the ``deepseek_v3`` family), from its shapes: only the
parameters that take part in a matmul *for a given token* count, so of a
layer's routed experts the ``moe_top_k`` a token is sent to, beside the
shared experts, the router, the attention projections, the leading dense
layers and the output head (the input embedding is a gather). Attention as
``flops/dense.py`` counts it, at the widths latent attention computes with
when the latent is expanded per head (training: ``qk_nope + qk_rope`` for
the scores, ``v_head_dim`` for the values). Recomputation is not counted.

No training cell runs such a configuration yet (ROADMAP R1); the file is
named by the configuration so that the cell that will has its counter, and
a test holds it to a count by hand.
"""
from __future__ import annotations


def active_matmul_params(model) -> int:
    """Parameters that multiply every token, by ``model``'s segments
    (``TransformerConfig.segments``: leading dense layers, expert layers)."""
    h = model.hidden_size
    total = model.vocab_size * h                         # the output head
    for _, seg in model.segments:
        n = seg.num_heads
        attn = (h * n * (seg.qk_nope_head_dim + seg.qk_rope_head_dim)
                + h * (seg.kv_lora_rank + seg.qk_rope_head_dim)
                + seg.kv_lora_rank * n * (seg.qk_nope_head_dim
                                          + seg.v_head_dim)
                + n * seg.v_head_dim * h)
        if seg.n_experts:
            ffn = 3 * h * (seg.moe_top_k * seg.moe_ffn + seg.moe_shared_size) \
                + h * seg.n_experts
        else:
            ffn = 3 * h * seg.ffn_size
        total += seg.num_layers * (attn + ffn)
    return total


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts) is not what a token
    meets and is ignored."""
    attn = 3 * model.num_layers * model.num_heads * seq_len * (
        model.qk_nope_head_dim + model.qk_rope_head_dim + model.v_head_dim)
    return 6.0 * active_matmul_params(model) + attn
