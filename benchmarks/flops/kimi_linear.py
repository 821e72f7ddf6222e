"""Model FLOPs per trained token of the ``kimi_linear`` family (delta-rule
linear-attention layers among latent-attention layers, one leading dense
layer, then routed experts beside a shared one), from its shapes: only the
parameters that take part in a matmul *for a given token* count, by kind of
layer: a ``kda`` layer's three projections in, its two low-rank gates, the
step-size projection and the projection out (its taps, decays and norms are
elementwise); a ``latent`` layer's query, latent, expansion and output
projections; a dense layer's SwiGLU; of an expert layer's experts the
``moe_top_k`` a token is sent to beside the shared expert and the router;
and the output head (the input embedding is a gather). Beyond the
matrices: the delta rule a token a head (decay, ``S^T k``, the rank-one
update, ``S^T q``: ``8 D^2``), a latent layer's scores and values over
``seq_len / 2`` positions on average. Recomputation is not counted.

No training cell runs this configuration (ISSUE 41: the delta rule's
backward has no kernel here); the file is named by the configuration so
that a cell that will has its counter, and a test holds it to a count by
hand.
"""
from __future__ import annotations

from typing import Dict


def mixer_matmul_params(model) -> Dict[str, int]:
    """Matrix entries a token multiplies in one layer's mixer, by kind."""
    h = model.hidden_size
    w, r = model.kda_heads * model.kda_head_dim, model.kda_rank
    n = model.num_heads
    dn, dr, dv = (model.qk_nope_head_dim, model.qk_rope_head_dim,
                  model.v_head_dim)
    return {"kda": 3 * h * w + 2 * (h * r + r * w) + h * model.kda_heads
            + w * h,
            "latent": h * n * (dn + dr) + h * (model.kv_lora_rank + dr)
            + model.kv_lora_rank * n * (dn + dv) + n * dv * h}


def active_matmul_params(model) -> int:
    """Every layer's mixer and the FFN a token meets in it, by ``model``'s
    segments (``TransformerConfig.segments``: the leading dense layer, the
    expert layers), and the output head."""
    h, per = model.hidden_size, mixer_matmul_params(model)
    total = model.vocab_size * h
    for _, seg in model.segments:
        ffn = 3 * h * (seg.moe_top_k * seg.moe_ffn + seg.moe_shared_size) \
            + h * seg.router_experts if seg.n_experts \
            else 3 * h * seg.ffn_size
        total += sum(per[kind] + ffn for kind in seg.layer_kinds)
    return total


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored."""
    kinds = model.layer_kinds
    rule = 8.0 * model.kda_heads * model.kda_head_dim ** 2 \
        * kinds.count("kda")
    attn = 2.0 * model.num_heads * (
        model.qk_nope_head_dim + model.qk_rope_head_dim + model.v_head_dim) \
        * (seq_len / 2.0) * kinds.count("latent")
    return 6.0 * active_matmul_params(model) + 3.0 * (rule + attn)
