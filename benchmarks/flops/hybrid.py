"""Model FLOPs per trained token of a decoder whose layers are of more than
one kind (the ``phi4flash`` family: state-space, windowed attention, one
full-attention layer, gated memory units, cross attention), from its
shapes: the parameters a token meets in a matmul, by kind of layer, plus
what attention and the state-space recurrence do per token beyond their
projections. Recomputation is not counted.

No training cell runs such a configuration (3.85 B parameters at 16 B each
do not fit a four-chip host); the file is named by the configuration so
that a cell that will has its counter, and a test holds it to a count by
hand.
"""
from __future__ import annotations

from typing import Dict


def mixer_matmul_params(model) -> Dict[str, int]:
    """Matrix entries a token multiplies in one layer's mixer, by kind."""
    h, di = model.hidden_size, model.ssm_inner
    n, r, d = model.ssm_state, model.ssm_dt_rank, model.head_dim
    q, kv = model.num_heads * d, model.kv_heads * d
    attn = h * q + 2 * h * kv + q * h
    return {"mamba": h * 2 * di + di * (r + 2 * n) + r * di + di * h,
            "gmu": 2 * h * di,
            "window": attn, "full": attn,
            "cross": h * q + q * h}


def active_matmul_params(model) -> int:
    """Every layer's mixer and MLP, and the output head (the input
    embedding is a gather)."""
    per = mixer_matmul_params(model)
    mlp = 3 * model.hidden_size * model.ffn_size
    return model.vocab_size * model.hidden_size + sum(
        per[kind] + mlp for kind in model.layer_kinds)


def attended_positions(model, seq_len: int) -> Dict[str, float]:
    """Mean cache positions a token's attention layer scores, by kind."""
    full = seq_len / 2.0
    return {"window": min(model.attn_window, full), "full": full,
            "cross": full}


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward. Attention: scores and values are ``2 * heads *
    head_dim`` each per attended position (differential attention's four
    products are two softmaxes over half the heads with values twice as
    wide: the same count). The recurrence: per token ``inner * state``
    multiply-adds for the state's update, its decay and its read-out.
    ``n_params`` is not what a token meets (the tied embedding counts
    once, as the head) and is ignored."""
    seen = attended_positions(model, seq_len)
    attn = sum(4.0 * model.num_heads * model.head_dim * seen[k]
               for k in model.layer_kinds if k in seen)
    scan = sum(2.0 * 3 * model.ssm_inner * model.ssm_state
               for k in model.layer_kinds if k == "mamba")
    return 6.0 * active_matmul_params(model) + 3.0 * (attn + scan)
