"""Model FLOPs per trained token of the ``KeyeVL2`` family's language model
(grouped-query attention over the positions a learned indexer chooses,
under routed experts with no shared one), from its shapes: only the
parameters that take part in a matmul *for a given token* count, so of a
layer's routed experts the ``moe_top_k`` a token is sent to, the router
(over its whole width), the four attention projections, the indexer's
three and the output head (the input embedding is a gather). Attention:
scores and values are ``2 * heads * head_dim`` each per ATTENDED position,
and a row attends to ``min(s, sparse_topk)`` of the ``s`` it has; the
indexer scores every one of the ``s``: ``2 * index_heads * index_head_dim``
each. Recomputation is not counted.

Where the program holds a SHARE of the experts (``moe_router_experts``), a
token's ``moe_top_k`` experts are the model's: what one chip of the
deployment multiplies is the share's part of that, which a training cell
for a share would have to say; none exists (the kernels of the choice have
no backward). The file is named by the configuration so that a cell that
will has its counter, and a test holds it to a count by hand.
"""
from __future__ import annotations


def active_matmul_params(model) -> int:
    """Parameters that multiply every token."""
    h = model.hidden_size
    q, kv = model.num_heads * model.head_dim, model.kv_heads * model.head_dim
    attn = h * q + 2 * h * kv + q * h
    index = h * model.index_heads * model.index_head_dim \
        + h * model.index_head_dim + h * model.index_heads
    ffn = 3 * h * model.moe_top_k * model.moe_ffn + h * model.router_experts
    return model.vocab_size * h + model.num_layers * (attn + index + ffn)


def attended_positions(model, seq_len: int) -> float:
    """Mean cache positions a token attends to in one layer: position t
    has ``t + 1`` and attends to ``min(t + 1, topk)``."""
    k = min(model.sparse_topk, seq_len)
    # sum_{t < k} (t + 1) + (seq_len - k) * k, over seq_len
    return (k * (k + 1) / 2.0 + (seq_len - k) * k) / seq_len


def indexed_positions(seq_len: int) -> float:
    """Mean cache positions the indexer scores for a token in one layer."""
    return (seq_len + 1) / 2.0


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` (all experts held) is not what a
    token meets and is ignored."""
    attn = 4.0 * model.num_heads * model.head_dim \
        * attended_positions(model, seq_len)
    index = 2.0 * model.index_heads * model.index_head_dim \
        * indexed_positions(seq_len)
    return 6.0 * active_matmul_params(model) \
        + 3.0 * model.num_layers * (attn + index)
