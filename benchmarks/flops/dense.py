"""Model FLOPs per trained token of a dense decoder, from its shapes. A
configuration file names its counter (``"flops": "dense"``); a family
this one does not fit (sparse experts: the active share only) brings a
file of its own beside it, with the same function.

Copied from ``bench.py`` ``_flops_per_token`` (6 x N + causal attention,
recomputation under remat NOT counted: model FLOPs, not hardware FLOPs),
with one correction: ``N`` here is the parameters that take part in a
matmul for every token. The untied input embedding is a gather, so its
``vocab x hidden`` table is left out (``bench.py`` counts it).
"""
from __future__ import annotations


def matmul_params(n_params: int, vocab_size: int, hidden_size: int,
                  tied_embeddings: bool) -> int:
    return n_params if tied_embeddings else n_params - vocab_size * hidden_size


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``model`` is the program's model config.
    Attention: QK^T and PV are 2*S*H each per token forward, halved by
    causality, times 3 for forward + backward."""
    n = matmul_params(n_params, model.vocab_size, model.hidden_size,
                      model.tie_embeddings)
    attn = 6 * model.num_layers * model.hidden_size * seq_len
    return 6.0 * n + attn
