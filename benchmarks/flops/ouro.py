"""Model FLOPs per trained token of the ``ouro`` family (a LOOPED dense
decoder: the stack's layers run ``loop_passes`` times over the same
weights), from its shapes: ``dense``'s count with every layer met that many
times. The parameters that take part in a matmul for a token are the
layers' matrices once a PASS and the output head once (the input embedding
is a gather; the exit gate's ``hidden`` products a pass are left out, as
the norms are); a layer's scores and values over ``seq_len / 2`` positions
on average, a pass. Recomputation is not counted.

No training cell runs this configuration (ISSUE 55: one chip holds 9 of 48
layers at 16 bytes a parameter, and the published objective cannot be
written down from the catalog); the file is named by the configuration so
that a cell that will has its counter, and a test holds it to a count by
hand.
"""
from __future__ import annotations


def layer_matmul_params(model) -> int:
    """Matrix entries a token multiplies in ONE application of a layer."""
    h = model.hidden_size
    qdim, kv = model.num_heads * model.head_dim, \
        model.kv_heads * model.head_dim
    mats = 3 if model.activation == "swiglu" else 2
    return 2 * h * qdim + 2 * h * kv + mats * h * model.ffn_size


def active_matmul_params(model) -> int:
    """Every layer as often as a token meets it, and the head."""
    return model.loop_passes * model.num_layers * layer_matmul_params(model) \
        + model.vocab_size * model.hidden_size


def train_flops_per_token(model, n_params: int, seq_len: int) -> float:
    """Forward + backward; ``n_params`` counts a layer once and is not
    what a token meets: ignored."""
    attn = 6 * model.loop_passes * model.num_layers \
        * model.num_heads * model.head_dim * seq_len
    return 6.0 * active_matmul_params(model) + attn
