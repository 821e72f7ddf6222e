"""What the indexer's scores need (``ops/pallas/index_scores.py``; its
Mosaic call is named ``index_scores`` in the trace, one call a ``sparse``
layer a tick).

The need is the model's, whatever implements it: a walk (a decode row, or a
chunk's rows, which share their sequence's positions) reads its sequence's
index keys once, ``index_head_dim`` values a position in the served type
(128 B at the published widths: what the model keeps, not the 256 B row the
store pads it to), and a row scores each of its positions with ``2 x
index_heads x index_head_dim`` operations (2,048). The program writes both
sums on the tick's span (``decode_tick``'s ``index_walk_positions`` and
``index_positions``, each over the tick's real rows times the sparse
layers) and ``tick_attrs`` joins the span to the tick's run on the device,
so a call's need is its own tick's: the larger of the two times, tick by
tick. The weighted sum over the heads (2 x heads a pair), the queries and
the scores written out are left out: a lower bound. A program without the
attributes, or without a call of the name, gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

NAME = "index_scores"


def classify(op) -> Optional[str]:
    return "index" if op.is_mosaic and op.name.startswith(NAME) else None


def key_bytes(model) -> int:
    """A position's index key in one layer, as the model keeps it."""
    return model.index_head_dim * model.compute_dtype.itemsize


def pair_ops(model) -> float:
    """A (row, position) pair's products."""
    return 2.0 * model.index_heads * model.index_head_dim


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    m = run.model
    ticks = [(t, its) for t, its in tick_attrs.calls_by_tick(
        tick_attrs.per_tick(run), calls) if "index_walk_positions" in t]
    if not calls or not ticks or not getattr(m, "index_heads", 0):
        return None
    total, by_compute = 0.0, 0.0
    for t, its in ticks:
        # the span's sums are over every sparse layer: a call's share
        part = len(its) / float(t["sparse_layers"])
        mem = part * t["index_walk_positions"] * key_bytes(m) \
            / run.peaks["hbm_bytes_per_s"]
        mxu = part * t["index_positions"] * pair_ops(m) \
            / run.peaks["bf16_flops_per_s"]
        total += max(mem, mxu)
        by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
