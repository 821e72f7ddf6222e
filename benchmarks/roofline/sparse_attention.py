"""What attention over the CHOSEN positions needs, in a ``sparse`` layer
(``models/paged.py::_sparse_mixer``'s third part: the operations under the
program's ``sparse`` scope, among them the Mosaic call ``sparse_attention``,
``ops/pallas/paged_attention.py``'s kernel with the choice as a mask).

The need is the model's, whatever implements it: a row attends to the
positions its indexer chose, ``min(length, sparse_topk)`` of them, and to
no other. A decode row reads each chosen position's keys and values once
(``2 x kv_heads x head_dim`` values: 2,048 B at the published widths); a
chunk's row scores and weighs each chosen position with ``4 x heads x
head_dim`` operations (16,384). The program writes both sums on the tick's
span (``sparse_selected_decode`` and ``sparse_selected`` less it, over the
tick's real rows times the sparse layers) and ``tick_attrs`` joins the span
to the tick's run, so need and time come from the same ticks: the larger of
the two times, tick by tick. A form that walks every position of a
sequence with the choice as a mask therefore reads LOW, by about the share
of its positions a row chose: that is the point of the number. The
operations are found by their scope and not by a kernel's name, so that a
form that gathers (plain XLA beside a kernel) is read by the same file. A
program without the scope or the attributes gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

SCOPE = "sparse"


def scoped_texts(run) -> set:
    from benchmarks import gap_chain

    if "sparse_texts" not in run.cache:
        path = gap_chain.trace_file(run) if run.trace is not None else None
        run.cache["sparse_texts"] = set() if path is None else {
            text for (_, text), s in gap_chain.op_scopes(path).items()
            if f"/{SCOPE}/" in s or s.endswith(f"/{SCOPE}")}
    return run.cache["sparse_texts"]


def calls(run) -> List:
    """The operations of the traced window under the scope."""
    tr, texts = run.trace, scoped_texts(run)
    if tr is None or not texts:
        return []
    return [o for c in tr.chips for o in tr.ops_in_window(c)
            if o.text in texts]


def position_bytes(model) -> int:
    """A position's keys and values in one layer."""
    return 2 * model.kv_heads * model.head_dim * model.compute_dtype.itemsize


def pair_ops(model) -> float:
    """A (row, chosen position) pair's scores and values."""
    return 4.0 * model.num_heads * model.head_dim


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    m = run.model
    ticks = [t for t in tick_attrs.per_tick(run) if "sparse_selected" in t]
    if not calls or not ticks or not getattr(m, "sparse_topk", 0):
        return None
    total, by_compute = 0.0, 0.0
    for t in ticks:
        decode = t["sparse_selected_decode"]
        mem = decode * position_bytes(m) / run.peaks["hbm_bytes_per_s"]
        mxu = (t["sparse_selected"] - decode) * pair_ops(m) \
            / run.peaks["bf16_flops_per_s"]
        total += max(mem, mxu)
        by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
