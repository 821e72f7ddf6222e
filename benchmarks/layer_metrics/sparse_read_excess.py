"""Positions the attention's form met for the rows of the traced stretch's
ticks over the positions those rows chose (the ``decode_tick`` span's
``sparse_positions_read`` over its ``sparse_selected``, summed over the
ticks joined to their runs, ``roofline/tick_attrs.py``): 1.0 where every
row reads what it chose (a gather), a sequence's length over
``sparse_topk`` where a row walks all of its sequence under a mask.
Nothing to read where the program's span has no such attributes.
"""
from benchmarks.roofline import tick_attrs


def read(run):
    ticks = [t for t in tick_attrs.per_tick(run) if "sparse_selected" in t]
    chosen = sum(t["sparse_selected"] for t in ticks)
    return sum(t["sparse_positions_read"] for t in ticks) / chosen \
        if chosen > 0 else None
