"""Of the positions the indexer scored in the ticks of the traced stretch,
the share the rows then attended to (the ``decode_tick`` span's
``sparse_selected`` over its ``index_positions``, summed over the ticks
joined to their runs, ``roofline/tick_attrs.py``): how much the choice
cuts. 100 where no row is longer than ``sparse_topk``. Nothing to read
where the program's span has no such attributes.

A property of the traffic's lengths, not a number to improve: the manifest
wants a direction (``lower``: the more is cut, the less an exact form
reads) and an end-to-end metric it ``moves``; judge no PR by either.
"""
from benchmarks.roofline import tick_attrs


def read(run):
    ticks = [t for t in tick_attrs.per_tick(run) if "sparse_selected" in t]
    scored = sum(t["index_positions"] for t in ticks)
    return 100.0 * sum(t["sparse_selected"] for t in ticks) / scored \
        if scored > 0 else None
