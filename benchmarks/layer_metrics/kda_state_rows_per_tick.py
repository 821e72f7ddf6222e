"""Median over the ticks of the traced stretch of the rows that wrote
delta-rule state: the rows that close a run of their sequence (a decode
row, a prompt chunk's last row), each of which writes its sequence slot's
matrix and convolution inputs in every ``kda`` layer (the ``decode_tick``
span's ``kda_state_rows``, joined to the tick's run on the device as the
rooflines' attributes are, ``roofline/tick_attrs.py``). Nothing to read
where the program's span has no such attribute.
"""
from benchmarks import stats
from benchmarks.roofline import tick_attrs


def read(run):
    rows = [t["kda_state_rows"] for t in tick_attrs.per_tick(run)
            if "kda_state_rows" in t]
    return stats.percentile(rows, 50) if rows else None
