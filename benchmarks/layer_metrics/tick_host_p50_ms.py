"""Median, over the traced ticks, of the ``serving_tick`` span's wall time
minus the device time of the tick's program: what the host adds to a tick.
"""
from benchmarks import readers


def read(run):
    rows = readers.traced_ticks(run)
    if not rows:
        return None
    return readers.pct_ms([r["wall"] - r["device"] for r in rows], 50)
