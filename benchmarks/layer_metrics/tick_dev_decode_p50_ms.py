"""Median device time of the tick program in traced ticks that held only
decode rows.
"""
from benchmarks import readers


def read(run):
    rows = readers.traced_ticks(run)
    if not rows:
        return None
    return readers.pct_ms([r["device"] for r in rows if not r["mixed"]], 50)
