"""Median device time of the tick program in traced ticks that held prompt
rows.
"""
from benchmarks import readers


def read(run):
    rows = readers.traced_ticks(run)
    if not rows:
        return None
    return readers.pct_ms([r["device"] for r in rows if r["mixed"]], 50)
