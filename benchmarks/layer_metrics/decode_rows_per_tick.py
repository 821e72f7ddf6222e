"""Median number of rows that produced a token in a tick of the window
(increase of ``fastgen_generated_tokens_total`` across the tick).
"""
from benchmarks import readers, stats


def read(run):
    ticks = readers.window_ticks(run)
    return stats.percentile([t[3] for t in ticks], 50) if ticks else None
