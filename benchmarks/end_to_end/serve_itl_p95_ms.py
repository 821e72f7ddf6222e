"""95th percentile of the gaps between consecutive visible tokens of one
request, every request pooled, gaps that ended inside the window.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms(readers.window_gaps_s(run), 95)
