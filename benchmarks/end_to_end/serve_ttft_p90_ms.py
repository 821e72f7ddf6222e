"""Open loop: 90th percentile, over the requests due inside the window, of
the time from the instant a request was due to the end of the tick that
made its first token visible; a failed request is the largest value.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms(readers.ttfts_s(run), 90)
