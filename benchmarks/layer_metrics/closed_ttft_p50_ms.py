"""Closed loop: median time from a client's previous completion to its next
request's first visible token. Must not decide a PR.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms(readers.ttfts_s(run), 50)
