"""Closed loop: median gap between visible tokens. Must not decide a PR: a
slower server gives itself less load.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms(readers.window_gaps_s(run), 50)
