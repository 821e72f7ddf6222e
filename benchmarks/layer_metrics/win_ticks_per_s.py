"""Serving ticks a second of the untraced window, by the program's own
count (``fastgen_tick_period_seconds``): runs of one program that differ
end to end differ here first (``window_account.py`` has the account).
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_ticks_per_s")
