"""The caller's share of a tick of the window
(``serving_loop_seconds_total{part="caller"}``: from ``run_tick``'s return
to its next entry while a request is active): here the load generator's
submits, its reads of new tokens and its log.
``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_caller_ms")
