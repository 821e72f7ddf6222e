"""The frontend's own share of a tick of the window:
``serving_loop_seconds_total{part="tick"}`` less the engine's six phases
(breaker, chaos points, roll-back snapshot, harvest).
``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_frontend_ms")
