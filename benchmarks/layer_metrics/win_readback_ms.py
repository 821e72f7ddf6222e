"""``tick_readback`` a tick of the window
(``fastgen_tick_phase_seconds_total{phase="readback"}``): the wait for the
device, for its runtime's completion and for the copy back.
``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_readback_ms")
