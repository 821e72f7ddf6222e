"""Mean period of the window's ticks that held prompt rows
(``fastgen_tick_period_seconds{kind="mixed"}``: sum over count, exact).
``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_period_mixed_ms")
