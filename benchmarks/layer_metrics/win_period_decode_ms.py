"""Mean period of the window's decode ticks (end of one ``tick_commit`` to
the end of the next, ``fastgen_tick_period_seconds{kind="decode"}``: sum
over count, exact). ``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_period_decode_ms")
