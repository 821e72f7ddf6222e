"""The engine's own host time a tick of the window: ``schedule + pack +
dispatch + overlap + commit`` of ``fastgen_tick_phase_seconds_total``.
``window_account.py`` says how a period is split.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "win_engine_host_ms")
