"""Milliseconds of garbage collection a second of the window
(``span_seconds{span="gc_pause"}``: every generation's pauses).
``window_account.py`` has the account.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "gc_pause_ms_per_s")
