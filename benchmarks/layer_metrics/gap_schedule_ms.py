"""Host time between two ticks spent packing the next one: the part of the
device's gap under ``schedule_tick`` (median over the traced ticks).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_schedule_ms")
