"""Host time between two ticks under ``tick_commit``: the sampled tokens
noted per row, finished sequences released, counters and gauges (median
over the traced ticks).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_commit_ms")
