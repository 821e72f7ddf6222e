"""What of the device's gap between two ticks (steps) the host interval does
not account for: completion notice and read-back after the device
finished, launch after the enqueue (median over the traced ticks or
steps).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_runtime_ms")
