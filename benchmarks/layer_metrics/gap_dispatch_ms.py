"""Host time between two ticks (steps) from the start of ``tick_dispatch``
(``train_step``) to the enqueue of the program: the copies to the device
and the jitted call's way down to the runtime (median over the traced
ticks or steps).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_dispatch_ms")
