"""Host time between two ticks (steps) under no span of the program: the
caller's own loop, and in training the caller's iterator
(``train_batch_fetch``) (median over the traced ticks or steps).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_client_ms")
