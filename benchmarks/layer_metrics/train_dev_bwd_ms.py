"""Device time a step spends in the backward pass proper: leaf operations of
chip 0 under ``transpose(jvp(..))``, recompute apart (median over the
traced steps).
``gap_chain.py`` says how an operation finds its phase.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "train_dev_bwd_ms")
