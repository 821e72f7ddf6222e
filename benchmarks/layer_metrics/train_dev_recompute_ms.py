"""Device time a step spends recomputing checkpointed blocks in the backward
pass: leaf operations of chip 0 under ``rematted_computation`` (median
over the traced steps).
``gap_chain.py`` says how an operation finds its phase.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "train_dev_recompute_ms")
