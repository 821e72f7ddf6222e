"""Device time a step spends in the forward pass: leaf operations of chip 0
whose name stack lies under ``loss_and_grads`` and in neither the backward
pass nor a recompute (median over the traced steps).
``gap_chain.py`` says how an operation finds its phase.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "train_dev_fwd_ms")
