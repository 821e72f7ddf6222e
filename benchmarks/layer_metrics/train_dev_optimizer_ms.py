"""Device time a step spends outside the model, in the step builder's own
work: leaf operations of chip 0 under ``optimizer`` (``zero_param_update``
inside it), ``grad_reduce`` or ``grad_accumulate`` (the zeroed accumulator
and the add of each micro-batch's gradients), collectives apart (median
over the traced steps).
``gap_chain.py`` says how an operation finds its phase.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "train_dev_optimizer_ms")
