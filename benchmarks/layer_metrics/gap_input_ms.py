"""Host time between two steps under ``train_batch_input``: stacking the
micro-batches and the batch's copy to the device (median over the traced
steps).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_input_ms")
