"""Median wall time of one ``train_batch`` call plus the read of its loss.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms([s for s, _ in run.client["steps"]], 50)
