"""The least time the chip could take for the experts' grouped matmuls of
the traced stretch (the matrices of the experts that had rows, read once a
call) over the time they took (``roofline/expert_gmm.py``; memory-bound).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "expert_gmm")
