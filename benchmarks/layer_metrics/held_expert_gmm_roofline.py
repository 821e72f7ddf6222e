"""The least time the chip could take for the expert layers' grouped
matmuls of the traced stretch where the layer holds a share of its experts
(the pairs on HELD experts in and out, the matrices of the held experts
that had rows) over the time they took (``roofline/held_expert_gmm.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "held_expert_gmm")
