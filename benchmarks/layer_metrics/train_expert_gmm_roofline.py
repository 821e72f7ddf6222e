"""The least time the chip could take for the grouped matmuls of the traced
stretch's training steps (``gmm`` forward and for the rows' gradient,
``tgmm`` for the matrices'), with the step's HELD pairs for rows, over the
time they took (``roofline/train_expert_gmm.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "train_expert_gmm")
