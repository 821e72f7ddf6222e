"""Bandwidth the paged kernel reached on the bytes it moves, over the peak
(``roofline/paged_attention.py``; memory-bound).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "paged_attention")
