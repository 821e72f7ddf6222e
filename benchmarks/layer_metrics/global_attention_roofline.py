"""The least time the chip could take for the full layers' kernel calls of
the traced stretch (``global_attention``: bytes of the sequences' cached
blocks, or operations of the prompt rows, whichever is longer in a tick)
over the time they took (``roofline/global_attention.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "global_attention")
