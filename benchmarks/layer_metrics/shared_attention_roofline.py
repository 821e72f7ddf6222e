"""The least time the chip could take for the calls of the traced stretch
that attend over the one shared block pool (the full-attention layer and
every cross-attention layer: bytes of the sequences' cached blocks, or
operations of the prompt rows, whichever is longer in a tick) over the time
they took (``roofline/shared_paged_attention.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "shared_paged_attention")
