"""The least time the chip could take for the window layers' kernel calls
of the traced stretch (``swa_attention``: bytes of the cached positions
inside the rows' windows, or operations of the prompt rows, whichever is
longer in a tick) over the time they took (``roofline/swa_attention.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "swa_attention")
