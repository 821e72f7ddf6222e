"""Share of their roofline the flash kernels reached where the call is
under a window, in the traced stretch (``roofline/window_flash_attention.py``
says what is needed: the operations of the live area; compute-bound).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "window_flash_attention")
