"""Share of their roofline the flash kernels' full calls reached in a stack
of window and full layers, in the traced stretch
(``roofline/full_flash_attention.py`` finds them by name and says what is
needed: the operations of the causal square; compute-bound).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "full_flash_attention")
