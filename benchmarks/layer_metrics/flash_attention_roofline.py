"""Share of their roofline the flash kernels reached in the traced stretch
(``roofline/flash_attention.py`` says what is needed; compute-bound).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "flash_attention")
