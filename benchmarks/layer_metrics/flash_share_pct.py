"""Device time of the flash forward and backward kernels over the device's
busy time, in the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "flash_attention")
