"""Device time of the flash forward and backward kernels under a window
(the calls named ``window_flash_*``) over the device's busy time, in the
traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "window_flash_attention")
