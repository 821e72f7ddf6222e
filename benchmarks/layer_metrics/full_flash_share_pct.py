"""Device time of the flash forward and backward kernels' full calls in a
stack of window and full layers (the calls named ``flash_*``, not
``window_flash_*``) over the device's busy time, in the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "full_flash_attention")
