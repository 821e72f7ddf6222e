"""Device time of the window layers' attention kernel (Mosaic calls named
``swa_attention``) over the device's busy time, in the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "swa_attention") or None
