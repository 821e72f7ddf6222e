"""Device time of the full layers' attention kernel (Mosaic calls named
``global_attention``) over the device's busy time, in the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "global_attention") or None
