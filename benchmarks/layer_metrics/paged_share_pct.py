"""Device time of the paged-attention kernel over the device's busy time, in
the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "paged_attention")
