"""Device time of the latent paged-attention kernel over the device's busy
time, in the traced stretch.
"""
from benchmarks import readers


def read(run):
    return readers.kernel_share_pct(run, "latent_paged_attention") or None
