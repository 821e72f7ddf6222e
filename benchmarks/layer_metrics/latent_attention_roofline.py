"""The least time the chip could take for the latent kernel's calls of the
traced stretch (bytes of the cached rows, or operations of the prompt rows,
whichever is longer in a tick) over the time they took
(``roofline/latent_paged_attention.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "latent_paged_attention")
