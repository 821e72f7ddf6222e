"""The least time the chip could take for the one-row form of the delta
rule in the traced stretch (every decode row's state read once and written
once in every ``kda`` layer) over the time its calls took
(``roofline/kda_step.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "kda_step")
