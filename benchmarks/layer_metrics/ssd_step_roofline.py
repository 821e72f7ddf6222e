"""The least time the chip could take for the one-row form of the Mamba-2
recurrence in the traced stretch (every decode row's state read once and
written once in every ``mamba2`` layer) over the time its calls took
(``roofline/ssd_step.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "ssd_step")
