"""The load generator's own lateness: submit instant - due instant, 99th
percentile over the window's requests. A reading beyond the longest tick
means the generator, not the server, was late, and voids the run's tails.
"""
from benchmarks import readers


def read(run):
    return readers.pct_ms(readers.lateness_s(run), 99)
