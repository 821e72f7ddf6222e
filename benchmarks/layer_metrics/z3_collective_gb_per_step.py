"""Bytes the compiled step's collectives move, from
``engine.collective_ledger()``: a count that repeats exactly.
"""


def read(run):
    n = run.extras.get("collective_bytes_per_step")
    return None if n is None else n / 1e9
