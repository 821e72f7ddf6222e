"""Milliseconds per step, per chip, inside collective events of the traced
stretch during which no other operation ran on that chip.
"""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:       # no device plane: nothing to read
        return None
    steps = len(tr.spans("bench.step"))
    return 1e3 * tr.collective_exposed_s() / steps if steps else None
