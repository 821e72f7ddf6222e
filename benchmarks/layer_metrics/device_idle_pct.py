"""1 - union of the device's operation intervals over the traced stretch,
averaged over the chips used.
"""


def read(run):
    idle = run.trace.idle_share() if run.trace is not None else None
    return None if idle is None else 100.0 * idle
