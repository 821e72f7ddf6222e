"""Device time of the operations the program wrote under its ``select``
scope (a sparse layer's choice: the exact top-k of each row's own scores,
as a mask or as positions) over the device's busy time, in the traced
stretch. Nothing to read where the program has no such scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "select")
