"""Device time of the operations the program wrote under its ``sparse``
scope (a sparse layer's attention over the positions chosen: the walk with
the choice as a mask, or a gather and the attention over it) over the
device's busy time, in the traced stretch. Nothing to read where the
program has no such scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "sparse")
