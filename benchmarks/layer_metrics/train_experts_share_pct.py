"""Device time of the operations a training step wrote under its
``experts`` scope (the sort by expert, the gathers, the grouped matmuls
forward and backward, the activation, the weighted sum back) over the
device's busy time, in the traced stretch. Nothing to read where the
program has no such scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "experts")
