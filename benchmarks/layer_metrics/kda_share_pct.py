"""Device time of the operations the program wrote under its ``kda`` scope
(a delta-rule linear-attention layer's mixer: its norm, the projections in
and out, the three convolutions, the gates, the rule in both its forms, the
state read and written back; not the layer's FFN or experts) over the
device's busy time, in the traced stretch. Nothing to read where the
program has no such scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "kda")
