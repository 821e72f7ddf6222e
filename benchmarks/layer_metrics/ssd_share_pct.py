"""Device time of the operations the program wrote under its ``ssd`` scope
(a Mamba-2 layer: its norm, the projections in and out, the convolution,
the recurrence in both its forms, the gated norm, the state read and
written back) over the device's busy time, in the traced stretch. Nothing
to read where the program has no such scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "ssd")
