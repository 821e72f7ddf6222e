"""Device time of the operations the program wrote under its ``gmu`` scope
(a gated memory unit's mixer: its two projections and the gate on the
handed-on scan output) over the device's busy time, in the traced stretch;
nothing to read where the program has no such scope.
"""
from benchmarks.layer_metrics import ssm_share_pct


def read(run):
    return ssm_share_pct.scope_share_pct(run, "gmu")
