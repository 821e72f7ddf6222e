"""Device time of the operations the program wrote under its ``index``
scope (a sparse layer's indexer: its three projections, the key's
LayerNorm, rotary, the key padded to its store's row, and the scores of
every row against its sequence's positions) over the device's busy time,
in the traced stretch. Nothing to read where the program has no such
scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "index")
