"""Device time of the operations a training step wrote under its ``router``
scope (the router's matmul in float32 over its whole width, the softmax,
the top-k, the balance term; forward and backward) over the device's busy
time, in the traced stretch. Nothing to read where the program has no such
scope.
"""
from benchmarks.layer_metrics.ssm_share_pct import scope_share_pct


def read(run):
    return scope_share_pct(run, "router")
