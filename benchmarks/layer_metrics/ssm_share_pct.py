"""Device time of the operations the program wrote under its ``ssm`` scope
(a state-space layer's mixer: projections, segmented convolution and scan,
gate, state read and written back; not its norms' MLP) over the device's
busy time, in the traced stretch. The scope of an operation is the
``tf_op`` name stack the trace keeps for it (``gap_chain.op_scopes``);
nothing to read where the program has no such scope.
"""
from benchmarks import gap_chain

SCOPE = "ssm"


def scope_share_pct(run, scope: str):
    tr = run.trace
    path = gap_chain.trace_file(run) if tr is not None else None
    if path is None or tr.busy_s() <= 0:
        return None
    scoped = {text for (_, text), s in gap_chain.op_scopes(path).items()
              if f"/{scope}/" in s or s.startswith(f"{scope}/")}
    took = tr.op_seconds(lambda o: o.text in scoped)
    return 100.0 * took / tr.busy_s() if took > 0 else None


def read(run):
    return scope_share_pct(run, SCOPE)
