"""Device time of the operations the program wrote under its ``experts``
scope (the sort by expert, the grouped matmuls, the activation, the
weighted sum back: the routed experts without the router and the shared
experts) over the device's busy time, in the traced stretch. The scope of
an operation is the ``tf_op`` name stack the trace keeps for it
(``gap_chain.op_scopes``); nothing to read where the program has no such
scope.
"""
from benchmarks import gap_chain


def read(run):
    tr = run.trace
    path = gap_chain.trace_file(run) if tr is not None else None
    if path is None or tr.busy_s() <= 0:
        return None
    scoped = {text for (_, text), scope in gap_chain.op_scopes(path).items()
              if "/experts/" in scope or scope.startswith("experts/")}
    took = tr.op_seconds(lambda o: o.text in scoped)
    return 100.0 * took / tr.busy_s() if took > 0 else None
