"""Of the (row, expert) pairs the routers chose in the window, over the
ticks' real rows and the expert layers, the share that fell on an expert
held here (the program's ``fastgen_expert_pairs_total{held}``, counted from
the rows per expert it reads back with the sampled tokens): the held
experts over the router's width where routing is even (32 of 256: 12.5).
Nothing to read where the program has no such counter.

A DIAGNOSTIC of how near the cut is to the deployment, not a number to
improve: it is a property of the seeded routing, and a program change that
moved it would have changed which experts the router chooses. The
manifest wants a direction for every metric (``better``: ``higher``) and an
end-to-end metric it ``moves`` (more pairs held are more grouped-matmul
rows a tick, so ``serve_out_tokens_per_s`` falls as it rises); judge no PR
by either.
"""


def read(run):
    if run.telemetry is None:
        return None
    held = run.telemetry.counter("fastgen_expert_pairs_total", held="yes")
    total = run.telemetry.counter("fastgen_expert_pairs_total")
    return 100.0 * held / total if total > 0 else None
