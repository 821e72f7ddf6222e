"""Mean over the window's ticks that held prompt rows (the full tick
bucket: every other bucket is a decode tick's) of the rows a held expert
got in a layer (the program's ``fastgen_held_expert_rows`` by tick bucket:
pairs on held experts over the experts held): how near a chunk tick's
grouped matmul is to the deployment's rows an expert. Nothing to read
where the program has no such histogram.
"""


def read(run):
    budget = (run.extras.get("engine") or {}).get("token_budget")
    if run.telemetry is None or not budget:
        return None
    hist = run.telemetry.histogram("fastgen_held_expert_rows",
                                   bucket=str(budget))
    if hist is None or hist[2] <= 0:
        return None
    return hist[3] / hist[2]
