"""Mean over the window's calls of an expert layer that holds a share of
its experts of the rows a held expert got (the program's
``train_moe_held_expert_rows``, from the rows of each held expert it reads
back with the step by an async callback; a layer calls twice a step under
full rematerialisation, with equal values, so the mean stands). The grouped
matmuls' arithmetic intensity goes with it.
"""


def mean_of(run, histogram: str):
    if run.telemetry is None:
        return None
    hist = run.telemetry.histogram(histogram)
    if hist is None or hist[2] <= 0:
        return None
    return hist[3] / hist[2]


def read(run):
    return mean_of(run, "train_moe_held_expert_rows")
