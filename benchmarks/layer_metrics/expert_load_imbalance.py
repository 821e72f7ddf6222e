"""Mean over the window's ticks of the busiest expert's rows over the mean
rows an expert got, each summed over the expert layers (the program's
``fastgen_expert_load_imbalance``, from the counts it reads back with the
sampled tokens): 1 is even routing; the grouped matmul's time follows the
busiest expert where rows outnumber experts.
"""


def read(run):
    if run.telemetry is None:
        return None
    hist = run.telemetry.histogram("fastgen_expert_load_imbalance")
    if hist is None or hist[2] <= 0:
        return None
    return hist[3] / hist[2]
