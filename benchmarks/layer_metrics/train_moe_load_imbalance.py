"""Mean over the window's calls of an expert layer that holds a share of
its experts of the busiest held expert's rows over the mean rows a held
expert got (the program's ``train_moe_load_imbalance``): 1 is even routing.
"""
from benchmarks.layer_metrics.train_moe_held_expert_rows import mean_of


def read(run):
    return mean_of(run, "train_moe_load_imbalance")
