"""Model FLOP/s utilisation: FLOPs a token needs forward and backward
(``benchmarks/flops/<name>.py``, named by the configuration file;
recomputation not counted) times the measured tokens per second per chip,
over the chip's peak in ``peaks.json``.
"""
from benchmarks.end_to_end.train_tokens_per_s_per_chip import read as rate
from benchmarks.manifest import load_plugin


def read(run):
    if run.peaks is None:
        return None
    per_token = load_plugin("flops", run.cell.config["flops"]) \
        .train_flops_per_token(run.model, run.extras["n_params"],
                               run.client["seq_len"])
    return 100.0 * per_token * rate(run) / run.peaks["bf16_flops_per_s"]
