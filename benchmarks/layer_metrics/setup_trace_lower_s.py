"""Seconds of set-up tracing jaxprs and lowering them to MLIR
(``xla_program_seconds_total{phase in trace, lower}`` at the window's
opening): Python's share of every program, paid warm or cold.
``setup_account.py`` has the account.
"""
from benchmarks import setup_account


def read(run):
    return setup_account.metric(run, "setup_trace_lower_s")
