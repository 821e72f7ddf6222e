"""Seconds of set-up in XLA compilations the persistent cache did not supply
(``xla_program_seconds_total{phase="compile"}`` at the window's opening): ~0
on a warm run unless a program cannot be cached (one that holds a host
callback never is; the benchmark sets JAX's one-second floor for cache
entries to 0, an operator's process keeps it). A traced training run's
``collective_ledger()`` compiles the step again: that event is its alone.
``setup_account.py`` has the account.
"""
from benchmarks import setup_account


def read(run):
    return setup_account.metric(run, "setup_compile_s")
