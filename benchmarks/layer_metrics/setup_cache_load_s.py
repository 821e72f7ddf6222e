"""Seconds of set-up loading executables from the persistent compilation cache
(``xla_program_seconds_total{phase="load"}`` at the window's opening).
``setup_account.py`` has the account.
"""
from benchmarks import setup_account


def read(run):
    return setup_account.metric(run, "setup_cache_load_s")
