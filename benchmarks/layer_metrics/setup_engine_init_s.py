"""Seconds of set-up in the engine's constructor (``span_seconds{span="engine_init"}``
at the window's opening), what it spent under JAX's compile path taken out
(``engine_init_compile_seconds_total``: those seconds are in the three
metrics beside this one).
``setup_account.py`` has the account.
"""
from benchmarks import setup_account


def read(run):
    return setup_account.metric(run, "setup_engine_init_s")
