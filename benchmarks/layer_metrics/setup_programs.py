"""Programs lowered before the window opened
(``xla_program_events_total{phase="lower"}`` at the window's opening), the small
ones under ``program="other"`` among them.
``setup_account.py`` has the account.
"""
from benchmarks import setup_account


def read(run):
    return setup_account.metric(run, "setup_programs")
