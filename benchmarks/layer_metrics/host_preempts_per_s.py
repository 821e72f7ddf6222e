"""Times a second of the window that the scheduler took a core from the
process (``process_context_switches_total{kind="involuntary"}``,
``getrusage``: every thread of the process, the runtime's too).
``window_account.py`` has the account.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "host_preempts_per_s")
