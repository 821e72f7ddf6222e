"""Share of the window that slow ticks spent over their programs' typical
period while waiting for the device
(``fastgen_slow_tick_excess_seconds_total{phase="readback"}`` over the
window's seconds). ``window_account.py`` has the account.
"""
from benchmarks import window_account


def read(run):
    return window_account.metric(run, "slow_excess_device_pct")
