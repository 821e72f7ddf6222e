"""Median of the frontend's ``serving_queue_wait_seconds`` over the window
(submit to first prefill progress), interpolated inside the program's
histogram buckets.
"""
from benchmarks import readers


def read(run):
    return readers.ms(run.telemetry.quantile("serving_queue_wait_seconds", 0.5))
