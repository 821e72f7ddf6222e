"""Largest share of the paged cache pool that held a sequence's tokens
(``fastgen_kv_pool_utilization_peak``, the engine's own high-water mark
at the end of the window). The pool is reserved whole at start-up, so
``hbm_peak_gb`` counts all of it whatever this reads.
"""


def read(run):
    if run.telemetry is None:
        return None
    peak = run.telemetry.gauge("fastgen_kv_pool_utilization_peak")
    return None if peak is None else 100.0 * peak
