"""Host time between two ticks in the frontend: ``serving_submit``,
``serving_harvest`` and what of ``serving_tick`` lies outside the engine's
spans (median over the traced ticks).
``gap_chain.py`` says how the gap is split.
"""
from benchmarks import gap_chain


def read(run):
    return gap_chain.metric(run, "gap_frontend_ms")
