"""Share of the window's ticks that held prompt rows (increase of
``fastgen_prefill_tokens_total`` across the tick). ``fastgen_ticks_total``
labels every ``step()`` tick ``mixed``, so it cannot tell.
"""
from benchmarks import readers


def read(run):
    ticks = readers.window_ticks(run)
    if not ticks:
        return None
    return 100.0 * sum(1 for t in ticks if t[2] > 0) / len(ticks)
