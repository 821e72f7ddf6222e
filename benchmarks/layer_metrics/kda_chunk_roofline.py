"""The least time the chip could take for the chunkwise form of the delta
rule in the traced stretch (the runs' states read and written, or the
chunk form's operations, whichever is longer in a tick) over the time the
operations under the program's ``kda_chunk`` scope took
(``roofline/kda_chunk.py``: the form is plain XLA today, so its calls are
found by their scope and not by a kernel's name). Nothing to read where the
program has no such scope or its spans no ``kda_chunk_rows``.
"""
from benchmarks.roofline import kda_chunk


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = kda_chunk.calls(run)
    took = sum(o.seconds for o in calls)
    least = kda_chunk.least_seconds(run, calls)
    if took <= 0 or least is None:
        return None
    seconds, bound = least
    run.extras.setdefault("roofline_bound", {})["kda_chunk"] = bound
    return 100.0 * seconds / took
