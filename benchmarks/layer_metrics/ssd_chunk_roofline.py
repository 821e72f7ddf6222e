"""The least time the chip could take for the chunked form of the Mamba-2
recurrence in the traced stretch (the runs' states read and written, or
the chunked form's operations, whichever is longer in a tick) over the
time the operations under the program's ``ssd_chunk`` scope took
(``roofline/ssd_chunk.py``: the form is plain XLA today, so its calls are
found by their scope and not by a kernel's name). Nothing to read where the
program has no such scope or its spans no ``ssd_chunk_rows``.
"""
from benchmarks.roofline import ssd_chunk


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = ssd_chunk.calls(run)
    took = sum(o.seconds for o in calls)
    least = ssd_chunk.least_seconds(run, calls)
    if took <= 0 or least is None:
        return None
    seconds, bound = least
    run.extras.setdefault("roofline_bound", {})["ssd_chunk"] = bound
    return 100.0 * seconds / took
