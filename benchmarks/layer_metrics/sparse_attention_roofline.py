"""The least time the chip could take for attention over the CHOSEN
positions in the traced stretch (a decode row's chosen keys and values read
once, a chunk row's products over what it chose, whichever is longer in a
tick) over the time the operations under the program's ``sparse`` scope
took (``roofline/sparse_attention.py``: found by their scope, so a form
that gathers and a form that walks with a mask are read alike; the second
reads low by what its rows did not choose). Nothing to read where the
program has no such scope or its spans no ``sparse_selected``.
"""
from benchmarks.roofline import sparse_attention


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls = sparse_attention.calls(run)
    took = sum(o.seconds for o in calls)
    least = sparse_attention.least_seconds(run, calls)
    if took <= 0 or least is None:
        return None
    seconds, bound = least
    run.extras.setdefault("roofline_bound", {})["sparse_attention"] = bound
    return 100.0 * seconds / took
