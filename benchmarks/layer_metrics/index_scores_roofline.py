"""The least time the chip could take for the indexer's scores in the
traced stretch (each walk's index keys read once, or the rows' products,
whichever is longer in a tick) over the time the ``index_scores`` calls
took (``roofline/index_scores.py``).
"""
from benchmarks import readers


def read(run):
    return readers.kernel_roofline_pct(run, "index_scores")
