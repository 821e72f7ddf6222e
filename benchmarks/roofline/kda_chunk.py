"""What the chunkwise form of the delta rule needs
(``ops/pallas/kda.py::kda_chunk``), whatever implements it. Today it is
plain XLA under the named scope ``kda_chunk``, so its "calls" are the
operations whose scope in the trace holds ``kda_chunk`` (the ``tf_op``
name stack, ``gap_chain.op_scopes``), not one Mosaic call.

The count is of the mathematics (ISSUE 41), a chunk of ``C`` rows a head of
``D`` keys and values: the two triangular products of decayed keys with
keys and with queries ``2 C^2 D``, the solve of ``[V | K+]`` ``2 C^2 D``,
``P U`` ``2 C^2 D``, and the three products with the carried state and the
state's update ``6 C D^2`` (there are four, ``W S_0``, ``Q+ S_0``, ``K^T
U`` and the decay of ``S_0``, the last elementwise): ``6 C D + 6 D^2`` a
row a head. Bytes: a run's state read at its first row and written after
its last, ``2 x heads x D x D x 4`` a run a layer. Rows and runs are
run-time values: the tick's span carries ``kda_chunk_rows``, and the runs
of the chunk form are the rows that close a run less those the one-row
form took (``kda_state_rows - kda_step_rows``). A tick's need is the
larger of the two times, a layer, times the ``kda`` layers. The
elementwise work (the decays' exponentials, a channel a pair of rows
within a sub-block) is not counted: a lower bound.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

SCOPE = "kda_chunk"
#: rows of a chunk in the count (the program's ``ops.pallas.kda.CHUNK``)
CHUNK = 64

def scoped_texts(run) -> set:
    """The instruction texts of the operations under the scope (read once a
    run)."""
    from benchmarks import gap_chain

    if "kda_chunk_texts" not in run.cache:
        path = gap_chain.trace_file(run) if run.trace is not None else None
        run.cache["kda_chunk_texts"] = set() if path is None else {
            text for (_, text), s in gap_chain.op_scopes(path).items()
            if f"/{SCOPE}/" in s or s.endswith(f"/{SCOPE}")}
    return run.cache["kda_chunk_texts"]


def calls(run) -> List:
    """The operations of the traced window under the scope."""
    tr, texts = run.trace, scoped_texts(run)
    if tr is None or not texts:
        return []
    return [o for c in tr.chips for o in tr.ops_in_window(c)
            if o.text in texts]


def needed_ops(chunk_rows: int, heads: int, d: int,
               chunk: int = CHUNK) -> float:
    return float(chunk_rows) * heads * (6.0 * chunk * d + 6.0 * d * d)


def needed_bytes(runs: int, heads: int, d: int) -> float:
    return 2.0 * runs * heads * d * d * 4


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    m = run.model
    ticks = [t for t in tick_attrs.per_tick(run) if "kda_chunk_rows" in t]
    if not calls or not ticks or not getattr(m, "kda_heads", 0):
        return None
    layers = m.layer_kinds.count("kda")
    total, by_compute = 0.0, 0.0
    for t in ticks:
        mem = needed_bytes(t["kda_state_rows"] - t["kda_step_rows"],
                           m.kda_heads, m.kda_head_dim) \
            / run.peaks["hbm_bytes_per_s"]
        mxu = needed_ops(t["kda_chunk_rows"], m.kda_heads, m.kda_head_dim) \
            / run.peaks["bf16_flops_per_s"]
        total += layers * max(mem, mxu)
        by_compute += layers * mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
