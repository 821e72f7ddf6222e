"""What the chunked form of the Mamba-2 recurrence needs
(``ops/pallas/ssd.py::ssd_chunk``), whatever implements it. Today it is
plain XLA under the named scope ``ssd_chunk``, so its "calls" are the
operations whose scope in the trace holds ``ssd_chunk`` (the ``tf_op`` name
stack, ``gap_chain.op_scopes``), not one Mosaic call.

The count is of the mathematics (ISSUE 53), a chunk of ``L`` rows, ``nh``
heads of ``P`` channels in ``G`` groups of ``N`` state values: a row's
products within its chunk ``C B^T`` ``2 L G N`` and ``(L o C B^T) X`` ``2 L
nh P``; with the carried state ``C S_0`` and the state's update ``2 x 2 nh
P N``: ``2 L (G N + nh P) + 4 nh P N`` a row, against the chip's bfloat16
peak (the products are float32 at full precision today, six passes: the
share read is the lower for it, and says so). Bytes: a run's state read at
its first row and written after its last, ``2 x nh x P x N x 4`` a run a
layer. Rows and runs are run-time values: the tick's span carries
``ssd_chunk_rows``, and the runs of the chunked form are the rows that
close a run less those the one-row form took (``ssd_state_rows -
ssd_step_rows``). A tick's need is the larger of the two times, a layer,
times the ``mamba2`` layers. The elementwise work (the decays'
exponentials, a head a pair of rows of a chunk) is not counted: a lower
bound.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

SCOPE = "ssd_chunk"


def scoped_texts(run) -> set:
    """The instruction texts of the operations under the scope (read once a
    run)."""
    from benchmarks import gap_chain

    if "ssd_chunk_texts" not in run.cache:
        path = gap_chain.trace_file(run) if run.trace is not None else None
        run.cache["ssd_chunk_texts"] = set() if path is None else {
            text for (_, text), s in gap_chain.op_scopes(path).items()
            if f"/{SCOPE}/" in s or s.endswith(f"/{SCOPE}")}
    return run.cache["ssd_chunk_texts"]


def calls(run) -> List:
    """The operations of the traced window under the scope."""
    tr, texts = run.trace, scoped_texts(run)
    if tr is None or not texts:
        return []
    return [o for c in tr.chips for o in tr.ops_in_window(c)
            if o.text in texts]


def needed_ops(chunk_rows: int, model) -> float:
    nh, p = model.mamba2_heads, model.mamba2_head_dim
    g, n, chunk = model.mamba2_groups, model.mamba2_state, model.mamba2_chunk
    return float(chunk_rows) * (2.0 * chunk * (g * n + nh * p)
                                + 4.0 * nh * p * n)


def needed_bytes(runs: int, model) -> float:
    return 2.0 * runs * 4 * model.mamba2_heads * model.mamba2_head_dim \
        * model.mamba2_state


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    m = run.model
    ticks = [t for t in tick_attrs.per_tick(run) if "ssd_chunk_rows" in t]
    if not calls or not ticks or not getattr(m, "mamba2_heads", 0):
        return None
    layers = m.layer_kinds.count("mamba2")
    total, by_compute = 0.0, 0.0
    for t in ticks:
        mem = needed_bytes(t["ssd_state_rows"] - t["ssd_step_rows"], m) \
            / run.peaks["hbm_bytes_per_s"]
        mxu = needed_ops(t["ssd_chunk_rows"], m) \
            / run.peaks["bf16_flops_per_s"]
        total += layers * max(mem, mxu)
        by_compute += layers * mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
