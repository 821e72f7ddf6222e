"""What the grouped matmul of the expert layers needs
(``moe/layer.py::grouped_dot`` -> ``megablox.gmm``; its Mosaic call is named
``gmm`` in the trace).

Operands end in ``lhs [M, K]`` (the tick's token-expert pairs, sorted by
expert) and ``rhs [E, K, N]`` (every expert's matrix); the result is
``[M, N]``. Operations: ``2 M K N`` from the call's shapes, whatever the
routing, with the tick's real rows for ``M`` (the span's ``rows`` times the
experts a token; the pad rows' pairs are in the shape and nobody needs
them). Bytes: those rows in and out, and the matrices of the experts that
have rows; how many that is is a run-time value: the program counts the
experts with a row from the per-expert row counts it reads back with the
sampled tokens and writes the sum over the expert layers on the span that
follows the tick (``tick_commit``'s ``experts_active``); ``tick_attrs``
joins it to the tick's run on the device, so the count applied to a call
is its own tick's. A layer's share of the sum is taken as the mean over the
layers, which can only understate (a call's need is convex in the count),
and pad rows, which route too, are not counted: a lower bound. At
this model's widths a call is memory-bound in every tick: 48 rows an
expert in a chunk tick, 1.5 in a decode tick, against a 5.8 MB matrix.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes


def classify(op) -> Optional[str]:
    return "gmm" if op.is_mosaic and op.name.startswith("gmm") else None


def shapes(text: str) -> Tuple[int, int, int, int, int]:
    """(M, K, N, E, itemsize) of a call."""
    _, operands = hlo_shapes.split(text)
    (dtype, (m, k)), (_, (e, k2, n)) = operands[-2], operands[-1]
    assert k == k2, text
    return m, k, n, e, hlo_shapes.nbytes((dtype, ()))


def ops_and_bytes(text: str, active_experts: float,
                  pairs: Optional[int] = None) -> Tuple[float, float]:
    """``pairs``: the token-expert pairs of the tick's real rows, where
    known; the call's ``M`` also holds those of its pad rows, which nobody
    needs."""
    m, k, n, _, size = shapes(text)
    m = m if pairs is None else min(m, pairs)
    return 2.0 * m * k * n, size * (m * k + m * n + active_experts * k * n)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    ticks = [(t, its) for t, its in tick_attrs.calls_by_tick(
        tick_attrs.per_tick(run), calls) if "experts_active" in t]
    layers = sum(c.num_layers for _, c in run.model.segments if c.n_experts)
    if not calls or not ticks or not layers:
        return None
    total, by_compute = 0.0, 0.0
    for t, its in ticks:
        for call in its:
            ops, moved = ops_and_bytes(
                call.text, t["experts_active"] / layers,
                t["rows"] * run.model.moe_top_k)
            mem = moved / run.peaks["hbm_bytes_per_s"]
            mxu = ops / run.peaks["bf16_flops_per_s"]
            total += max(mem, mxu)
            by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
