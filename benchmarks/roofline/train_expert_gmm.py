"""What the grouped matmuls of a TRAINING step's expert layers need where
the layer holds a share of its experts (``moe/layer.py``:
``_held_routed`` -> ``grouped_dot`` -> ``megablox.gmm`` and, in the
backward, ``gmm`` again for the rows' gradient and ``tgmm`` for the
matrices'; the Mosaic calls are named ``gmm`` and ``tgmm`` in the trace).

``gmm``: operands end in ``lhs [M, A]`` and ``rhs [E, B, C]`` (``[E, K, N]``
forward, the same matrices read transposed for the rows' gradient), the
result is ``[M, .]``; ``tgmm``: ``lhs [K, M]`` (or ``[M, K]`` under a
transposed layout), ``rhs [M, N]``, the result ``[E, K, N]``. Operations: ``2 M K N`` with the step's HELD pairs for
``M``, whatever rows the call's shape has: the pairs on experts that are
not here sort behind the groups and are never multiplied, so the shape's
``M`` (a row a pair, held or not) is not the work. The held
pairs are a run-time value: the program reads the rows of each held expert
back with the step (an async callback, ``train_moe_held_expert_rows`` in
its registry: the mean rows a held expert got, a layer's call); times the
experts held that is a call's pairs, taken as the mean over the window's
calls. Bytes: those rows in and out once, and the matrices of the experts
held once a call. A program without the histogram (every other model; the
parent) gives nothing to read.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes


def classify(op) -> Optional[str]:
    # ``gmm.12`` / ``tgmm.3`` inside a rematerialised scan; a program that
    # differentiates the call directly names it ``transpose_jvp_jit_tgmm__``
    if not op.is_mosaic:
        return None
    return "tgmm" if "tgmm" in op.name else "gmm" if "gmm" in op.name else None


def ops_and_bytes(kind: str, text: str, pairs: float) -> Tuple[float, float]:
    results, operands = hlo_shapes.split(text)
    (ldt, lhs), (rdt, rhs) = operands[-2], operands[-1]
    lsz, rsz = hlo_shapes.nbytes((ldt, ())), hlo_shapes.nbytes((rdt, ()))
    if kind == "gmm":
        m, a = lhs
        e, b, c = rhs
        m = min(m, pairs)
        out = b if a == c else c
        return 2.0 * m * b * c, lsz * m * (a + out) + rsz * e * b * c
    # (XLA folds the transposition of ``lhs`` into the operand's layout:
    # the text may say ``[M, K]``; the result says K and N either way)
    _, k, n = results[0][1]
    m = min(max(lhs), pairs)
    out = hlo_shapes.nbytes(results[0])
    return 2.0 * m * k * n, lsz * m * k + rsz * m * n + out


def held_pairs(run) -> Optional[float]:
    """Mean (row, expert) pairs on held experts of one layer's call, over
    the measured window (the traced stretch follows it; the routing of a
    seeded stream of fresh batches is stationary)."""
    if run.telemetry is None:
        return None
    hist = run.telemetry.histogram("train_moe_held_expert_rows")
    if hist is None or hist[2] <= 0:
        return None
    return hist[3] / hist[2] * run.model.n_experts


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    pairs = held_pairs(run)
    if not calls or pairs is None:
        return None
    total, by_compute = 0.0, 0.0
    for call in calls:
        ops, moved = ops_and_bytes(classify(call), call.text, pairs)
        mem = moved / run.peaks["hbm_bytes_per_s"]
        mxu = ops / run.peaks["bf16_flops_per_s"]
        total += max(mem, mxu)
        by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
