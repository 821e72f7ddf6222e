"""What ``ops/pallas/flash_attention.py``'s three Mosaic kernels need, from
the shapes in their HLO instruction.

Operands are ``q [B*N, S, D]`` and ``k, v [B*K, S, D]`` (forward), plus
``do, lse, delta`` (the two backward kernels; ``dq`` returns one array,
``dk/dv`` a pair). One attention matmul over a causal square is
``2 * BN * S * S * D / 2`` operations. What the *algorithm* needs:

* forward: 2 matmuls (QK^T, PV);
* backward: 5 (recompute QK^T, dP = dO V^T, dV, dK, dQ). This program
  splits it into two kernels that each recompute QK^T and dP (7 matmuls
  run); the needed 5 are booked 2 to ``dq`` (dQ and half the shared two)
  and 3 to ``dkv``, so the second recomputation shows as lost roofline
  share, not as work.

Bytes: every operand and result once. At head size 128 and S in the
thousands all three are compute-bound.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes

_MATMULS = {"fwd": 2.0, "dq": 2.0, "dkv": 3.0}


def classify(op) -> Optional[str]:
    """``fwd``, ``dq`` or ``dkv`` for a flash kernel call, else None."""
    if not op.is_mosaic:
        return None
    results, operands = hlo_shapes.split(op.text)
    if len(operands) == 3:
        return "fwd"
    if len(operands) == 6:
        return "dkv" if len(results) == 2 else "dq"
    return None


def ops_and_bytes(kind: str, text: str) -> Tuple[float, float]:
    results, operands = hlo_shapes.split(text)
    bn, s, d = operands[0][1]
    flops = _MATMULS[kind] * 2.0 * bn * s * s * d / 2.0
    moved = sum(hlo_shapes.nbytes(x) for x in results + operands)
    return flops, float(moved)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    """(least seconds for these calls, which bound applies to most of it)."""
    t_c = t_m = least = 0.0
    for o in calls:
        flops, moved = ops_and_bytes(classify(o), o.text)
        c = flops / run.peaks["bf16_flops_per_s"]
        m = moved / run.peaks["hbm_bytes_per_s"]
        least += max(c, m)
        t_c, t_m = t_c + c, t_m + m
    return (least, "compute" if t_c >= t_m else "memory") if calls else None
