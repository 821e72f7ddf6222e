"""What ``ops/pallas/flash_attention.py``'s three Mosaic kernels need where
the call is under a WINDOW, from the shapes in their HLO instruction and
the model's window. A window call carries a name of its own in the trace
(``window_flash_fwd`` / ``window_flash_dq`` / ``window_flash_dkv``), which
is how it is told from a full one (``flash_fwd`` / ``flash_dq`` /
``flash_dkv``): ``flash_attention.py`` beside this file counts a causal
square for every call it finds by operand counts. :func:`named` tells both
by name; this file's ``classify`` takes the window calls, and
``full_flash_attention.py`` the full ones of a stack of kinds.

Operands start with ``q [B*N, S, D]``. The LIVE area of a causal square
under a window of ``W`` positions is ``S * W - W^2 / 2`` rows x columns
(row ``t`` sees ``min(t + 1, W)`` columns), ``S^2 / 2`` where the window is
no shorter than the sequence; one attention matmul over it is ``2 * BN *
area * D`` operations, whatever tiles the kernel runs (a tile that crosses
the window's edge or the diagonal multiplies dead columns too: that shows
as lost roofline share, not as work). Matmuls needed, as
``flash_attention.py`` books them: forward 2, ``dq`` 2, ``dkv`` 3. Bytes:
every operand and result once.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes

_MATMULS = {"fwd": 2.0, "dq": 2.0, "dkv": 3.0}
PREFIX = "flash_"


def named(op) -> Optional[Tuple[str, bool]]:
    """(``fwd``, ``dq`` or ``dkv``, whether under a window) for a flash
    kernel call, by the call's name; else None."""
    if not op.is_mosaic or PREFIX not in op.name:
        return None
    head, tail = op.name.split(PREFIX, 1)
    kind = tail.split(".")[0].rstrip("_")
    return (kind, head.endswith("window_")) if kind in _MATMULS else None


def classify(op) -> Optional[str]:
    """``fwd``, ``dq`` or ``dkv`` for a flash kernel call under a window,
    else None."""
    found = named(op)
    return found[0] if found and found[1] else None


def live_area(s: int, window: int) -> float:
    if not window or window >= s:
        return s * s / 2.0
    return s * window - window * window / 2.0


def ops_and_bytes(kind: str, text: str, window: int) -> Tuple[float, float]:
    results, operands = hlo_shapes.split(text)
    bn, s, d = operands[0][1]
    flops = _MATMULS[kind] * 2.0 * bn * live_area(s, window) * d
    moved = sum(hlo_shapes.nbytes(x) for x in results + operands)
    return flops, float(moved)


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    """(least seconds for these calls, which bound applies to most of it):
    a window call over the model's window, a full one over the square."""
    window = int(getattr(run.model, "attn_window", 0) or 0)
    t_c = t_m = least = 0.0
    for o in calls:
        kind, windowed = named(o)
        flops, moved = ops_and_bytes(kind, o.text, window if windowed else 0)
        c = flops / run.peaks["bf16_flops_per_s"]
        m = moved / run.peaks["hbm_bytes_per_s"]
        least += max(c, m)
        t_c, t_m = t_c + c, t_m + m
    return (least, "compute" if t_c >= t_m else "memory") if calls else None
