"""What the two instantiations of ``ops/pallas/paged_attention.py``'s dense
kernel that a stack of layer kinds calls need (``models/paged.py``,
``tick_attention``): ``shared_paged_attention`` (the one full-attention
layer and every cross-attention layer over that layer's block pool) and,
in ``window_paged_attention.py`` beside this file,
``window_paged_attention`` (a window layer over its ring). Both are
classified by the call's *name* in the trace.

Operands: ``tables s32[T, MB]``, ``lengths s32[2 * T]``, ``q [T, N, 2 D]``
(differential attention's paired heads: a query is zero outside its own
half), ``kpool, vpool [rows, K/2, bs, 2 D]``, heads first in a block. A
cache position is ``K D`` keys and as many values (5,120 B at the published
widths), whatever the pairing.

* bytes: every sequence with rows in the tick has its cached blocks read
  once a call: ``blocks x bs x 2 K D x itemsize`` (``blocks`` from the
  client's tick log, as for the dense kernel);
* operations: a prompt row at context ``c`` does ``2 N D c`` for its
  scores and ``2 N (2 D) c`` for its values (each softmax's output is over
  both value halves); the zero halves of the paired queries are the
  kernel's own cost and not a need. The contexts' sum over a tick's prompt
  rows is on the tick's span (``prompt_attended``). Against the chip's
  bfloat16 peak, though the kernel multiplies in float32: a lower bound.
  The decode rows' operations are left out, as for the latent kernel.

A tick's need is the larger of the two times, over the ticks whose span was
found (``tick_attrs``); the calls of a tick the stretch cut count in the
time and not in the need. A lower bound throughout.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes

NAME = "shared_paged_attention"


def classify(op) -> Optional[str]:
    return "shared" if op.is_mosaic and op.name.startswith(NAME) else None


def position_bytes(text: str) -> Tuple[int, int]:
    """(positions of a block, bytes of one position's keys and values in
    one layer) from the key pool's shape, operand 3 of the call."""
    _, operands = hlo_shapes.split(text)
    dtype, (_, k, bs, d) = operands[3]
    return bs, 2 * hlo_shapes.nbytes((dtype, (k, d)))


def needed_ops(attended: float, heads: int, head_dim: int) -> float:
    """Per call: ``attended`` (row, position) pairs of prompt rows."""
    return 2.0 * heads * 3 * head_dim * attended


def least_seconds_of(run, calls: List, positions, attended
                     ) -> Optional[Tuple[float, str]]:
    """``positions(tick, bs)`` / ``attended(tick)``: cache positions a call
    of that tick must read, and the (row, position) pairs its prompt rows
    score; None where the tick's span lacks the attribute."""
    from benchmarks.roofline import tick_attrs

    ticks = tick_attrs.calls_by_tick(tick_attrs.per_tick(run), calls)
    if not calls or not ticks:
        return None
    bs, per_position = position_bytes(calls[0].text)
    m = run.model
    total, by_compute = 0.0, 0.0
    for t, its in ticks:
        n_pos, n_att = positions(t, bs), attended(t)
        if n_pos is None or n_att is None:
            return None
        mem = float(n_pos) * per_position * len(its) \
            / run.peaks["hbm_bytes_per_s"]
        mxu = needed_ops(n_att, m.num_heads, m.head_dim) * len(its) \
            / run.peaks["bf16_flops_per_s"]
        total += max(mem, mxu)
        by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    return least_seconds_of(
        run, calls, lambda t, bs: t["blocks"] * bs,
        lambda t: t.get("prompt_attended"))
