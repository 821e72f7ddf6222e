"""What ``swa_attention`` needs: ``ops/pallas/paged_attention.py``'s dense
kernel over a window layer's per-sequence RING, as the stacks of ``window``
and ``full`` layers of the standard block call it (``models/paged.py``,
``tick_attention``; the ``afmoe`` family). ``global_attention.py`` beside
this file is the same kernel over a full layer's own block range. Both are
classified by the call's *name* in the trace.

Operands: ``tables s32[slots, MB]`` (one table a sequence slot), ``meta
s32[3 * T]`` (lengths, whether a row continues the row before, each row's
slot), ``q [T, N, D]``, ``kpool, vpool [rows, bs, K, D]``: ordinary
grouped-query blocks. A cache position is ``K D`` keys and as many values
in one layer (4,096 B at the published widths: 8 heads of 128).

* bytes: a window layer's row sees its last ``window`` positions alone, so
  a sequence with rows in the tick needs the positions inside its rows'
  windows once a call: ``min(position + 1, window)`` for a decode row,
  ``min(a + c, c + window - 1)`` for a chunk of ``c`` rows from position
  ``a``; the engine writes their sum over the tick's sequences on the
  tick's span (``window_positions``). A full layer's call needs every
  cached block of those sequences (``blocks`` of the client's tick log);
* operations: a prompt row that scores ``c`` positions does ``2 N D c`` for
  its scores and ``2 N D c`` for its values; the sum of ``c`` over the
  tick's prompt rows is on the tick's span (``window_attended``: at most
  ``window`` a row; ``prompt_attended``: position + 1 a row). Against the
  chip's bfloat16 peak, the type the products take here. The decode rows'
  operations are left out, as for the latent kernel.

A tick's need is the larger of the two times, over the ticks whose span was
found (``tick_attrs``), times the calls of the kind in the tick (the layers
of that kind); the calls of a tick the stretch cut count in the time and
not in the need. A lower bound throughout. A program without the
attributes, or without a call of the name, gives nothing to read.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from benchmarks.roofline import hlo_shapes

NAME = "swa_attention"


def classify(op) -> Optional[str]:
    return "swa" if op.is_mosaic and op.name.startswith(NAME) else None


def position_bytes(text: str) -> Tuple[int, int]:
    """(positions of a block, bytes of one position's keys and values in
    one layer) from the key pool's shape, operand 3 of the call."""
    _, operands = hlo_shapes.split(text)
    dtype, (_, bs, k, d) = operands[3]
    return bs, 2 * hlo_shapes.nbytes((dtype, (k, d)))


def needed_ops(attended: float, heads: int, head_dim: int) -> float:
    """Per call: ``attended`` (row, position) pairs of prompt rows."""
    return 4.0 * heads * head_dim * attended


def least_seconds_of(run, calls: List, positions: Callable,
                     attended: Callable) -> Optional[Tuple[float, str]]:
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
        run, calls, lambda t, bs: t.get("window_positions"),
        lambda t: t.get("window_attended"))
