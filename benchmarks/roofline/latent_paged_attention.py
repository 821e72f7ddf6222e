"""What the latent instantiation of ``ops/pallas/paged_attention.py``'s
Mosaic kernel needs (its call is named ``latent_paged_attention`` in the
trace; the dense instantiation is ``paged_attention``).

Operands: ``tables s32[T, MB]``, ``lengths s32[2 * T]``, ``q [T, N, W]``,
``pool [L*NB, bs, W]``: one row of ``W`` stored columns a cache position,
of which ``kv_lora_rank + qk_rope_head_dim`` carry the latent and its rope
key (the rest is lane padding) and the first ``kv_lora_rank`` are also the
value. Latent attention is multi-query attention with one KV head and N
query heads on it, so unlike the dense cells' shapes it has two regimes:

* bytes: every sequence with rows in the tick has its cached rows read
  once, ``blocks x bs x (kvr + dr) x itemsize`` a layer (``blocks`` from
  the client's tick log, as for the dense kernel; the padding columns are
  moved too but nobody needs them, so they are left out of the need);
* operations: a prompt row at context ``c`` does ``2 N (kvr + dr) c``
  for its scores and ``2 N kvr c`` for its values. A 496-row chunk at
  context 2.5k is 44 GFLOP a layer against 3 MB: compute-bound. The
  contexts are run-time values: the program writes their sum over a tick's
  prompt rows on the tick's span (``prompt_attended``), and
  ``tick_attrs`` joins the span to the tick's run on the device, so a
  tick's need and its time are the same tick's. The decode rows'
  operations are left out (a few per cent of a mixed tick's, and their
  ticks are memory-bound): the need is a lower bound there.

A tick's need is the larger of the two times, over the ticks whose span was
found; the calls of a tick the stretch cut count in the time and not in the
need. A lower bound throughout.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes

NAME = "latent_paged_attention"


def classify(op) -> Optional[str]:
    return "latent" if op.is_mosaic and op.name.startswith(NAME) else None


def block_positions(text: str) -> Tuple[int, int]:
    """(positions of a block, bytes of a stored value) from the pool's
    shape, operand 3 of the call."""
    _, operands = hlo_shapes.split(text)
    dtype, (_, bs, _) = operands[3]
    return bs, hlo_shapes.nbytes((dtype, ()))


def needed_bytes(seq_blocks: int, layers: float, bs: int, width: int,
                 itemsize: int) -> float:
    return float(seq_blocks) * layers * bs * width * itemsize


def needed_ops(prompt_attended: float, layers: float, heads: int,
               kv_rank: int, rope_dim: int) -> float:
    return 2.0 * heads * (2 * kv_rank + rope_dim) * prompt_attended * layers


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks.roofline import tick_attrs

    ticks = tick_attrs.calls_by_tick(tick_attrs.per_tick(run), calls)
    if not calls or not ticks:
        return None
    bs, itemsize = block_positions(calls[0].text)
    m = run.model
    total, by_compute = 0.0, 0.0
    for t, its in ticks:
        layers = len(its)             # one call a layer
        mem = needed_bytes(t["blocks"], layers, bs,
                           m.kv_lora_rank + m.qk_rope_head_dim, itemsize) \
            / run.peaks["hbm_bytes_per_s"]
        mxu = needed_ops(t["prompt_attended"], layers, m.num_heads,
                         m.kv_lora_rank, m.qk_rope_head_dim) \
            / run.peaks["bf16_flops_per_s"]
        total += max(mem, mxu)
        by_compute += mxu if mxu > mem else 0.0
    return total, "compute" if by_compute > total / 2 else "memory"
