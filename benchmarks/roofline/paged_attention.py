"""What ``ops/pallas/paged_attention.py``'s Mosaic kernel needs.

Operands: ``tables s32[T, MB]``, ``lengths s32[T]``, ``q [T, N, D]``,
``kpool, vpool [L*NB, bs, K, D]``. The kernel walks a (row, block) grid,
one row of the flat token batch at a time. Paged attention is
memory-bound at every shape the cells use (a row's N heads do
``4 * D`` operations per cached position-head pair against ``4 * D *
K / N`` bytes), so what it *needs* is bytes: every sequence with rows in
the tick has its cached key and value blocks read once. How many blocks
that is depends on the sequences' lengths, which are run-time values the
trace does not hold, so the runner logs them per tick
(``blocks`` in the client's tick log: for each sequence with rows in the
tick, the blocks its cache holds after it) and the block's bytes come
from the pool's shape in the trace.

Two byte models that use shapes alone were tried and are wrong (PR 22,
PERF.md): "every block the table tier covers, for every row" reads 287 %
in the decode cell, because the kernel does not fetch blocks past a row's
length. A kernel that reads a sequence's blocks once per *row* (as this
one does for the hundreds of prompt rows of a chunk) shows here as a
small share: that is the headroom of grouping rows by sequence.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.roofline import hlo_shapes


def classify(op) -> Optional[str]:
    return "paged" if op.is_mosaic else None


def block_bytes(text: str) -> int:
    """Bytes of one key block plus one value block of one layer."""
    _, operands = hlo_shapes.split(text)
    dtype, (_, bs, k, d) = operands[3]
    return 2 * hlo_shapes.nbytes((dtype, (bs, k, d)))


def needed_bytes(seq_blocks: int, calls: int, per_block: int) -> float:
    """``seq_blocks`` cache blocks read once by each of ``calls`` layers."""
    return float(seq_blocks) * calls * per_block


def least_seconds(run, calls: List) -> Optional[Tuple[float, str]]:
    from benchmarks import readers

    ticks = readers.traced_tick_log(run)
    if not calls or not ticks:
        return None
    per_tick = len(calls) / len(ticks)          # layers
    moved = sum(needed_bytes(t[4], per_tick, block_bytes(calls[0].text))
                for t in ticks)
    return moved / run.peaks["hbm_bytes_per_s"], "memory"
