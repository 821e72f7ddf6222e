"""What ``ops/pallas/paged_attention.py``'s Mosaic kernel needs.

Operands: ``tables s32[T, MB]``, ``lengths s32[2 * T]`` (the rows' lengths
and, behind them, whether a row shares its table with the row before),
``q [T, N, D]``, ``kpool, vpool [L*NB, bs, K, D]``, the pools left in HBM.
Since PR 24 the kernel's grid is tiles of 32 rows of the flat token
batch; inside a tile it splits the rows into runs that share a block
table (a prompt chunk, the pad rows; a decode row is a run of one) and
walks each run's table once, a few blocks a fetch step, stopping at
``ceil(length / bs)``. Paged attention is memory-bound at every shape the
cells use (a row's N heads do ``4 * D`` operations per cached
position-head pair against ``4 * D * K / N`` bytes), so what it *needs*
is bytes: every sequence with rows in the tick has its cached key and
value blocks read once. How many blocks that is depends on the
sequences' lengths, which are run-time values the trace does not hold,
so the runner logs them per tick (``blocks`` in the client's tick log:
for each sequence with rows in the tick, the blocks its cache holds
after it) and the block's bytes come from the pool's shape in the trace.

What the kernel moves beyond that need, and the share therefore shows as
headroom: a chunk that spans several tiles walks its table once per tile,
not once per tick, and a walk's first fetch is not hidden behind the walk
before it (54 % in the decode cell, 21 % in the chat cell; PERF.md, PR
24). A byte model from shapes alone is wrong: "every block the table
tier covers, for every row" read 287 % in the decode cell (PR 22),
because no form of the kernel fetches blocks past a row's length.
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
