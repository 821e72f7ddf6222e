"""Pallas kernel: a sparse layer's choice, the exact ``topk`` of each row's
indexer scores as a mask, ``paged.sparse_choice`` with a tile of rows'
scores held in VMEM for every counting pass.

The plain form counts the entries at or over a candidate cut 32 times a
row, and each count reads the tick's scores from HBM (2,048 rows x 18,432
positions: 32 x 75 MB a layer). Here one grid step takes a tile of ``R``
rows' scores over the whole reach, ``[S / 128, R, 128]`` as
``index_scores`` lays them, and everything after that one read happens in
VMEM:

- the ORDER-PRESERVING WORD of every entry, made once into scratch: a
  float32's bits with a negative's flipped, which as a signed word is
  ascending in the score (``-0.0`` under ``+0.0``, as in the plain form);
  an entry at or past its row's length takes the lowest word there is, and
  a valid entry's word is held over it;
- the bisection over the word's 32 bits, the highest first: a pass counts
  each row's entries at or over a candidate as ``[R, 128]`` partial sums
  over the lane tiles (planes) and reduces the lanes once; the cut is the
  ``topk``-th largest word itself;
- the second bisection, over positions among the entries equal to the cut,
  only in a tile where they straddle a row's cut: the lower position first.

What a step reads off its rows' lengths, and nothing else decides: a tile
none of whose rows is longer than ``topk`` (the pad tiles of a decode
bucket, a prompt's first chunk) writes ``position < length`` and counts
nothing; a tile scans the planes up to its longest row's and writes zeros
past them. The mask is the plain form's element for element.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.index_scores import TILE_ROWS
from deepspeed_tpu.ops.pallas.paged_attention import _LANES, _use_interpret

#: the word of an entry that is not its row's to choose: under every valid one
_LOW = -2 ** 31
#: what a step's blocks and scratch may take of VMEM (the scores and the
#: mask twice each, the pipeline's, and the words once)
_VMEM_BYTES = 40 * 1024 * 1024


def tile_rows(reach: int) -> int:
    """Rows of a grid step: ``index_scores``' tile while five blocks of
    ``reach`` positions a row fit ``_VMEM_BYTES`` (they do up to 65k
    positions), else the half or the quarter of it."""
    return max([r for r in (TILE_ROWS, TILE_ROWS // 2, TILE_ROWS // 4)
                if 5 * 4 * r * reach <= _VMEM_BYTES] or [TILE_ROWS // 4])


def count_tiles(lengths, topk: int,
                rows: int = TILE_ROWS) -> Tuple[int, int, int]:
    """(grid steps that hold one of a tick's real rows, those of them that
    count, lane tiles their longest rows reach: a step scans them in whole
    trips), by the kernel's own rule of the rows' ``lengths`` (numpy, the
    real rows alone) in tiles of ``rows``: what the ``decode_tick`` span
    and ``tools/choice_kernel_alone.py`` say of a tick."""
    lengths = np.asarray(lengths)
    if not lengths.size:
        return 0, 0, 0
    longest = np.maximum.reduceat(lengths, np.arange(0, lengths.size, rows))
    counting = longest[longest > topk]
    return (longest.size, counting.size,
            int((-(-counting // _LANES)).sum()))


def _planes_a_trip(planes: int) -> int:
    """Lane tiles a trip of a counting loop takes: the most of eight that
    divide the reach's (a tile scans whole trips)."""
    return max(u for u in range(1, 9) if planes % u == 0)


def _kernel(len_ref, s_ref, o_ref, key_ref, *, topk):
    nP, R, L = s_ref.shape
    U = _planes_a_trip(nP)
    t0 = pl.program_id(0) * R
    row = lax.broadcasted_iota(jnp.int32, (R, L), 0)
    lane = lax.broadcasted_iota(jnp.int32, (R, L), 1)
    zeros = jnp.zeros((R, L), jnp.int32)

    def a_row(r, carry):
        hi, lengths = carry
        n = len_ref[t0 + r]
        return jnp.maximum(hi, n), jnp.where(row == r, n, lengths)

    # the tile's longest row, and every row's length along its lanes
    hi, lengths = lax.fori_loop(0, R, a_row, (jnp.int32(0), zeros))
    trips = pl.cdiv(pl.cdiv(hi, L), U)

    def write(first, last, plane):
        def at(p, _):
            o_ref[p] = plane(p).astype(o_ref.dtype)

        lax.fori_loop(first, last, at, None)

    @pl.when(hi <= topk)
    def _every_position():
        write(0, nP, lambda p: lane + p * L < lengths)

    @pl.when(hi > topk)
    def _the_largest():
        def word(p, _):
            bits = lax.bitcast_convert_type(s_ref[p], jnp.int32)
            asc = bits ^ ((bits >> 31) & 0x7fffffff)
            key_ref[p] = jnp.where(lane + p * L < lengths,
                                   jnp.maximum(asc, _LOW + 1), _LOW)

        lax.fori_loop(0, trips * U, word, None)

        def count(*which):
            """Each row's entries that ``which[n](plane, words)`` holds
            for, ``[R, 1]`` a predicate."""
            def trip(g, sums):
                for j in range(U):
                    p = g * U + j
                    words = key_ref[p]
                    sums = tuple(s + f(p, words).astype(jnp.int32)
                                 for s, f in zip(sums, which))
                return sums

            sums = lax.fori_loop(0, trips, trip, (zeros,) * len(which))
            return [jnp.sum(s, axis=1, keepdims=True) for s in sums]

        def bit(i, cut):
            # ``cut``: the unsigned word's bits found so far, a row's along
            # its lanes; as a signed word it is that with the top bit turned
            cand = cut | (jnp.int32(1) << (31 - i))
            at = cand ^ _LOW
            n, = count(lambda p, words: words >= at)
            return jnp.where(n >= topk, cand, cut)

        cut = lax.fori_loop(0, 32, bit, zeros)
        # a row with no more than ``topk`` entries cuts under all of them
        cuts = cut != 0
        at = cut ^ _LOW
        over, equal = count(lambda p, words: words > at,
                            lambda p, words: words == at)
        room = topk - over

        def lowest():
            n_bits = max(nP * L, 2).bit_length()

            def bit(i, P):
                cand = P | (jnp.int32(1) << (n_bits - 1 - i))
                n, = count(lambda p, words: (words == at)
                           & (lane + p * L < cand))
                return jnp.where(n <= room, cand, P)

            return lax.fori_loop(0, n_bits, bit, zeros)

        # the entries equal to the cut are a row's under position ``P``
        P = lax.cond(jnp.max(jnp.where(cuts & (equal > room), 1, 0)) > 0,
                     lowest, lambda: jnp.full((R, L), 2 ** 31 - 1, jnp.int32))
        P = jnp.where(cuts, P, 0)

        def chosen(p):
            words = key_ref[p]
            return (words > at) | ((words == at) & (lane + p * L < P))

        write(0, trips * U, chosen)
        write(trips * U, nP, lambda p: zeros)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("topk", "name", "interpret"))
def _tiles(lengths, scores, *, topk, name, interpret):
    nP, T, L = scores.shape
    R = tile_rows(nP * L)
    block = pl.BlockSpec((nP, R, L), lambda i, *_: (0, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(T // R,), in_specs=[block],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((nP, R, L), jnp.int32)])
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024)
    return pl.pallas_call(
        functools.partial(_kernel, topk=topk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.float32),
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(lengths, scores)


def sparse_choice(scores: jax.Array, lengths: jax.Array, topk: int,
                  interpret: Optional[bool] = None, *,
                  name: str = "sparse_choice") -> jax.Array:
    """The positions each row attends to, as a float32 mask laid as
    ``scores``: of a row's positions under its length the ``topk`` of the
    largest score, the lower position first among equals; all of them while
    it has no more than ``topk`` (``paged.sparse_choice``'s mask, element
    for element).

    scores [S / 128, T', 128] float32, position ``s`` of row ``t`` at
    ``[s // 128, t, s % 128]`` (``index_scores``' result; ``T'`` whole
    tiles of rows); lengths [T] with ``T <= T'``: the rows past them choose
    nothing."""
    if interpret is None:
        interpret = _use_interpret()
    nP, Tp, L = scores.shape
    assert L == _LANES and Tp % TILE_ROWS == 0 and scores.dtype == jnp.float32
    lengths = jnp.pad(lengths.astype(jnp.int32),
                      (0, Tp - lengths.shape[0]))
    return _tiles(lengths, scores, topk=int(topk), name=name,
                  interpret=interpret)
