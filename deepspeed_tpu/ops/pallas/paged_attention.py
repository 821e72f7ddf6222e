"""Pallas paged-attention kernel: block-table KV gather + online softmax over
TILES of rows, without materializing the gathered context in HBM.

Parity: reference ``inference/v2/kernels/ragged_ops`` (blocked flash attention
over the blocked KV cache) — the CUDA tree walks each sequence's block list
once for all of that sequence's query rows; so does this kernel.

Grid: one step per tile of ``R`` consecutive rows of the flat token batch.
The pools stay in HBM (``memory_space=ANY``); a step splits its tile into
RUNS of consecutive rows that carry the same block table and walks each
run's table ONCE, ``P`` blocks at a time, with double-buffered
``make_async_copy`` fetches whose trip count is read from the prefetched
lengths (how the fetches of a call follow one another: "The chain" below):

- a run of two or more rows (a prompt chunk of one sequence, which
  ``FastGenEngine._step_impl`` lays out contiguously; the pad rows of a tick)
  meets each fetched block with all the tile's rows in one product per KV
  head, ``[R*rep, D] x [D, P*bs]``, and the per-row causal limit
  ``c < lengths[row]`` — 0 for the tile's rows outside the run — is a mask;
- a run of one row (a decode row) walks alone, ``[rep, D] x [D, P*bs]``.

``R`` and ``P`` are one rule of the operands' shapes (``tile_rows``,
``_blocks_per_fetch``). A fetch step has a cost of its own, so a tile takes
the rows and a step the cache positions that 1 MB each of accumulator,
scores and fetched bytes leave room for, K/V pools within eight blocks a
step. Mistral, LFM2 and Keye: 32 rows x 256 positions; Trinity and
Phi-4-mini-flash, by their scores, 32 x 128; Pythia 32 x 64; the latent
pools of Moonlight and Kimi-Linear 32 x 512.

A step does what its columns need and no more (PERF.md sections 5-6, PR 34:
Trinity's tile-step 2.56 -> 1.67 us and a decode row's 1.21 -> 0.85 on the
v5e, every other instantiation 9 to 34 % a call, every output equal to the
bit):

- two FORMS of a step. A walk's steps ``begin .. end`` are cut once a walk
  by :func:`step_ranges` into edge steps, OPEN steps ``first .. last`` and
  edge steps. On an open step every row of the walk sees every column:
  ``(i + 1) * C <= min(length)`` and ``i * C >= max(length) - window``, and
  the walk is a row alone or a run that is its whole tile. It runs
  ``online_softmax`` with no mask: no iota, no compares, no select over the
  scores or the probabilities (the mask was all true). Edge steps, and every
  step of a run that shares its tile, take the masked form. The host counts
  both with the same rule (:func:`count_steps`; the engine's
  ``fastgen_attention_steps_total{form}``);
- keys and values are multiplied as they lie. A fetched ``[C, K, D]`` slot is
  rows ``c*K + k`` of a matrix, so KV head k is every K-th row: a strided
  load of the slot's 32-bit words (two bfloat16 heads a word) and integer
  shifts put a head's positions together; no value is transposed
  (``head_major``). ``heads_first`` and latent slots are head-major already;
- the softmax statistics ``m`` and ``l`` lie replicated over their 128 lanes
  (as upstream Pallas TPU flash attention holds them), so no step slices a
  lane out of them or broadcasts one back: the largest item, a third of a
  tile-step;
- whether a walk is a row alone or a tile is chosen once a walk (a loop of
  steps for each form and stretch), not by a branch a step.

The chain. A call's fetches are ONE chain over all its walks, so that the
copy engine always has the next step's blocks in flight (PERF.md section 6,
PR 58: a walk used to start its first fetch itself and wait for it, ~2 us a
walk of nothing in flight, 12 walks a call 192 times a tick in the looped
cell). While a step is computed out of one slot, the other is being filled
with the chain's next step: the walk's step i+1 or, during a walk's LAST
step, the first step of the run after it, in this tile or, past the tile's
last row, the next tile's first run (the slots, their semaphores and the
plan below outlive a grid step; the lengths and ``same`` of every row of the
call lie in scalar memory). A walk finds its run in ``plan_ref`` (scalar
scratch: the run's end, the least and greatest of its lengths, the slot of
its first step, then its first fetch's row, blocks and step), left there by
the walk before it, which looked it up to start its fetch; it looks up its
own successor before its first step, and waits for a fetch with the
descriptors of the start it answers, the same ``(row, blocks, step,
slot)``. A slot is therefore the parity of a step's
number in the CHAIN, not in its walk: two in flight at once at most, one
computed and one filling. Only the call's first run starts cold (at the top
of the first grid step, ahead of the tile's opening), and the call's last
run starts nothing. What a step computes, and from which blocks, is what it
was: every output is equal to the bit. The host counts a call's walks as it
counts its steps (:func:`count_walks`; the engine's ``attn_walks`` on the
``decode_tick`` span).

Which rows share a table is DATA: ``same[t] = all(tables[t] == tables[t-1])``
is computed on the device beside the call and rides in the second scalar
operand behind the lengths, so a tick's composition never reaches a compile
key and the result is right for any row layout. A walk fetches
``ceil(length / bs)`` blocks, not the table's width; a pad row (all-zero
table, length 1) costs at most one block, and a run of pad rows one.

Arithmetic: bf16 values, float32 from the cast on (products, scores, softmax
statistics, probabilities, accumulator). On the v5e bf16 operands into either
product bought nothing: the kernel is not MXU-bound.

A serving set-up builds a tick program per ``(Tn, mb)``, seven of them, and
tracing this kernel's body for each was a tenth of the benchmark's
``setup_s``. So the traced program is kept small (one ``online_softmax``
with a static flag for its two forms, one ``steps`` loop instantiated a
stretch of a walk; every loop over rows is a ``fori_loop``) and is traced
once a tick bucket: the call is an inlined inner ``jit``, whose trace is
cached by operand shapes, and the table is widened to a multiple of 64
columns so that every tier of a bucket has the same shapes.

Shapes: q [T, N, D]; kpool/vpool [NB, bs, K, D]; tables [T, MB] int32;
lengths [T] int32 (context length per token, pos+1). GQA via in-kernel
head-group batching (N = K * rep).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# what one fetch step holds of K plus V blocks (times two slots in VMEM), and
# what a tile's float32 accumulator and a step's float32 scores may each take
_FETCH_BYTES = 1024 * 1024
_TILE_BYTES = 1024 * 1024
_LANES = 128
# blocks a fetch step of K/V pools copies at most, past one lane width
_STEP_BLOCKS = 8
# block tables are widened to a multiple of this many columns (see _tiles)
_TABLE_COLS = 64


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_rows(num_heads: int, value_dim: int) -> int:
    """Rows of the flat token batch a grid step works on: 32, and 16 (the q
    block's bf16 sublane tile) where the tile's float32 accumulator
    ``[R, num_heads, value_dim]`` would pass ``_TILE_BYTES``. A chunk's
    context is fetched and walked once a tile, so a tile takes the rows its
    state leaves room for. Past 32 the rows compete with the positions of a
    step for the scores' room (see ``_blocks_per_fetch``), and positions
    serve decode rows too: on the v5e the latent kernel read 840 us a mixed
    tick's call at 32 rows x 512 positions, 837 at 64 x 256, 1,419 at
    16 x 128 (PERF.md section 6, PR 32)."""
    return max(16, min(32, _TILE_BYTES // (4 * num_heads * value_dim)
                       // 16 * 16))


def _blocks_per_fetch(bs: int, row_bytes: int, query_rows: int,
                      head_axis: bool) -> int:
    """Blocks a fetch step copies and multiplies. ``row_bytes``: what one
    cache position holds over every pool; ``query_rows``: the rows of a
    step's scores over every KV head (``tile_rows`` x query heads);
    ``head_axis``: the pools have one (K/V pools).

    A live step costs 0.3 us for a decode row and more for a tile whatever
    it carries (a tile's statistics and accumulator are read, rescaled and
    written back every step: half of a masked tile-step's vector work at
    128 positions), so it carries what VMEM has room for: as many cache
    positions as keep the fetch under ``_FETCH_BYTES`` and the float32
    scores ``[query_rows, positions]`` under ``_TILE_BYTES``, in whole lane
    widths (the scores' minor dim), or whole blocks where not one lane
    width fits (Pythia: 64 positions are 1 MB). A walk's last step
    multiplies the positions past its context all the same, so with a head
    axis a step stays within ``_STEP_BLOCKS`` blocks where that is a lane
    width or more: a pool's block is its deployment's word on how long its
    contexts are (toy pools of 8-position blocks keep one lane width).

    Read on the v5e, the call alone at its cell's tick shapes, 128 -> 256
    positions a step (PERF.md section 6, PR 48; PR 32's one-lane-width rule
    for K/V pools dated from a step that cast and transposed what it
    fetched): Keye's masked walk over blocks of 128 (every step masked by
    the choice) 6,052 -> 4,576 us a chunk tick's call at ~8k, 1,835 -> 1,288
    a decode tick's 24 rows at ~17k (512 a step, which the scores' room
    does not give: 4,440 and 1,197); LFM2's 256 rows at 300-2,000 1,584 ->
    1,214 and its chunk tick 1,809 -> 1,440; Mistral's 41 rows at 150-1,150
    203-209 -> 199-205 and its chunk tick 301-304 -> 291-294 (every context
    at 300: 125 -> 134; at 600: 190 -> 185). Trinity's and Phi-4's scores
    hold them to 128 whatever this says. The latent pool's row is
    multiplied as it lies, 1,280 B a position: 128 positions are 0.2 us of
    HBM time, and 512 a step read 314 against 470 us a decode tick's call,
    840 against 1,278 a mixed tick's (PERF.md section 6, PR 32)."""
    positions = min(_FETCH_BYTES // row_bytes,
                    _TILE_BYTES // (4 * query_rows))
    if head_axis:
        positions = min(positions, max(_LANES, _STEP_BLOCKS * bs))
    if positions >= _LANES:
        positions -= positions % _LANES
    return max(1, positions // bs)


def step_ranges(lo, hi, step: int, window: Optional[int], whole, xp=np):
    """The fetch steps, ``step`` cache positions each, of a walk whose rows'
    lengths lie in ``lo .. hi`` (a row of length n sees the columns under n
    and, with a ``window``, no more than ``window`` of them), as ``(begin,
    first, last, end)``: the walk takes steps ``begin .. end``, outside
    which no row sees a column; steps ``first .. last`` are OPEN: every
    row sees every column, ``(i + 1) * step <= lo`` and ``i * step >= hi -
    window``, so they are computed without a mask. Only a ``whole`` walk
    has open steps: a row alone, or a run that is its whole tile (any other
    meets the blocks with rows of the tile that see nothing).

    The kernel's walk (``xp=jnp``, on scalars) and the host's count of a
    tick's steps (:func:`count_steps`, on arrays) are this one rule."""
    end = -(-hi // step)
    if window is None:
        begin = first = 0 * lo                 # zeros of ``lo``'s kind
    else:
        begin = xp.maximum(lo - window, 0) // step
        first = xp.clip(-((window - hi) // step), begin, end)
    last = xp.where(whole, xp.clip(lo // step, first, end), first)
    return begin, first, last, end


def _runs(starts, tile: int):
    """The first rows of the kernel's runs, ``starts[t]`` true where row t
    carries another table than row t-1: a run ends at a tile's last row
    too. The rows are whole tiles': the caller adds the pad rows the
    wrapper would (length 1, one table)."""
    cut = np.array(starts, bool)
    if len(cut) % tile:
        raise ValueError(f"{len(cut)} rows are not whole tiles of {tile}")
    cut[::tile] = True
    return np.flatnonzero(cut)


def count_steps(lengths, starts, tile: int, step: int,
                window: Optional[int] = None) -> Tuple[int, int]:
    """(fetch steps, open fetch steps) one call of the kernel walks over
    rows of these ``lengths`` in tiles of ``tile`` rows and steps of
    ``step`` cache positions: the kernel's own runs (:func:`_runs`) under
    :func:`step_ranges`, on the host."""
    lengths = np.asarray(lengths)
    at = _runs(starts, tile)
    rows = np.diff(np.append(at, len(lengths)))
    begin, first, last, end = step_ranges(
        np.minimum.reduceat(lengths, at), np.maximum.reduceat(lengths, at),
        step, window, (rows == 1) | (rows == tile))
    return int((end - begin).sum()), int((last - first).sum())


def count_walks(starts, tile: int) -> int:
    """The walks of one call of the kernel, one a run of :func:`_runs`. Every
    walk but a call's first finds its first fetch in flight (see the
    module's docstring), so walks less calls is how often the chain
    engages."""
    return len(_runs(starts, tile))


def _kernel(tables_ref, meta_ref, q_ref, *refs,     # 2 scalar prefetch
            n_pool: int, scale: float, mxu_dtype, window: Optional[int],
            heads_first: bool, indirect: bool = False,
            chosen: bool = False):
    """``heads_first``: a pool block is ``[K, bs, D]`` and not ``[bs, K,
    D]`` (a head count off the sublane tiling, 10 say, cannot be the
    second-minor dim of a block a copy slices); the fetch slots are then
    ``[2, K, P, bs, D]``, filled a head-strided copy a block, and are
    head-major as they lie.
    ``indirect``: the tables are one a SEQUENCE and a third section of
    ``meta`` names each row's (``paged_attention``'s ``row_table``).
    ``window``: a row sees its last ``window`` cache positions alone
    (``limit - window <= c < limit``) and a walk starts at the block of its
    rows' lowest such position instead of block 0; None: the whole context.
    ``refs``: the key pool and, where ``n_pool`` is 2, the value pool
    (with one pool the value is the leading columns of the key's block:
    latent attention, whose value is the latent itself), the output, then
    the scratch: a fetch buffer per pool, the semaphores, the plan of the
    run to walk next (scalar memory), the queries head-major, lengths,
    softmax statistics and accumulator.
    ``chosen``: behind the pools lies the tile's choice, ``[planes, R, L]``
    (nonzero: row r of the tile attends to position ``plane * L + l``; a
    sparse layer's, in planes of one lane width as ``sparse_choice`` writes
    them), and EVERY step of a walk takes the masked form with its ``C //
    L`` planes of the choice, side by side, as one more term of its mask: a
    third form of a step beside open and edge. Every other instantiation
    traces what it traced."""
    pools = refs[:n_pool]
    chosen_ref = refs[n_pool] if chosen else None
    refs = refs[int(chosen):]
    o_ref = refs[n_pool]
    bufs = refs[n_pool + 1:2 * n_pool + 1]
    sems, plan_ref, q3_ref, len_ref, m_ref, l_ref, acc_ref = \
        refs[2 * n_pool + 1:]
    if heads_first:
        K, P, bs = bufs[0].shape[1:4]
    else:
        P, bs = bufs[0].shape[1:3]
        K = bufs[0].shape[3] if bufs[0].ndim == 5 else 1
    R, N, _ = q_ref.shape
    rep, T = N // K, meta_ref.shape[0] // (3 if indirect else 2)
    M, C = R * rep, P * bs
    Dv = acc_ref.shape[2]
    tile = pl.program_id(0)
    t0 = tile * R

    def choice(i, rows):
        """Step ``i``'s planes of the choice for ``rows`` of the tile, side
        by side as the step's columns lie: ``[rows, C]``."""
        n = C // chosen_ref.shape[2]
        planes = [chosen_ref[i * n + j, rows, :] for j in range(n)]
        return planes[0] if n == 1 else jnp.concatenate(planes, axis=1)

    def length(r):
        return meta_ref[t0 + r]

    def fetch(t, nblk, i, slot, start):
        """Start, or wait for, the copies into ``slot`` of blocks ``i*P ..``
        of row ``t``'s table that lie under ``nblk`` (``i`` counts fetch
        steps from block 0, also where a windowed walk starts later). A
        wait answers the start of the same ``(t, nblk, i, slot)``, also
        where another walk, a tile earlier, made that start."""
        def page(p, _):
            j = i * P + p

            @pl.when(j < nblk)
            def _():
                blk = tables_ref[meta_ref[2 * T + t] if indirect else t, j]
                for n, (pool, buf) in enumerate(zip(pools, bufs)):
                    copy = pltpu.make_async_copy(
                        pool.at[blk], buf.at[slot, :, p] if heads_first
                        else buf.at[slot, p], sems.at[n, slot])
                    copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, P, page, None)

    def plan(t, r, slot, live=True):
        """Finds the run that begins at row ``r`` of the tile whose first
        row is ``t`` (any tile of the call: the lengths and ``same`` lie in
        scalar memory whole) and leaves in ``plan_ref``, for its walk, its
        end, the least and the greatest of its lengths and the ``slot`` of
        its first step and, for the walk before it, its first fetch,
        ``(t, nblk, i)`` of :func:`fetch`: no block where no such run is
        ``live``. They lie in scalar memory and not in registers over a
        walk's steps: Moonlight's call alone read 1 % less so (PR 58)."""
        def shares(c):                 # row c[0] carries the row before's table
            return jnp.logical_and(
                c[0] < R, meta_ref[T + t + jnp.minimum(c[0], R - 1)] != 0)

        def take(c):
            n = meta_ref[t + c[0]]
            return c[0] + 1, jnp.minimum(c[1], n), jnp.maximum(c[2], n)

        n = meta_ref[t + r]
        r1, lo, hi = jax.lax.while_loop(shares, take, (r + 1, n, n))
        for at, x in enumerate((
                r1, lo, hi, slot, t + r, jnp.where(live, pl.cdiv(hi, bs), 0),
                step_ranges(lo, hi, C, window, False, jnp)[0])):
            plan_ref[at] = x

    def ahead():                       # the planned run's first fetch
        return plan_ref[4], plan_ref[5], plan_ref[6]

    @pl.when(tile == 0)
    def _open():
        # a fetch step skips the blocks past a walk's last; what the slots
        # hold there is masked out of the scores but multiplies the (zero)
        # probabilities, so it must be finite from the first step on
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        # the one fetch of a call that starts cold, and ahead of the tile's
        # own opening below
        plan(0, 0, 0)
        fetch(*ahead(), 0, True)

    # the tile's queries head-major, [K, R*rep, D]: row r of the tile is
    # rows r*rep .. of every KV head; its lengths beside them; its softmax
    # statistics and accumulator opened once, each run closes its own rows
    q = q_ref[...].astype(q3_ref.dtype).reshape(R, K, rep, q_ref.shape[2])
    q3_ref[...] = jnp.swapaxes(q, 0, 1).reshape(K, M, q_ref.shape[2])

    def put_length(r, _):
        len_ref[pl.ds(r * rep, rep), :] = jnp.full(
            (rep, _LANES), length(r), jnp.int32)

    jax.lax.fori_loop(0, R, put_length, None)
    # the statistics lie replicated over their 128 lanes: no step slices a
    # lane out or broadcasts one back
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def lanes(x, n):
        """``x [.., 128]``, every lane the same, as ``[.., n]``."""
        if n <= _LANES:
            return x[..., :n]
        if n % _LANES:
            return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))
        return jnp.concatenate([x] * (n // _LANES), axis=x.ndim - 1)

    def head_major(buf, slot):
        """A fetched slot as ``[K, C, D]`` in the products' type."""
        D = buf.shape[-1]
        if heads_first:
            return buf[slot].astype(mxu_dtype).reshape(K, C, D)
        if buf.ndim == 4:                      # a pool without a head axis
            return buf[slot].astype(mxu_dtype).reshape(1, C, D)
        # ``[C, K, D]`` as it lies is rows ``c*K + k`` of a matrix: head k
        # is every K-th row from row k, which a load reads strided, and no
        # value is transposed
        if buf.dtype.itemsize == 4:
            rows = buf.at[slot].reshape(C * K, D)
            return jnp.stack([rows[pl.ds(k, C, stride=K), :]
                              for k in range(K)]).astype(mxu_dtype)
        packed = mxu_dtype == jnp.bfloat16
        if buf.dtype == jnp.bfloat16 and K % 2 == 0 and not (packed
                                                             and C % 2):
            # rows of 16 bits lie two to a 32-bit word, heads 2j (the low
            # half) and 2j+1: the strided load takes the words
            words = buf.at[slot].bitcast(jnp.uint32).reshape(C * K // 2, D)
            low, high = jnp.uint32(0xffff), jnp.uint32(0xffff0000)
            heads = []
            for j in range(K // 2):
                if packed:
                    # bfloat16 products take positions 2c (low) and 2c+1
                    # of ONE head a word: the halves of an even and an odd
                    # position's words, put together as they are
                    even = words[pl.ds(j, C // 2, stride=K), :]
                    odd = words[pl.ds(K // 2 + j, C // 2, stride=K), :]
                    heads += [(even & low) | (odd << 16),
                              (even >> 16) | (odd & high)]
                else:
                    # a bfloat16 is the high half of the float32 it rounds
                    w = words[pl.ds(j, C, stride=K // 2), :]
                    heads += [w << 16, w & high]
            if packed:
                return pltpu.bitcast(jnp.stack(heads), jnp.bfloat16)
            return pltpu.bitcast(jnp.stack(heads),
                                 jnp.float32).astype(mxu_dtype)
        x = buf[slot].astype(mxu_dtype)
        return jnp.swapaxes(x.reshape(C, K, D), 0, 1)

    # bfloat16 products are pinned to the one precision Mosaic has for
    # them, whatever ``jax.default_matmul_precision`` says around the call
    # (under "highest" it refused them: ``Bad lhs type``); float32 products
    # take the context's, as they did
    precision = jax.lax.Precision.DEFAULT if mxu_dtype == jnp.bfloat16 \
        else None

    def online_softmax(i, rows, limit, slot, pick=None):
        """Fetch step ``i`` of the query rows ``rows`` (a slice of the
        tile's ``R*rep``) against the keys and values in ``slot``.
        ``limit`` broadcasts against the ``[K, rows, C]`` scores:
        a row sees the columns under it (and, with a window, no more than
        ``window`` of them); None on an OPEN step, whose every column
        every row sees: no mask is built and nothing selected. ``pick(i)``:
        the rows' choice among the step's columns, one more term of the
        mask."""
        kt = head_major(bufs[0], slot)
        vt = kt[:, :, :Dv] if n_pool == 1 else head_major(bufs[1], slot)
        s = jax.lax.dot_general(
            q3_ref[:, rows, :].astype(mxu_dtype), kt,
            (((2,), (2,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32) * scale
        if limit is not None:
            col = i * C + jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
            live = col < limit
            if window is not None:
                live &= col >= limit - window
            if pick is not None:
                live &= pick(i)
            s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[:, rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - lanes(m_new, C))
        if limit is not None:
            # a row outside the run has no live column: its max stays
            # NEG_INF, so the probabilities are zeroed by the mask, not by
            # the exponent
            p = jnp.where(live, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(mxu_dtype), vt, (((2,), (1,)), ((0,), (0,))),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[:, rows, :] = m_new
        l_ref[:, rows, :] = alpha * l_ref[:, rows, :] + jnp.sum(
            p, axis=2, keepdims=True)
        acc_ref[:, rows, :] = acc_ref[:, rows, :] * lanes(alpha, Dv) + pv

    def walk(r0):
        """The run at row ``r0`` of the tile, as ``plan_ref`` holds it: rows
        ``r0 .. r1`` carry one table. Walk it once, ``P`` blocks a step,
        the chain's next fetch in flight while a step is computed: this
        walk's step i+1 or, on its last step, the first of the run after
        it, in this tile or the next. Returns ``r1``."""
        r1, lo, hi, slot0 = (plan_ref[at] for at in range(4))
        t = t0 + r0
        nblk = pl.cdiv(hi, bs)
        # a row alone (a decode row) meets the blocks alone; a run meets
        # them with the whole tile, rows outside it masked out. Which of
        # the two, and which steps are open, is settled once a walk
        alone = r1 - r0 == 1
        begin, first, last, end = step_ranges(
            lo, hi, C, window, alone | (r1 - r0 == R), jnp)
        # the run after this one begins at r1 or, past the tile's last row,
        # at the next tile's first; the call's last run has none (a dummy
        # plan of rows that are there, no block of which is fetched). Its
        # first step takes the slot after this walk's last
        wraps = r1 == R
        live = jnp.logical_not(wraps & (tile == pl.num_programs(0) - 1))
        after = (slot0 + jnp.maximum(end - begin, 0)) & 1
        shift = slot0 - begin
        plan(jnp.where(wraps & live, t0 + R, t0), jnp.where(wraps, 0, r1),
             after, live)

        def steps(start, stop, rows, limit, pick=None):
            def step(i, _):
                slot = (i + shift) & 1
                more = i + 1 < end

                # two starts and not one of selected operands: a step's
                # pages are then copied from operands that do not change
                # over the walk (Moonlight's call alone read 5 % over the
                # parent's with the select, 1 % so: PERF.md, PR 58)
                @pl.when(more)
                def _():
                    fetch(t, nblk, i + 1, 1 - slot, True)

                @pl.when(jnp.logical_not(more))
                def _():
                    fetch(*ahead(), 1 - slot, True)

                fetch(t, nblk, i, slot, False)
                online_softmax(i, rows, limit, slot, pick)

            jax.lax.fori_loop(start, stop, step, None)

        def attend(rows, limit, pick=None):
            if chosen:                     # no step of a choice is open
                return steps(begin, end, rows, limit, pick)
            if window is not None:         # else the walk begins open
                steps(begin, first, rows, limit)
            steps(first, last, rows, None)
            steps(last, end, rows, limit)

        def alone_form():
            pick = None
            if chosen:                     # the row's own planes of a step
                pick = lambda i: (choice(i, pl.ds(r0, 1))    # noqa: E731
                                  != 0)[None]                   # [1, 1, C]
            attend(pl.ds(r0 * rep, rep), length(r0), pick)

        def tile_form():
            row = jax.lax.broadcasted_iota(jnp.int32, (1, M, 1), 1)
            limit = jnp.where((row >= r0 * rep) & (row < r1 * rep),
                              len_ref[:, 0:1][None], 0)         # [1, M, 1]
            pick = None
            if chosen:
                # a row's choice is its ``rep`` query rows': a product with
                # ``E[m, r] = (m // rep == r)`` repeats the step's ``[R, C]``
                # planes ``rep`` times a row on the MXU, exactly (one term a
                # sum), where a repeat along sublanes is a relayout
                m = jax.lax.broadcasted_iota(jnp.int32, (M, R), 0)
                r = jax.lax.broadcasted_iota(jnp.int32, (M, R), 1)
                spread = ((m >= r * rep) & (m < (r + 1) * rep)).astype(
                    chosen_ref.dtype)
                pick = lambda i: (jax.lax.dot_general(        # noqa: E731
                    spread, choice(i, slice(None)), (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) > 0.5)[None]
            attend(slice(None), limit, pick)

        jax.lax.cond(alone, alone_form, tile_form)

        @pl.when(end <= begin)
        def _():        # no step (no row has a length) handed the chain on
            fetch(*ahead(), after, True)

        def put(r, _):
            rows = pl.ds(r * rep, rep)
            l = l_ref[:, rows, :]
            out = acc_ref[:, rows, :] / lanes(jnp.where(l == 0.0, 1.0, l), Dv)
            o_ref[pl.ds(r, 1)] = out.reshape(1, N, Dv).astype(o_ref.dtype)

        jax.lax.fori_loop(r0, r1, put, None)
        return r1

    jax.lax.while_loop(lambda r: r < R, walk, 0)


def _geometry(q, pools, value_dim, heads_first):
    """(KV heads, block size, rows a tile, blocks a fetch step) of a call,
    from its operands' shapes alone."""
    if heads_first:
        K, bs = pools[0].shape[1:3]
    else:
        bs = pools[0].shape[1]
        K = pools[0].shape[2] if pools[0].ndim == 4 else 1
    N = q.shape[1]
    R = tile_rows(N, value_dim)
    return K, bs, R, _blocks_per_fetch(
        bs, sum(K * x.shape[-1] * x.dtype.itemsize for x in pools), R * N,
        pools[0].ndim == 4)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "value_dim", "scale", "name", "mxu_dtype", "interpret", "window",
    "heads_first", "indirect"))
def _tiles(tables, meta, q, *pools, value_dim, scale, name, mxu_dtype,
           interpret, window=None, heads_first=False, indirect=False,
           chosen=None):
    """The kernel over whole tiles: ``pools`` the key pool and the value
    pool, or the key pool alone where the value is the first ``value_dim``
    columns of the key's block. Jitted (inlined into the caller's program)
    for its trace cache alone: the kernel body is traced once per set of
    operand shapes, not once per program that calls it."""
    T, N, _ = q.shape
    K, bs, R, P = _geometry(q, pools, value_dim, heads_first)
    rep = N // K
    # a decode row's queries are ``rep`` rows of the head-major scratch
    # from a run-time offset: a 16-bit scratch takes that only where the
    # rows fill its packed sublane tile (16), so it is float32 otherwise
    # (a group of 6 query heads a KV head) and cast where it is multiplied
    q3_dtype = mxu_dtype if rep % 16 == 0 else jnp.float32
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // R,),
        in_specs=[pl.BlockSpec((R,) + q.shape[1:],
                               lambda i, tbl, meta: (i, 0, 0))]
        + [hbm] * len(pools)
        # a tile's choice, every step's plane of it, lies in VMEM
        + ([] if chosen is None else [pl.BlockSpec(
            (chosen.shape[0], R, chosen.shape[2]),
            lambda i, tbl, meta: (0, i, 0))]),
        out_specs=pl.BlockSpec((R, N, value_dim),
                               lambda i, tbl, meta: (i, 0, 0)),
        scratch_shapes=[pltpu.VMEM(
            (2, K, P) + x.shape[2:] if heads_first else (2, P) + x.shape[1:],
            x.dtype) for x in pools] + [
            pltpu.SemaphoreType.DMA((len(pools), 2)),
            pltpu.SMEM((7,), jnp.int32),
            pltpu.VMEM((K, R * rep, q.shape[2]), q3_dtype),
            pltpu.VMEM((R * rep, _LANES), jnp.int32),
            pltpu.VMEM((K, R * rep, _LANES), jnp.float32),
            pltpu.VMEM((K, R * rep, _LANES), jnp.float32),
            pltpu.VMEM((K, R * rep, value_dim), jnp.float32),
        ],
    )
    compiler_params = None
    if not interpret:
        # the fetch slots are cleared on the first step and reused by the
        # next, so the tiles run in order
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    return pl.pallas_call(
        functools.partial(_kernel, n_pool=len(pools), scale=scale,
                          mxu_dtype=mxu_dtype, window=window,
                          heads_first=heads_first, indirect=indirect,
                          chosen=chosen is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, N, value_dim), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name=name,
    )(tables, meta, q, *pools, *(() if chosen is None else (chosen,)))


def _walk(q, pools, tables, lengths, *, value_dim, scale, name, mxu_dtype,
          interpret, window=None, heads_first=False, row_table=None,
          chosen=None):
    """Pads the rows to whole tiles, says which rows share a table, and
    runs the kernel: what both entry points below are."""
    if interpret is None:
        interpret = _use_interpret()
    Tn, N, _ = q.shape
    pad = -Tn % tile_rows(N, value_dim)
    if chosen is not None:
        assert row_table is not None and window is None
        K, bs, _, P = _geometry(q, pools, value_dim, heads_first)
        planes, rows, L = chosen.shape
        assert rows >= Tn + pad and P * bs % L == 0 \
            and planes * L >= tables.shape[1] * bs, (
                chosen.shape, Tn + pad, P * bs, tables.shape)
        chosen = chosen[:, :Tn + pad]
        short = -planes % (P * bs // L)
        if short:       # a step reads whole planes (the serving tiers' are)
            chosen = jnp.pad(chosen, ((0, short), (0, 0), (0, 0)))
    if pad:                            # pad rows: zero table, length 1
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        if row_table is None:
            tables = jnp.pad(tables, ((0, pad), (0, 0)))
        lengths = jnp.pad(lengths, (0, pad), constant_values=1)
    if row_table is not None:
        # one table a sequence (its row 0 the pad rows'): the tables lie in
        # scalar memory, where a row's own copy of a wide table for each
        # of thousands of rows has no room
        which = jnp.pad(row_table.astype(jnp.int32), (0, pad))
        tables = jnp.pad(tables, ((0, 0),
                                  (0, -tables.shape[1] % _TABLE_COLS)))
        same = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                                which[1:] == which[:-1]])
        meta = jnp.concatenate([lengths.astype(jnp.int32),
                                same.astype(jnp.int32), which])
        return _tiles(tables, meta, q, *pools, value_dim=value_dim,
                      scale=scale, name=name, mxu_dtype=mxu_dtype,
                      interpret=interpret, window=window,
                      heads_first=heads_first, indirect=True,
                      chosen=chosen)[:Tn]
    # every table tier of a tick bucket hands the kernel the same shapes, so
    # its body is traced once a bucket, not once a (Tn, mb) program; the
    # columns added are never read (walks end at ceil(length / bs))
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % _TABLE_COLS)))
    same = jnp.concatenate([
        jnp.zeros((1,), jnp.bool_),
        jnp.all(tables[1:] == tables[:-1], axis=1)])
    meta = jnp.concatenate([lengths.astype(jnp.int32),
                            same.astype(jnp.int32)])
    return _tiles(tables, meta, q, *pools, value_dim=value_dim, scale=scale,
                  name=name, mxu_dtype=mxu_dtype, interpret=interpret,
                  window=window, heads_first=heads_first)[:Tn]


def paged_attention(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: Optional[bool] = None, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    heads_first: bool = False,
                    name: str = "paged_attention",
                    mxu_dtype=jnp.float32,
                    row_table: Optional[jax.Array] = None,
                    chosen: Optional[jax.Array] = None) -> jax.Array:
    """Drop-in for ``models.paged.paged_attention_reference``. ``scale``:
    the scores' factor where it is not ``D ** -0.5``; ``window``: each row
    attends to its last ``window`` positions alone, and the table may then
    be a RING (column ``c`` naming the block that holds positions
    ``c*bs ..`` now: a walk reads columns from the window's start on);
    ``heads_first``: the pools are ``[NB, K, bs, D]``, which a head count
    that is no multiple of 8 needs; ``name``: the Mosaic call's name in a
    device trace; ``mxu_dtype``: the products' operand type (float32: the
    homogeneous cells are not MXU-bound; bfloat16 where a long chunk
    against a long context is); ``row_table`` [T]: ``tables`` is then one
    table a SEQUENCE, ``[sequences, MB]`` with row 0 the pad rows', and
    this names each row's (rows of one sequence are told by it);
    ``chosen`` [planes, T', L] (``T'``: the rows up to whole tiles; ``L``
    divides a fetch step's positions, :func:`step_positions`: one lane
    width as ``sparse_choice`` writes it; with ``row_table``): row t
    attends to position ``plane * L + l`` only where the entry is nonzero
    (and the position lies under its length): a sparse layer's choice,
    which every step of the walk then takes as a mask, its own planes of
    it side by side."""
    D, K = q.shape[2], kpool.shape[1 if heads_first else 2]
    assert D == kpool.shape[3] and q.shape[1] % K == 0
    return _walk(q, (kpool, vpool), tables, lengths, value_dim=D,
                 scale=D ** -0.5 if scale is None else scale,
                 name=name, mxu_dtype=mxu_dtype, interpret=interpret,
                 window=window, heads_first=heads_first,
                 row_table=row_table, chosen=chosen)


def step_positions(q, kpool, vpool) -> int:
    """Cache positions a fetch step of :func:`paged_attention` meets, and
    so the positions of the planes of ``chosen`` that a step reads: the
    kernel's own rule of the operands' shapes (arrays or their
    ``ShapeDtypeStruct``)."""
    _, bs, _, P = _geometry(q, (kpool, vpool), q.shape[2], False)
    return bs * P


def latent_paged_attention(q: jax.Array, pool: jax.Array,
                           tables: jax.Array, lengths: jax.Array,
                           value_dim: int, scale: float,
                           interpret: Optional[bool] = None,
                           row_table: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Weight-absorbed latent (MLA) attention over the paged latent pool:
    multi-query attention with ONE KV head whose key is a cache position's
    whole row (``c_kv ++ k_pe``, zero-padded to a lane multiple) and whose
    value is that row's first ``value_dim`` columns (``c_kv``), so each
    position is read once. The same walk as :func:`paged_attention`,
    instantiated with one pool and no head axis.

    q [T, N, W]: the queries in latent space (``W_uk`` folded in) beside
    their rope part, padded like the pool's rows; pool [NB, bs, W]; returns
    the attended latents [T, N, value_dim] (``W_uv`` is the caller's). The
    products take bf16 operands (a prompt chunk against a long context is
    MXU-bound, unlike the dense cells' shapes) and accumulate in float32;
    the jnp path rounds its probabilities the same way. ``row_table``: as
    :func:`paged_attention`'s (one table a sequence and each row's)."""
    assert q.shape[2] == pool.shape[2] and pool.ndim == 3
    return _walk(q, (pool,), tables, lengths, value_dim=value_dim,
                 scale=float(scale),
                 name="latent_paged_attention", mxu_dtype=jnp.bfloat16,
                 interpret=interpret, row_table=row_table)
