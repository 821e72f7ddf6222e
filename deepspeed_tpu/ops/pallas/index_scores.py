"""Pallas kernel: a sparse-attention indexer's scores over the paged store of
index keys, ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` for every
row ``t`` of a tick and every cache position ``s`` of its sequence.

The walk is ``paged_attention.py``'s: one grid step a tile of ``R``
consecutive rows of the flat token batch, the store in HBM, a tile split
into RUNS of rows that carry one block table (``row_table``: one table a
sequence slot and each row's slot), each run's table walked ONCE with
double-buffered ``make_async_copy`` fetches of ``P`` blocks whose trip count
is read from the prefetched lengths. The step differs: where that kernel
keeps an online softmax and returns a value a row, this one has no state
between steps and returns a score a (row, position):

- a run of two or more rows meets a fetched ``[C, W]`` slab of index keys
  with all the tile's rows in one product, ``[R * heads, W] x [W, C]``, takes
  ``relu``, and sums the heads under their weights with a second product,
  ``[R, R * heads] x [R * heads, C]``, whose left operand is the tile's
  weights laid block-diagonally (built beside the call: no value is
  broadcast along lanes or reduced along sublanes inside the kernel); the
  rows outside the run keep what they hold;
- a run of one row (a decode row) walks alone: ``[heads, W] x [W, C]`` and
  ``[1, heads] x [heads, C]``.

The result is laid ``[S / 128, T, 128]``: lane tile ``c`` of every row
together, so that a step writes whole ``[R, 128]`` planes at a leading
index, and ``paged_attention(chosen=)`` reads a step's plane of the choice
made from these scores the same way. Position ``s`` of row ``t`` is
``out[s // 128, t, s % 128]``; what lies at or past a row's length was
either never written or scored against stale keys: the caller masks it.

Arithmetic: the keys as stored, the queries in the keys' type, float32 from
the first product on; the second product is float32 at HIGHEST precision
(a rounded score would exchange positions near the cut).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.paged_attention import _LANES, _use_interpret

#: rows of a tile, and the most cache positions a fetch step scores
TILE_ROWS = 32
_STEP_POSITIONS = 512


def table_cols(blocks: int, block_size: int) -> int:
    """Columns a table of ``blocks`` is widened to: whole lane tiles of
    positions and no more (the scores and the choice made from them are as
    wide as the table reaches: a quarter tier of 144 blocks widened to 192
    would be a third more of both)."""
    unit = max(1, _LANES // block_size)
    return -(-blocks // unit) * unit


def step_positions(block_size: int, reach: int) -> int:
    """Cache positions a fetch step scores: whole blocks and whole lane
    tiles, as many of ``_STEP_POSITIONS`` as divide ``reach`` (the
    positions a table covers, a multiple of 128)."""
    assert reach % _LANES == 0 and _LANES % block_size == 0, (reach,
                                                              block_size)
    tiles = reach // _LANES
    return _LANES * max(n for n in (4, 2, 1) if tiles % n == 0
                        and n * _LANES <= _STEP_POSITIONS)


def _kernel(tables_ref, meta_ref, q_ref, w_ref, wd_ref, store, o_ref,
            buf, sem, *, product):
    P, bs, W = buf.shape[1:]
    R, H = w_ref.shape
    C = P * bs
    T = meta_ref.shape[0] // 3
    t0 = pl.program_id(0) * R

    def length(r):
        return meta_ref[t0 + r]

    def same(r):
        return meta_ref[T + t0 + r] != 0

    @pl.when(pl.program_id(0) == 0)
    def _clear():
        # a step skips the blocks past a walk's last: what the slot holds
        # there is scored (and masked by the caller), so it must be finite
        buf[...] = jnp.zeros_like(buf)

    def fetch(t, nblk, i, slot, start):
        def page(p, _):
            j = i * P + p

            @pl.when(j < nblk)
            def _():
                copy = pltpu.make_async_copy(
                    store.at[tables_ref[meta_ref[2 * T + t], j]],
                    buf.at[slot, p], sem.at[slot])
                copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, P, page, None)

    def scores(q, wd, slot):
        """``wd [rows, heads]`` x relu(``q [heads, W]`` x keys) -> [rows, C]
        float32."""
        keys = buf[slot].reshape(C, W)
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())), precision=product,
            preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            wd, jnp.maximum(s, 0.0), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def walk(r0, r1):
        t = t0 + r0
        hi = jax.lax.fori_loop(
            r0, r1, lambda r, n: jnp.maximum(n, length(r)), jnp.int32(0))
        nblk = pl.cdiv(hi, bs)

        def steps(step_of):
            def step(i, _):
                slot = i % 2

                @pl.when((i + 1) * P < nblk)
                def _():
                    fetch(t, nblk, i + 1, 1 - slot, True)

                fetch(t, nblk, i, slot, False)
                step_of(i, slot)

            jax.lax.fori_loop(0, pl.cdiv(hi, C), step, None)

        def alone_form():
            q = q_ref[pl.ds(r0, 1)].reshape(H, W)
            wd = w_ref[pl.ds(r0, 1), :]

            def step(i, slot):
                out = scores(q, wd, slot)                       # [1, C]
                for n in range(C // _LANES):
                    o_ref[i * (C // _LANES) + n, pl.ds(r0, 1), :] = \
                        out[:, n * _LANES:(n + 1) * _LANES]

            steps(step)

        def tile_form():
            q = q_ref[...].reshape(R * H, W)
            wd = wd_ref[...]
            row = jax.lax.broadcasted_iota(jnp.int32, (R, _LANES), 0)
            mine = (row >= r0) & (row < r1)

            def step(i, slot):
                out = scores(q, wd, slot)                       # [R, C]
                for n in range(C // _LANES):
                    at = i * (C // _LANES) + n
                    o_ref[at] = jnp.where(
                        mine, out[:, n * _LANES:(n + 1) * _LANES], o_ref[at])

            steps(step)

        fetch(t, nblk, 0, 0, True)
        jax.lax.cond(r1 - r0 == 1, alone_form, tile_form)

    def next_run(r0):
        r1 = jax.lax.while_loop(
            lambda r: jnp.logical_and(r < R, same(jnp.minimum(r, R - 1))),
            lambda r: r + 1, r0 + 1)
        walk(r0, r1)
        return r1

    jax.lax.while_loop(lambda r: r < R, next_run, 0)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("name", "interpret"))
def _tiles(tables, meta, q, w, wd, store, *, name, interpret):
    T, H, W = q.shape
    bs = store.shape[1]
    reach = tables.shape[1] * bs
    C = step_positions(bs, reach)
    R = TILE_ROWS
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // R,),
        in_specs=[pl.BlockSpec((R, H, W), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((R, H), lambda i, *_: (i, 0)),
                  pl.BlockSpec((R, R * H), lambda i, *_: (i, 0)), hbm],
        out_specs=pl.BlockSpec((reach // _LANES, R, _LANES),
                               lambda i, *_: (0, i, 0)),
        scratch_shapes=[pltpu.VMEM((2, C // bs, bs, W), store.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    compiler_params = None
    if not interpret:
        # the fetch slots are cleared on the first step: tiles in order
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024)
    product = jax.lax.Precision.DEFAULT if store.dtype == jnp.bfloat16 \
        else jax.lax.Precision.HIGHEST
    return pl.pallas_call(
        functools.partial(_kernel, product=product),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((reach // _LANES, T, _LANES),
                                       jnp.float32),
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(tables, meta, q, w, wd, store)


def index_scores(q: jax.Array, w: jax.Array, store: jax.Array,
                 tables: jax.Array, lengths: jax.Array,
                 row_table: jax.Array, interpret: Optional[bool] = None, *,
                 name: str = "index_scores") -> jax.Array:
    """The indexer's scores of a tick's rows, ``[S / 128, T', 128]``
    float32 (``T'``: the rows up to whole tiles of ``TILE_ROWS``; ``S``:
    the tables' reach up to whole lane tiles).

    q [T, heads, W]: the indexer's queries, as wide as a stored key (zeros
    beyond the indexer's own columns); w [T, heads]: the heads' weights;
    store [NB, bs, W]: the index keys, a row a position; tables [slots,
    MB]: one table a sequence slot, row 0 the pad rows'; lengths [T];
    row_table [T]: each row's slot."""
    if interpret is None:
        interpret = _use_interpret()
    Tn, H, W = q.shape
    bs = store.shape[1]
    assert W == store.shape[2] and store.ndim == 3
    pad = -Tn % TILE_ROWS
    q = jnp.pad(q.astype(store.dtype), ((0, pad), (0, 0), (0, 0)))
    # (float32: a row alone reads its weights at a run-time sublane)
    w = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0)))
    lengths = jnp.pad(lengths, (0, pad), constant_values=1)
    which = jnp.pad(row_table.astype(jnp.int32), (0, pad))
    tables = jnp.pad(tables, ((0, 0), (0, table_cols(
        tables.shape[1], bs) - tables.shape[1])))
    same = jnp.concatenate([jnp.zeros((1,), jnp.bool_),
                            which[1:] == which[:-1]])
    meta = jnp.concatenate([lengths.astype(jnp.int32),
                            same.astype(jnp.int32), which])
    # the tile's weights block-diagonally: row r of a tile holds its heads'
    # weights at columns r * heads .., so that ``wd x relu(scores)`` sums
    # each row's own heads
    r = jnp.arange(Tn + pad) % TILE_ROWS
    wd = (w[:, None, :] * (r[:, None] == jnp.arange(TILE_ROWS))[:, :, None]
          .astype(w.dtype)).reshape(Tn + pad, TILE_ROWS * H)
    return _tiles(tables, meta, q, w, wd, store, name=name,
                  interpret=interpret)


def index_scores_reference(q: jax.Array, w: jax.Array, store: jax.Array,
                           tables: jax.Array) -> jax.Array:
    """:func:`index_scores` in plain jnp, ``[T, S]`` (the CPU path and the
    kernel's oracle): ``tables [T, MB]`` a table a row; it gathers every
    row's whole table, so it is for short tables only."""
    Tn = q.shape[0]
    keys = store[tables].reshape(Tn, -1, store.shape[-1])       # [T, S, W]
    s = jnp.einsum("tjw,tsw->tjs", q.astype(store.dtype), keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(s), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
