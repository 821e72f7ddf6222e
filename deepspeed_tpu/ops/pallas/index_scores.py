"""Pallas kernel: a sparse-attention indexer's scores over the paged store of
index keys, ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` for every
row ``t`` of a tick and every cache position ``s`` of its sequence.

The walk is ``paged_attention.py``'s: one grid step a tile of ``R``
consecutive rows of the flat token batch, the store in HBM, a tile split
into RUNS of rows that carry one block table (``row_table``: one table a
sequence slot and each row's slot), each run's table walked ONCE with
``make_async_copy`` fetches of ``P`` blocks whose trip count is read from
the prefetched lengths. The fetches land in a ring of ``_SLOTS`` slabs, all
but the one a step reads in flight ahead of it, and a step scores up to
``_STEP_POSITIONS`` positions: what a step costs whatever it carries (its
fetches' issue and latency, the loop's turn: ~0.2 us of a 512-position
step's 0.4-0.7, PERF.md, PR 56) is paid half as often and behind two more
fetches than a double buffer hides. The step differs: where that kernel
keeps an online softmax and returns a value a row, this one has no state
between steps and returns a score a (row, position):

- a run of two or more rows meets a fetched ``[C, W]`` slab of index keys
  with all the tile's rows in ONE product, ``[heads * R, W] x [W, C]``, the
  queries laid head-major (the wrapper hands them over ``[heads, T', W]``
  too), so that the result is a slab ``[R, C]`` a head: the ``R`` rows'
  scores under that head. The heads' weighted sum is taken where that
  result lies, in float32 on the vector unit: ``out = wb[0] * relu(s[0])``,
  then ``out += wb[j] * relu(s[j])`` for ``j = 1 .. heads - 1`` in
  ascending order, ``wb[j]`` the rows' weight of head ``j`` repeated over a
  lane tile (a ``[heads * R, 128]`` scratch built once a tile, before its
  walks: no step broadcasts along lanes or reduces along sublanes); the
  rows outside the run keep what they hold;
- a run of one row (a decode row) walks alone: ``[heads, W] x [W, C]``, its
  weights as a column (a strided read of the same scratch) times ``relu``
  of the result, and one sum over the ``heads`` sublanes.

The result is laid ``[S / 128, T, 128]``: lane tile ``c`` of every row
together, so that a step writes whole ``[R, 128]`` planes at a leading
index, and ``paged_attention(chosen=)`` reads a step's plane of the choice
made from these scores the same way. Position ``s`` of row ``t`` is
``out[s // 128, t, s % 128]``; what lies at or past a row's length was
either never written or scored against stale keys: the caller masks it.

Arithmetic: the keys as stored, the queries in the keys' type, float32 from
the product on (a rounded score would exchange positions near the cut):
``relu``, the multiply by a weight and the sum over the heads are float32
operations of the vector unit, one rounding each. A tile's sum runs over
the heads in ascending order, left to right; a row alone's is the sublane
reduction's (a tree over the heads), so the two forms may differ in a
score's last bit, as either does from the plain form's product.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.paged_attention import _LANES, _use_interpret

#: rows of a tile, the most cache positions a fetch step scores, and the
#: slabs of fetched keys a walk holds (one read, the others in flight)
TILE_ROWS = 32
_STEP_POSITIONS = 1024
_SLOTS = 4


def table_cols(blocks: int, block_size: int) -> int:
    """Columns a table of ``blocks`` is widened to: whole lane tiles of
    positions and no more (the scores and the choice made from them are as
    wide as the table reaches: a quarter tier of 144 blocks widened to 192
    would be a third more of both)."""
    unit = max(1, _LANES // block_size)
    return -(-blocks // unit) * unit


def step_positions(block_size: int, reach: int) -> int:
    """Cache positions a fetch step scores: whole blocks and whole lane
    tiles, the most of them within ``_STEP_POSITIONS`` that divide
    ``reach`` (the positions a table covers, a multiple of 128): 1,024 of
    a tier of 72 or 144 blocks of 128, 768 of one of 36."""
    assert reach % _LANES == 0 and _LANES % block_size == 0, (reach,
                                                              block_size)
    tiles = reach // _LANES
    return _LANES * max(n for n in range(1, _STEP_POSITIONS // _LANES + 1)
                        if tiles % n == 0)


def _kernel(tables_ref, meta_ref, q_ref, qh_ref, w_ref, store, o_ref,
            buf, wb, sem, *, product):
    NS, P, bs, W = buf.shape
    R, H = w_ref.shape
    C = P * bs
    planes = C // _LANES
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

    # the tile's weights head-major, each repeated over a lane tile: rows
    # j * R .. of ``wb`` are the R rows' weight of head j. Built once a
    # tile, so that no step broadcasts along lanes
    w = w_ref[...]
    for j in range(H):
        wb[j * R:(j + 1) * R, :] = jnp.broadcast_to(w[:, j:j + 1],
                                                    (R, _LANES))

    def fetch(t, nblk, i, slot, start):
        def page(p, _):
            j = i * P + p

            @pl.when(j < nblk)
            def _():
                copy = pltpu.make_async_copy(
                    store.at[tables_ref[meta_ref[2 * T + t], j]],
                    buf.at[slot, p], sem.at[slot])
                copy.start() if start else copy.wait()

        # unrolled: up to eight descriptors a step behind a rolled loop's
        # branches cost a chunk tick's call 14 %, a decode tick's 35 %
        jax.lax.fori_loop(0, P, page, None, unroll=True)

    def relu_scores(q, slot):
        """relu(``q [rows, W]`` x keys) -> [rows, C] float32."""
        s = jax.lax.dot_general(
            q, buf[slot].reshape(C, W), (((1,), (1,)), ((), ())),
            precision=product, preferred_element_type=jnp.float32)
        return jnp.maximum(s, 0.0)

    def walk(r0, r1):
        t = t0 + r0
        hi = jax.lax.fori_loop(
            r0, r1, lambda r, n: jnp.maximum(n, length(r)), jnp.int32(0))
        nblk = pl.cdiv(hi, bs)

        def steps(step_of):
            def step(i, _):
                ahead = i + NS - 1

                @pl.when(ahead * P < nblk)
                def _():
                    fetch(t, nblk, ahead, ahead % NS, True)

                fetch(t, nblk, i, i % NS, False)
                step_of(i, i % NS)

            jax.lax.fori_loop(0, pl.cdiv(hi, C), step, None)

        def alone_form():
            q = q_ref[pl.ds(r0, 1)].reshape(H, W)
            # [H, 128]: the row's weight of head j along sublane j
            wcol = wb[pl.ds(r0, H, stride=R), :]

            def step(i, slot):
                s = relu_scores(q, slot)                        # [H, C]
                for n in range(planes):
                    o_ref[i * planes + n, pl.ds(r0, 1), :] = jnp.sum(
                        wcol * s[:, n * _LANES:(n + 1) * _LANES], axis=0,
                        keepdims=True)

            steps(step)

        def tile_form():
            q = qh_ref[...].reshape(H * R, W)
            row = jax.lax.broadcasted_iota(jnp.int32, (R, _LANES), 0)
            mine = (row >= r0) & (row < r1)

            def step(i, slot):
                s = relu_scores(q, slot)        # [H * R, C]: a slab a head
                for n in range(planes):
                    at = i * planes + n
                    lanes = slice(n * _LANES, (n + 1) * _LANES)
                    out = wb[0:R, :] * s[0:R, lanes]
                    for j in range(1, H):
                        rows = slice(j * R, (j + 1) * R)
                        out = out + wb[rows, :] * s[rows, lanes]
                    o_ref[at] = jnp.where(mine, out, o_ref[at])

            steps(step)

        # a walk's first fetches; then step i starts the fetch of step
        # i + NS - 1 into the slot that step i - 1 read
        for ahead in range(NS - 1):
            @pl.when(ahead * P < nblk)
            def _(ahead=ahead):
                fetch(t, nblk, ahead, ahead, True)

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
def _tiles(tables, meta, q, w, store, *, name, interpret):
    T, H, W = q.shape
    bs = store.shape[1]
    reach = tables.shape[1] * bs
    C = step_positions(bs, reach)
    R = TILE_ROWS
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // R,),
        # the queries twice: row-major for a row alone (it reads its heads
        # at a run-time LEADING index; a 16-bit array takes a run-time
        # sublane at whole packed tiles alone), head-major for a tile
        in_specs=[pl.BlockSpec((R, H, W), lambda i, *_: (i, 0, 0)),
                  pl.BlockSpec((H, R, W), lambda i, *_: (0, i, 0)),
                  pl.BlockSpec((R, H), lambda i, *_: (i, 0)), hbm],
        out_specs=pl.BlockSpec((reach // _LANES, R, _LANES),
                               lambda i, *_: (0, i, 0)),
        scratch_shapes=[pltpu.VMEM((_SLOTS, C // bs, bs, W), store.dtype),
                        pltpu.VMEM((H * R, _LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((_SLOTS,))])
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
    )(tables, meta, q, q.transpose(1, 0, 2), w, store)


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
    return _tiles(tables, meta, q, w, store, name=name, interpret=interpret)


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
