"""The recurrence of a Mamba-2 layer (state-space duality,
``models/hybrid.py``) in the two forms a serving tick needs, over a store of
states float32 (a matrix ``[channels, state]`` a head a sequence, 4 MB at
128 heads of 64 x 128; stored ``[rows, heads / k, N, k x P]``, the state
values down a tile's rows and ``k`` heads' channels along its lanes:
:func:`store_shape`) that is updated in place:

* :func:`ssd_step`: rows that are runs of one (decode rows), a Mosaic
  kernel: a grid step reads a row's matrices, decays each by its head's
  scalar, adds the row's ``delta x B^T``, writes them back and reads them
  out by the group's ``C``. One read and one write of the state a row a
  layer, which is all a decode tick's state-space layers cost; the grid is
  the rows that are there, not the bucket.
* :func:`ssd_chunk`: every other run, in the chunked form (within a chunk
  of ``chunk`` rows ``(L o C B^T) X`` as matrix products, across chunks the
  carried state), segmented at the starts of runs, a Mosaic kernel under
  the named scope ``ssd_chunk`` whose grid is the PIECES of chunks that hold
  a row of a run, and no more: a run's state is read at its first piece,
  carried in VMEM from piece to piece as the store has it and written after
  its last, and never exists a row at a time; nothing of ``[chunks, heads,
  L, L]`` exists outside VMEM. :func:`ssd_chunk_reference` is the same in
  plain XLA (all of the bucket's chunks at once, then a loop over the
  pieces): the CPU path, the form autodiff goes through and the kernel's
  oracle.

Both compute, a head, ``S = a S + delta x B^T; y = S C``
(``hybrid.ssd_recurrence`` is the arbiter).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.kda import _dot, _pieces

_LANES = 128


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# the store's layout
# --------------------------------------------------------------------------- #

def heads_a_tile(heads: int, groups: int, channels: int) -> int:
    """Heads whose matrices lie side by side along the lanes of one tile of
    the store: as many of a GROUP's heads as fill the 128 lanes (2 of 64
    channels), so that a tile's rows are the state values ``n`` (what ``B``
    and ``C`` run over: a sum over them is a sum of whole registers, not a
    reduction across lanes) and its lanes ``(head, channel)``."""
    hg = heads // groups
    return max(k for k in range(1, hg + 1)
               if hg % k == 0 and k * channels <= max(_LANES, channels))


def store_shape(heads: int, groups: int, channels: int, state: int) -> tuple:
    """A sequence's row of the store: ``[heads / k, N, k x P]``."""
    k = heads_a_tile(heads, groups, channels)
    return (heads // k, state, k * channels)


def to_store(s: jax.Array, groups: int) -> jax.Array:
    """``[..., heads, P, N]`` (the equations' order) -> the store's."""
    *lead, nh, P, N = s.shape
    k = heads_a_tile(nh, groups, P)
    s = s.reshape(*lead, nh // k, k, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, nh // k, N, k * P)


def from_store(s: jax.Array, heads: int) -> jax.Array:
    """The store's ``[..., heads / k, N, k x P]`` -> ``[..., heads, P, N]``."""
    *lead, J, N, W = s.shape
    k = heads // J
    s = s.reshape(*lead, J, N, k, W // k)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, heads, W // k, N)


# --------------------------------------------------------------------------- #
# one row
# --------------------------------------------------------------------------- #

def ssd_step_reference(x, delta, a, B, C, state, slots, fresh):
    """:func:`ssd_step` in plain jnp (the CPU path and the kernel's oracle):
    gathers every row's state, so it is for small sizes."""
    nh, G = x.shape[1], B.shape[1]
    rep = nh // G
    b, c = jnp.repeat(B, rep, axis=1), jnp.repeat(C, rep, axis=1)
    s = jnp.where(fresh[:, None, None, None], 0.0,
                  from_store(state[slots], nh))
    s = a[..., None, None] * s \
        + (delta[..., None] * x)[..., None] * b[:, :, None, :]
    y = jnp.einsum("rhpn,rhn->rhp", s, c)
    put = jnp.where(slots > 0, slots, state.shape[0])
    return (jnp.where((slots > 0)[:, None, None], y, 0.0),
            state.at[put].set(to_store(s, G), mode="drop"))


def _step_kernel(n_ref, slots_ref, fresh_ref, rows_ref, bc_ref, s_ref, y_ref,
                 s_out_ref, *, groups):
    """One row a grid step, every head of it. ``rows_ref`` [1, 2 J, W]: a
    tile's ``delta x`` (J rows), then its heads' decays along its lanes;
    ``bc_ref`` [1, N, 128]: column ``2 g`` group g's ``B``, ``2 g + 1`` its
    ``C``, down the sublanes as the state values lie."""
    del n_ref, slots_ref
    i = pl.program_id(0)
    J = s_ref.shape[1]
    per = J // groups
    keep = jnp.where(fresh_ref[i] > 0, 0.0, 1.0)
    for g in range(groups):
        b = bc_ref[0, :, 2 * g:2 * g + 1]                     # [N, 1]
        c = bc_ref[0, :, 2 * g + 1:2 * g + 2]
        for j in range(g * per, (g + 1) * per):
            s = s_ref[0, j] * (rows_ref[0, J + j:J + j + 1, :] * keep) \
                + b * rows_ref[0, j:j + 1, :]                 # [N, W]
            s_out_ref[0, j] = s
            y_ref[0, j:j + 1, :] = jnp.sum(s * c, axis=0, keepdims=True)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("groups", "interpret", "name"))
def _step_call(n, slots, fresh, rows, bc, state, *, groups, interpret, name):
    R, J2, W = rows.shape
    J, N = J2 // 2, bc.shape[1]

    def row(i, n, s, f):
        return (i, 0, 0)

    def held(i, n, s, f):
        return (s[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # a step a row that is there (they lie first): not the bucket
        grid=(n[0],),
        in_specs=[pl.BlockSpec((1, J2, W), row),
                  pl.BlockSpec((1, N, _LANES), row),
                  pl.BlockSpec((1, J, N, W), held)],
        out_specs=[pl.BlockSpec((1, J, W), row),
                   pl.BlockSpec((1, J, N, W), held)])
    compiler_params = None
    if not interpret:
        # a row's state in and out, each twice (the next row's is fetched
        # while this one is worked on)
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * 4 * J * N * W + (16 << 20))
    return pl.pallas_call(
        functools.partial(_step_kernel, groups=groups), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, J, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the store is updated in place (operands count the prefetched)
        input_output_aliases={5: 1},
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(n, slots, fresh, rows, bc, state)


def ssd_step(x: jax.Array, delta: jax.Array, a: jax.Array, B: jax.Array,
             C: jax.Array, state: jax.Array, slots: jax.Array,
             fresh: jax.Array, interpret: Optional[bool] = None, *,
             name: str = "ssd_step") -> Tuple[jax.Array, jax.Array]:
    """One row of the recurrence a grid step. x [R, nh, P], delta, a [R,
    nh] (``a`` the decay itself, in (0, 1)), B, C [R, G, N], float32; state
    [rows, *store_shape] float32, updated in place; slots [R] int32: each
    row's row of ``state`` (0: the row is skipped, its output zero; the
    rows that are not skipped lie FIRST and no two name the same); fresh
    [R] bool: the row starts from zero, whatever is stored. Returns (y [R,
    nh, P] float32, state)."""
    if interpret is None:
        interpret = _use_interpret()
    f32 = jnp.float32
    R, nh, P = x.shape
    G, N = B.shape[1:]
    J, _, W = state.shape[1:]
    assert (J, N, W) == store_shape(nh, G, P, N) and 2 * G <= _LANES, \
        (state.shape, nh, G, P, N)
    slots = slots.astype(jnp.int32)
    # a tile's vectors over its lanes (head, channel), as they lie
    dx = (delta[..., None] * x).astype(f32).reshape(R, J, W)
    ab = jnp.broadcast_to(a.astype(f32)[..., None], x.shape).reshape(R, J, W)
    bc = jnp.moveaxis(jnp.stack([B, C], axis=2).astype(f32), 3, 1).reshape(
        R, N, 2 * G)
    bc = jnp.pad(bc, ((0, 0), (0, 0), (0, _LANES - 2 * G)))
    n = jnp.sum(slots > 0, dtype=jnp.int32)[None]
    y, state = _step_call(n, slots, fresh.astype(jnp.int32),
                          jnp.concatenate([dx, ab], axis=1), bc, state,
                          groups=G, interpret=interpret, name=name)
    # a row no step took is whatever its buffer held
    return jnp.where((slots > 0)[:, None, None], y.reshape(R, nh, P), 0.0), \
        state


# --------------------------------------------------------------------------- #
# chunked
# --------------------------------------------------------------------------- #

def _exp_le0(x):
    """``exp`` of a difference of running log-decays that is <= 0 wherever
    it is used; the places it is not are masked, and must not overflow."""
    return jnp.exp(jnp.minimum(x, 0.0))


def count_pieces(runs: Sequence[Tuple[int, int]], chunk: int) -> int:
    """The grid steps :func:`ssd_chunk` runs for ``runs``, (first row,
    rows) of each run it is given: a piece a chunk of ``chunk`` rows of
    the tick that a run has a row in."""
    return sum((first + n - 1) // chunk - first // chunk + 1
               for first, n in runs)


def _einsum(spec, x, y):
    """A product of float32 operands at float32's precision."""
    return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@jax.named_scope("ssd_chunk")
def ssd_chunk_reference(x: jax.Array, delta: jax.Array, g: jax.Array,
                        B: jax.Array, C: jax.Array, runs, rows: jax.Array,
                        state: jax.Array, slot: jax.Array, chunk: int = 128
                        ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over the runs of ``rows`` [T] bool (whole runs; no
    two of one sequence), chunked, in plain XLA (the CPU path, the form
    autodiff goes through and the oracle of :func:`ssd_chunk`). x [T, nh,
    P], delta, g [T, nh] (``g`` the LOG decay, <= 0), B, C [T, G, N]
    float32; ``runs`` (``hybrid.Runs``); state [rows of state,
    *store_shape] float32; slot [T]: each row's row of ``state``. Returns (y [T, nh, P], zero outside
    ``rows``; state with the matrix after each run's last row written to
    its sequence's row).

    The tick's rows are cut into chunks of ``chunk`` on a fixed grid and a
    chunk into PIECES at the starts of runs: a piece's rows are of one run.
    With ``G_r`` the product of a head's decays from its piece's start
    through row r: ``y_r = G_r S_0 C_r + sum_{i <= r} (C_r . B_i) (G_r /
    G_i) delta_i x_i`` and ``S_end = G_end S_0 + sum_i (G_end / G_i)
    delta_i x_i B_i^T``. What needs no state (the sum within a piece: ``(L
    o C B^T) X``, two products a chunk) is computed for all chunks at once;
    a loop over the pieces then carries the state, two products a piece.
    Only ratios ``G_r / G_i <= 1`` are formed, from differences of the
    running log-decay within a chunk (``1 / G`` alone overflows for a head
    that decays fast)."""
    f32 = jnp.float32
    T0, nh, P = x.shape
    G, N = B.shape[1:]
    rep = nh // G
    L = chunk if T0 >= chunk else -(-T0 // 8) * 8
    T = -(-T0 // L) * L
    nC = T // L

    def padded(a, fill=0):
        return jnp.pad(a, [(0, T - T0)] + [(0, 0)] * (a.ndim - 1),
                       constant_values=fill)

    rows = padded(rows, False)
    g = jnp.where(rows[:, None], padded(g.astype(f32)), 0.0)
    dx = jnp.where(rows[:, None, None], padded(
        delta.astype(f32)[..., None] * x.astype(f32)), 0.0)
    B, C = (jnp.where(rows[:, None, None], padded(a.astype(f32)), 0.0)
            for a in (B, C))
    start, last = padded(runs.start, True), padded(runs.last, True)
    fresh, slot = padded(runs.fresh, True), padded(slot)
    t = jnp.arange(T, dtype=jnp.int32)

    # pieces: a row opens one where its run starts or a chunk does
    opens = rows & (start | (t % L == 0))
    piece = jnp.where(rows, jnp.cumsum(opens), -1)              # [T]
    first = lax.cummax(jnp.where(opens, t, 0))                  # its 1st row
    closes = rows & jnp.concatenate([piece[1:] != piece[:-1],
                                     jnp.ones((1,), bool)])
    end = lax.cummin(jnp.where(closes, t, T - 1), reverse=True)  # its last

    def chunks(a):                      # [T, ...] -> [nC, L, ...]
        return a.reshape((nC, L) + a.shape[1:])

    def prepare():
        """(the sums within the pieces [nC, L, nh, P], the running
        log-decay from each row's piece's start through the row [T, nh])."""
        cs = jnp.cumsum(chunks(g), axis=1)                      # [nC, L, nh]
        flat = cs.reshape(T, nh)
        gam = flat - (flat[first] - g[first])
        r_i = jnp.arange(L)
        seen = (chunks(piece)[:, :, None] == chunks(piece)[:, None, :]) \
            & chunks(rows)[:, :, None] & (r_i[:, None] >= r_i[None, :])
        cb = _einsum("crgn,cign->cgri", chunks(C), chunks(B))   # [nC, G, L, L]
        by_head = jnp.moveaxis(cs, 2, 1)                        # [nC, nh, L]
        ratio = _exp_le0(by_head[..., :, None] - by_head[..., None, :])
        # a group's products meet each of its heads' ratios: [c, h, r, i]
        w = jnp.where(seen[:, None], (
            cb[:, :, None] * ratio.reshape(nC, G, rep, L, L)).reshape(
                nC, nh, L, L), 0.0)
        return _einsum("chri,cihp->crhp", w, chunks(dx)), gam

    n_pieces = jnp.sum(opens)
    shapes = jax.eval_shape(prepare)
    # a tick without such runs (most decode ticks) computes none of it
    y, gam = lax.cond(
        n_pieces > 0, prepare,
        lambda: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes))
    starts = jnp.nonzero(opens, size=T, fill_value=T)[0]

    def body(carry):
        i, s, y, state = carry
        t0 = starts[i]
        c = t0 // L
        at = c * L
        mine = lax.dynamic_slice(piece, (at,), (L,)) == piece[t0]   # [L]
        stored = lax.dynamic_slice(
            state, (slot[t0], 0, 0, 0), (1,) + state.shape[1:])
        s0 = jnp.where(start[t0], jnp.where(
            fresh[t0], 0.0, from_store(stored[0], nh)), s)
        gam_c = lax.dynamic_slice(gam, (at, 0), (L, nh))
        B_c = lax.dynamic_slice(B, (at, 0, 0), (L, G, N))
        C_c = lax.dynamic_slice(C, (at, 0, 0), (L, G, N))
        dx_c = lax.dynamic_slice(dx, (at, 0, 0), (L, nh, P))
        through = _einsum("lgn,gjpn->lgjp", C_c, s0.reshape(G, rep, P, N)
                       ).reshape(L, nh, P) * jnp.exp(gam_c)[..., None]
        y_c = lax.dynamic_slice(y, (c, 0, 0, 0), (1, L, nh, P))[0]
        y = lax.dynamic_update_slice(
            y, jnp.where(mine[:, None, None], y_c + through, y_c)[None],
            (c, 0, 0, 0))
        g_end = gam[end[t0]]                                    # [nh]
        left = jnp.where(mine[:, None], _exp_le0(g_end[None] - gam_c), 0.0)
        s = jnp.exp(g_end)[:, None, None] * s0 + _einsum(
            "lgjp,lgn->gjpn", (dx_c * left[..., None]).reshape(
                L, G, rep, P), B_c).reshape(nh, P, N)
        # the run ends in this piece: its state is its sequence's
        state = lax.dynamic_update_slice(
            state, jnp.where(last[end[t0]], to_store(s, G)[None], stored),
            (slot[t0], 0, 0, 0))
        return i + 1, s, y, state

    _, _, y, state = lax.while_loop(
        lambda carry: carry[0] < n_pieces, body,
        (jnp.int32(0), jnp.zeros((nh, P, N), f32), y, state))
    y = jnp.where(rows[:, None, None], y.reshape(T, nh, P), 0.0)
    return y[:T0], state


def _sub_block(L: int) -> int:
    """Rows of the sub-blocks a chunk's triangle is walked in: the blocks
    above the diagonal are not computed."""
    return 128 if L % 128 == 0 else L


def _chunk_kernel(n_ref, chunk_ref, lo_ref, hi_ref, slot_ref, flag_ref,
                  x_ref, d_ref, g_ref, b_ref, c_ref, s_hbm, y_ref, s_out,
                  s_buf, cs_ref, left_ref, cs_t, d_t, cb_ref, b_t, sem, *,
                  groups):
    """One piece a grid step, every head of it: see :func:`ssd_chunk`. The
    rows of the piece's chunk as the model has them, a head's channels (a
    group's state values) side by side along the lanes: ``x_ref``, ``y_ref``
    [L, nh P] (the store's tile j at lanes ``j W``), ``b_ref``, ``c_ref``
    [L, G N]; ``s_buf`` [J, N, W] carries the run's matrices as the store
    has them. Scratch a piece: the running log-decay ``cs_ref`` and ``delta
    exp(g_end - cs)`` ``left_ref`` [L, nh], the running log-decay and
    ``delta`` with the rows along the lanes ``cs_t``, ``d_t`` [nh, L];
    a group: ``cb_ref`` [L, L] (``C B^T``) and ``b_t`` [N, L] (``B^T``, the
    piece's rows alone)."""
    del n_ref, chunk_ref
    f32 = jnp.float32
    p = pl.program_id(0)
    J, N, W = s_buf.shape
    L, nh = g_ref.shape
    k = nh // J                      # heads side by side in a tile
    P, per = W // k, J // groups
    SB = _sub_block(L)
    lo, hi, flag = lo_ref[p], hi_ref[p], flag_ref[p]
    opens, fresh, closes = (flag & 1) != 0, (flag & 2) != 0, (flag & 4) != 0

    @pl.when(opens & ~fresh)
    def _():
        copy = pltpu.make_async_copy(s_hbm.at[slot_ref[p]], s_buf, sem.at[0])
        copy.start()
        copy.wait()

    @pl.when(opens & fresh)
    def _():
        s_buf[...] = jnp.zeros_like(s_buf)

    r = lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    mine = (r >= lo) & (r <= hi)                           # [L, 1]
    ri = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    ci = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # running log-decay within the piece, a row's own included (g is
    # masked: the rows past the piece keep its whole, those before it 0)
    cs = _dot(jnp.where(ri >= ci, 1.0, 0.0).astype(f32),
              jnp.where(mine, g_ref[...], 0.0))            # [L, nh]
    cs_ref[...] = cs
    # the same numbers with the rows along the lanes (a transpose, not a
    # second sum: a row's difference from itself is exactly 0)
    cs_t[...] = cs.T
    d_t[...] = d_ref[...].T
    # what a row's delta x is worth at the piece's end: exp(g_end - cs)
    left_ref[...] = d_ref[...] * _exp_le0(cs[L - 1:L] - cs)
    # a diagonal sub-block's i <= r
    low = lax.broadcasted_iota(jnp.int32, (SB, SB), 1) \
        <= lax.broadcasted_iota(jnp.int32, (SB, SB), 0)
    head_lane = lax.broadcasted_iota(jnp.int32, (SB, nh), 1)
    tile_head = lax.broadcasted_iota(jnp.int32, (1, W), 1) // P

    def column(ref, at, h):
        """Head h's column of ``ref[at]`` [SB, nh], [SB, 1]."""
        return jnp.sum(jnp.where(head_lane == h, ref[at, :], 0.0), axis=1,
                       keepdims=True)

    def group_lanes(gi):
        return pl.ds(pl.multiple_of(gi * N, N), N)

    def tile(j, carry):
        lanes_j = pl.ds(pl.multiple_of(j * W, W), W)
        x = x_ref[:, lanes_j]                              # [L, W]
        s0 = s_buf[j]                                      # [N, W]
        through = _dot(c_ref[:, group_lanes(j // per)], s0)
        y = a = left = jnp.zeros((L, W), f32)
        kept = jnp.zeros((1, W), f32)
        for hh in range(k):
            h = j * k + hh
            here = tile_head == hh                         # [1, W]
            row = cs_t[pl.ds(h, 1), :]                     # [1, L]
            d_row = d_t[pl.ds(h, 1), :]
            y_h, a_h, left_h = [], [], []
            for s in range(L // SB):
                at, K = slice(s * SB, (s + 1) * SB), (s + 1) * SB
                col = column(cs_ref, at, h)
                # (C B^T) o exp(cs_r - cs_i) delta_i, i <= r: the blocks
                # left of the diagonal whole, the diagonal's under its mask
                w = cb_ref[at, :K] * _exp_le0(col - row[:, :K]) \
                    * d_row[:, :K]
                if s:
                    w = jnp.concatenate([w[:, :s * SB], jnp.where(
                        low, w[:, s * SB:], 0.0)], axis=1)
                else:
                    w = jnp.where(low, w, 0.0)
                y_h.append(_dot(w, x[:K]))                 # [SB, W]
                a_h.append(jnp.exp(col))
                left_h.append(column(left_ref, at, h))
            y, a, left = (jnp.where(here, jnp.concatenate(x_h, axis=0), x_w)
                          for x_h, x_w in ((y_h, y), (a_h, a),
                                           (left_h, left)))
            kept = jnp.where(here, jnp.exp(row[:, L - 1:L]), kept)
        y_ref[:, lanes_j] = jnp.where(mine, y + a * through,
                                      y_ref[:, lanes_j])
        # S_end = exp(g_end) S_0 + sum_i B_i (exp(g_end - cs_i) dx_i)^T
        s_buf[j] = kept * s0 + _dot(b_t[...], x * left)
        return carry

    def group(gi, carry):
        # B's rows outside the piece are zero: no column of the weights
        # and no row of the state's update is another run's
        b = jnp.where(mine, b_ref[:, group_lanes(gi)], 0.0)     # [L, N]
        cb_ref[...] = _dot(c_ref[:, group_lanes(gi)], b, ((1,), (1,)))
        b_t[...] = b.T
        return lax.fori_loop(gi * per, (gi + 1) * per, tile, carry)

    lax.fori_loop(0, groups, group, 0)

    @pl.when(closes)
    def _():
        copy = pltpu.make_async_copy(s_buf, s_out.at[slot_ref[p]], sem.at[0])
        copy.start()
        copy.wait()


@functools.partial(jax.jit, inline=True,
                   static_argnames=("chunk", "interpret", "name"))
def _chunk_call(pieces, x, delta, g, B, C, state, *, chunk, interpret, name):
    L, nh = chunk, g.shape[1]
    J, N, W = state.shape[1:]
    G = B.shape[1] // N

    tiles, by_group, heads = (pl.BlockSpec(
        (L, w), lambda p, n, c, *_: (c[p], 0)) for w in (J * W, G * N, nh))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pieces),
        # a step a piece: as many as the tick's runs make, none for a tick
        # without (no bound on them is static: every row may be a run)
        grid=(pieces[0][0],),
        in_specs=[tiles, heads, heads, by_group, by_group,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[tiles, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((J, N, W), jnp.float32),
                        pltpu.VMEM((L, nh), jnp.float32),
                        pltpu.VMEM((L, nh), jnp.float32),
                        pltpu.VMEM((nh, L), jnp.float32),
                        pltpu.VMEM((nh, L), jnp.float32),
                        pltpu.VMEM((L, L), jnp.float32),
                        pltpu.VMEM((N, L), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))])
    compiler_params = None
    if not interpret:
        # a chunk's rows of x and y, each twice, and the run's matrices
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(4 * L + N) * J * W * 4 + (24 << 20))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, groups=G), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the store is updated in place (operands count the prefetched)
        input_output_aliases={len(pieces) + 5: 1},
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(*pieces, x, delta, g, B, C, state)


@jax.named_scope("ssd_chunk")
def ssd_chunk(x: jax.Array, delta: jax.Array, g: jax.Array, B: jax.Array,
              C: jax.Array, runs, rows: jax.Array, state: jax.Array,
              slot: jax.Array, chunk: int = 128,
              interpret: Optional[bool] = None, *, name: str = "ssd_chunk"
              ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over the runs of ``rows`` [T] bool (whole runs; no
    two of one sequence), chunked, a Mosaic kernel. Operands and results as
    :func:`ssd_chunk_reference`, whose doc-string is the mathematics; what
    differs is what is computed when. The grid is the tick's PIECES and
    nothing else: a step loads its chunk's rows (the block stays where the
    piece before had the same chunk), reads the run's matrices from the
    store where the piece opens its run (zero where the run starts at
    position 0), forms the heads' running log-decay from the piece's first
    row (a product with a triangle of ones) and, a group, ``C B^T``; then a
    tile of the store after the other (its ``k`` heads' channels side by
    side along the lanes, as the store has them) the weights ``(C B^T) o
    exp(cs_r - cs_i) delta_i`` a head in sub-blocks of 128 rows, none above
    the diagonal, ``y = W x + exp(cs) (C S_0)`` and ``S = exp(g_end) S_0 +
    B^T (exp(g_end - cs) delta x)``, carried in VMEM to the run's next
    piece; the piece that closes its run writes the matrices to the store.
    A tick without such runs is a call of no grid step: the store comes back
    as it went in. Nothing of ``[T, nh, P]`` or ``[chunks, nh, L, L]`` is
    built around the call but the result's mask."""
    if interpret is None:
        interpret = _use_interpret()
    f32 = jnp.float32
    T0, nh, P = x.shape
    G, N = B.shape[1:]
    J, _, W = state.shape[1:]
    assert (J, N, W) == store_shape(nh, G, P, N), (state.shape, nh, G, P, N)
    L = chunk if T0 >= chunk else -(-T0 // 8) * 8
    T = -(-T0 // L) * L

    def padded(a, fill=0):
        return jnp.pad(a, [(0, T - T0)] + [(0, 0)] * (a.ndim - 1),
                       constant_values=fill)

    rows = padded(rows, False)
    pieces = _pieces(rows, padded(runs.start, True), padded(runs.last, True),
                     padded(runs.fresh, True), padded(slot), L)
    # the rows as the model makes them, heads and groups along the lanes
    x, delta, g, B, C = (padded(a.astype(f32)).reshape(T, -1)
                         for a in (x, delta, g, B, C))

    # one call whatever the tick holds, the store aliased through it: a
    # tick without such runs (most decode ticks) has a grid of no step,
    # ~1 us a layer. ``kda.kda_chunk``'s loop of at most one trip would
    # need an array that is dead after the call to carry the result's
    # place, and ``x`` is not (the mixer's skip reads it): the loop copies
    # it, 0.4 ms a layer of a 2,048-row tick (PERF.md, PR 61)
    y, state = _chunk_call(pieces, x, delta, g, B, C, state, chunk=L,
                           interpret=interpret, name=name)
    # a row no piece holds is whatever its buffer held
    return jnp.where(rows[:, None], y, 0.0)[:T0].reshape(T0, nh, P), state
