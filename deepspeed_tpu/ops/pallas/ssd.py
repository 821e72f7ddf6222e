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
  carried state), segmented at the starts of runs: a run's state is read at
  its first piece and written after its last, and never exists a row at a
  time. Plain XLA under the named scope ``ssd_chunk`` (no Mosaic kernel
  yet: ROADMAP R7).

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
    """The trips of :func:`ssd_chunk`'s loop for ``runs``, (first row,
    rows) of each run it is given: a piece a chunk of ``chunk`` rows of
    the tick that a run has a row in."""
    return sum((first + n - 1) // chunk - first // chunk + 1
               for first, n in runs)


def _as_stored(x):
    """A sequence's row of the store, read or about to be written, pinned
    to the store's own order of dimensions. The loop below meets the store
    only through such rows; left to itself the compiler may instead give
    the WHOLE store the order its products like inside the loop and re-lay
    it around every ``ssd_step`` call, whose operand's order is fixed: 1.4
    GB copied twice a layer in a stack of paired blocks (PERF.md, PR 60)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def _dot(spec, x, y):
    """A product of float32 operands at float32's precision."""
    return jnp.einsum(spec, x, y, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@jax.named_scope("ssd_chunk")
def ssd_chunk(x: jax.Array, delta: jax.Array, g: jax.Array, B: jax.Array,
              C: jax.Array, runs, rows: jax.Array, state: jax.Array,
              slot: jax.Array, chunk: int = 128
              ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over the runs of ``rows`` [T] bool (whole runs; no
    two of one sequence), chunked, in plain XLA. x [T, nh, P], delta, g
    [T, nh] (``g`` the LOG decay, <= 0), B, C [T, G, N] float32; ``runs``
    (``hybrid.Runs``); state [rows of state, *store_shape] float32; slot [T]:
    each row's row of ``state``. Returns (y [T, nh, P], zero outside
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
        cb = _dot("crgn,cign->cgri", chunks(C), chunks(B))      # [nC, G, L, L]
        by_head = jnp.moveaxis(cs, 2, 1)                        # [nC, nh, L]
        ratio = _exp_le0(by_head[..., :, None] - by_head[..., None, :])
        # a group's products meet each of its heads' ratios: [c, h, r, i]
        w = jnp.where(seen[:, None], (
            cb[:, :, None] * ratio.reshape(nC, G, rep, L, L)).reshape(
                nC, nh, L, L), 0.0)
        return _dot("chri,cihp->crhp", w, chunks(dx)), gam

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
        stored = _as_stored(lax.dynamic_slice(
            state, (slot[t0], 0, 0, 0), (1,) + state.shape[1:]))
        s0 = jnp.where(start[t0], jnp.where(
            fresh[t0], 0.0, from_store(stored[0], nh)), s)
        gam_c = lax.dynamic_slice(gam, (at, 0), (L, nh))
        B_c = lax.dynamic_slice(B, (at, 0, 0), (L, G, N))
        C_c = lax.dynamic_slice(C, (at, 0, 0), (L, G, N))
        dx_c = lax.dynamic_slice(dx, (at, 0, 0), (L, nh, P))
        through = _dot("lgn,gjpn->lgjp", C_c, s0.reshape(G, rep, P, N)
                       ).reshape(L, nh, P) * jnp.exp(gam_c)[..., None]
        y_c = lax.dynamic_slice(y, (c, 0, 0, 0), (1, L, nh, P))[0]
        y = lax.dynamic_update_slice(
            y, jnp.where(mine[:, None, None], y_c + through, y_c)[None],
            (c, 0, 0, 0))
        g_end = gam[end[t0]]                                    # [nh]
        left = jnp.where(mine[:, None], _exp_le0(g_end[None] - gam_c), 0.0)
        s = jnp.exp(g_end)[:, None, None] * s0 + _dot(
            "lgjp,lgn->gjpn", (dx_c * left[..., None]).reshape(
                L, G, rep, P), B_c).reshape(nh, P, N)
        # the run ends in this piece: its state is its sequence's
        state = lax.dynamic_update_slice(
            state, _as_stored(jnp.where(
                last[end[t0]], to_store(s, G)[None], stored)),
            (slot[t0], 0, 0, 0))
        return i + 1, s, y, state

    _, _, y, state = lax.while_loop(
        lambda carry: carry[0] < n_pieces, body,
        (jnp.int32(0), jnp.zeros((nh, P, N), f32), y, state))
    y = jnp.where(rows[:, None, None], y.reshape(T, nh, P), 0.0)
    return y[:T0], state
