"""The gated delta rule of Kimi Delta Attention (``models/hybrid.py``) in
the two forms a serving tick needs, over a store of states ``[rows, N, D,
D]`` float32 (a matrix ``[keys, values]`` a head a sequence, 2 MB at 32
heads of 128) that is updated in place:

* :func:`kda_step`: rows that are runs of one (decode rows), a Mosaic
  kernel: a grid step reads a row's matrix, decays it a key channel,
  corrects it by the row's key and value, writes it back and reads it out
  by the row's query. One read and one write of the matrix a row a layer,
  which is all a decode tick's linear-attention layers cost.
* :func:`kda_chunk`: every other run, chunkwise (the paper's form: a
  triangular solve a chunk and matrix products), in plain XLA under the
  named scope ``kda_chunk``: a run's state is carried from chunk to chunk
  and never exists a row at a time.

Both compute, a head, ``S' = diag(a) S; S = S' + b k (v - S'^T k)^T; o =
S^T q`` (``hybrid.kda_recurrence`` is the arbiter).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a chunk and of the sub-blocks its decays are referred to
CHUNK, SUB = 64, 16


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# one row
# --------------------------------------------------------------------------- #

def kda_step_reference(q, k, v, a, b, state, slots, fresh):
    """:func:`kda_step` in plain jnp (the CPU path and the kernel's oracle):
    gathers every row's matrix, so it is for small sizes."""
    s = jnp.where(fresh[:, None, None, None], 0.0, state[slots])
    s = a[..., None] * s
    u = b[..., None] * (v - jnp.einsum("rnk,rnkv->rnv", k, s))
    s = s + k[..., None] * u[:, :, None, :]
    o = jnp.einsum("rnk,rnkv->rnv", q, s)
    put = jnp.where(slots > 0, slots, state.shape[0])
    return (jnp.where((slots > 0)[:, None, None], o, 0.0),
            state.at[put].set(s, mode="drop"))


def _step_kernel(slots_ref, fresh_ref, x_ref, v_ref, s_ref, o_ref, s_out_ref):
    i = pl.program_id(0)

    @pl.when(slots_ref[i] > 0)
    def _():
        x = x_ref[0]                     # [4 N, D]: q | k | b k | a, by head
        N = x.shape[0] // 4
        # a vector over the keys has to lie along the matrix's rows
        # (sublanes): one transpose of the four vectors of every head
        xt = x.T                         # [D, 4 N]
        keep = jnp.where(fresh_ref[i] > 0, 0.0, 1.0)
        for h in range(N):
            def col(w, h=h):
                return xt[:, w * N + h:w * N + h + 1]     # [D, 1]
            s = s_ref[0, h] * keep * col(3)               # [D keys, D values]
            u = v_ref[0, h:h + 1, :] - jnp.sum(s * col(1), axis=0,
                                               keepdims=True)
            s = s + col(2) * u
            s_out_ref[0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(s * col(0), axis=0, keepdims=True)

    @pl.when(slots_ref[i] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("interpret", "name"))
def _step_call(slots, fresh, x, v, state, *, interpret, name):
    R, N, D = v.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(R,),
        in_specs=[pl.BlockSpec((1, 4 * N, D), lambda i, s, f: (i, 0, 0)),
                  pl.BlockSpec((1, N, D), lambda i, s, f: (i, 0, 0)),
                  pl.BlockSpec((1, N, D, D),
                               lambda i, s, f: (s[i], 0, 0, 0))],
        out_specs=[pl.BlockSpec((1, N, D), lambda i, s, f: (i, 0, 0)),
                   pl.BlockSpec((1, N, D, D),
                                lambda i, s, f: (s[i], 0, 0, 0))])
    compiler_params = None
    if not interpret:
        # a row's matrix in and out, each twice (the next row's is fetched
        # while this one is worked on)
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * 4 * N * D * D + (16 << 20))
    return pl.pallas_call(
        _step_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, N, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the store is updated in place (operands count the two prefetched)
        input_output_aliases={4: 1},
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(slots, fresh, x, v, state)


def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, a: jax.Array,
             b: jax.Array, state: jax.Array, slots: jax.Array,
             fresh: jax.Array, interpret: Optional[bool] = None, *,
             name: str = "kda_step") -> Tuple[jax.Array, jax.Array]:
    """One row of the rule a grid step. q, k, v, a [R, N, D] float32 (``a``
    the decay itself, in (0, 1)); b [R, N]; state [rows, N, D, D] float32,
    updated in place; slots [R] int32: each row's row of ``state`` (0: the
    row is skipped, its output zero; no two rows name the same);
    fresh [R] bool: the row starts from zero, whatever is stored. Returns
    (o [R, N, D] float32, state)."""
    if interpret is None:
        interpret = _use_interpret()
    # the four vectors over the keys of every head as ONE [4 N, D] tile a
    # row, which the kernel transposes whole
    x = jnp.concatenate([q, k, b[..., None] * k, a], axis=1)
    o, state = _step_call(slots.astype(jnp.int32), fresh.astype(jnp.int32),
                          x.astype(jnp.float32), v.astype(jnp.float32),
                          state, interpret=interpret, name=name)
    return o, state


# --------------------------------------------------------------------------- #
# chunkwise
# --------------------------------------------------------------------------- #

def _exp_le0(x):
    """``exp`` of a difference of running log-decays that is <= 0 wherever
    it is used; the places it is not are masked, and must not overflow."""
    return jnp.exp(jnp.minimum(x, 0.0))


@jax.named_scope("kda_chunk")
def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              b: jax.Array, runs, rows: jax.Array, state: jax.Array,
              slot: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The rule over the runs of ``rows`` [T] bool (whole runs), chunkwise.
    q, k, v, g [T, N, D] float32 (``g`` the LOG decay, <= 0); b [T, N];
    ``runs`` (``hybrid.Runs``); state [rows of state, N, D, D] float32;
    slot [T]: each row's row of ``state``. Returns (o [T, N, D], zero
    outside ``rows``; state with the matrix after each run's last row
    written to its sequence's row).

    The tick's rows are cut into chunks of ``CHUNK`` on a fixed grid and a
    chunk into PIECES at the starts of runs: a piece's rows are of one run.
    With ``G_r`` the product of the decays from the piece's start through
    row r, ``U`` solves ``(I + A) U = diag(b) (V - K+ S_0)``, ``A[r, i] =
    b_r sum_d k_r k_i G_r / G_i`` (i < r), ``K+ = G k``; ``o = Q+ S_0 + P
    U``, ``P[r, i] = sum_d q_r k_i G_r / G_i`` (i <= r); ``S_end =
    diag(G_end) S_0 + sum_i (k_i G_end / G_i) u_i^T``. Everything that does
    not need ``S_0`` (``A``, ``P``, the solve of ``diag(b) [V | K+]``) is
    computed for all chunks at once; a loop over the pieces then carries
    the state, four products a piece. Only ratios ``G_r / G_i <= 1`` are
    formed, from differences of the running log-decay: within a sub-block
    of ``SUB`` rows directly, across sub-blocks through the log-decay at the
    later sub-block's first row (``1 / G`` alone overflows for a channel
    that decays fast)."""
    f32 = jnp.float32
    T0, N, D = q.shape
    C = CHUNK if T0 >= CHUNK else -(-T0 // SUB) * SUB
    T = -(-T0 // C) * C
    nC, nS = T // C, C // SUB

    def padded(x, fill=0):
        return jnp.pad(x, [(0, T - T0)] + [(0, 0)] * (x.ndim - 1),
                       constant_values=fill)

    rows = padded(rows, False)
    m = rows[:, None, None]
    q, k, v, g = (jnp.where(m, padded(x.astype(f32)), 0.0)
                  for x in (q, k, v, g))
    b = jnp.where(rows[:, None], padded(b.astype(f32)), 0.0)
    start, last = padded(runs.start, True), padded(runs.last, True)
    fresh, slot = padded(runs.fresh, True), padded(slot)
    t = jnp.arange(T, dtype=jnp.int32)

    # pieces: a row opens one where its run starts or a chunk does
    opens = rows & (start | (t % C == 0))
    piece = jnp.where(rows, jnp.cumsum(opens), -1)              # [T]
    first = lax.cummax(jnp.where(opens, t, 0))                  # its 1st row
    closes = rows & jnp.concatenate([piece[1:] != piece[:-1],
                                     jnp.ones((1,), bool)])
    end = lax.cummin(jnp.where(closes, t, T - 1), reverse=True)  # its last

    def chunks(x):                      # [T, ...] -> [nC, C, ...]
        return x.reshape((nC, C) + x.shape[1:])

    def blocks(x):                      # [T, ...] -> [T/SUB, SUB, ...]
        return x.reshape((T // SUB, SUB) + x.shape[1:])

    def prepare():
        """What the pieces' loop needs that no state enters: (P, u0, w,
        Q+, the keys as each piece's end sees them), [nC, N, C, .], and the
        running log-decay [T, N, D]."""
        # running log-decay within the piece, its first row's included
        cs = jnp.cumsum(g, axis=0)
        gam = cs - (cs[first] - g[first])                       # [T, N, D]
        # the log-decay just before each sub-block's first row, for its rows
        ref = jnp.repeat((gam - g)[::SUB], SUB, axis=0)         # [T, N, D]
        same = chunks(piece)[:, :, None] == chunks(piece)[:, None, :]
        same &= chunks(rows)[:, :, None]                        # [nC, C, C]
        r_i = jnp.arange(C)
        sub_r, sub_i = r_i[:, None] // SUB, r_i[None, :] // SUB
        kq = jnp.stack([k, q])                                  # [2, T, N, D]
        # across sub-blocks: row r through the reference of its sub-block,
        # column i against the reference of EACH sub-block of its chunk
        to_ref = (kq * _exp_le0(gam - ref)).reshape(2, nC, nS, SUB, N, D)
        refs = chunks(ref)[:, ::SUB]                            # [nC, nS, N, D]
        k_from = chunks(k)[:, None] * _exp_le0(
            refs[:, :, None] - chunks(gam)[:, None])         # [nC, nS, C, N, D]
        across = jnp.einsum("xcsrnd,csind->xcnsri", to_ref, k_from,
                            preferred_element_type=f32
                            ).reshape(2, nC, N, C, C)
        # within a sub-block: the ratio of every pair, a channel
        ratio = _exp_le0(blocks(gam)[:, :, None] - blocks(gam)[:, None])
        within = jnp.sum(
            blocks(jnp.moveaxis(kq, 0, 1))[:, :, None]    # [B, r, 1, 2, N, D]
            * (blocks(k)[:, None] * ratio)[:, :, :, None],
            axis=-1)                                      # [B, r, i, 2, N]
        within = jnp.transpose(within, (3, 0, 4, 1, 2)).reshape(
            2, nC, nS, N, SUB, SUB)
        within = jnp.einsum("xcsnri,sz->xcnsrzi", within,
                            jnp.eye(nS, dtype=f32)).reshape(2, nC, N, C, C)
        both = within + jnp.where(sub_r > sub_i, across, 0.0)
        keep = same[:, None]
        A = jnp.where(keep & (r_i[:, None] > r_i[None, :]), both[0], 0.0) \
            * jnp.moveaxis(chunks(b), -1, 1)[..., None]
        P = jnp.where(keep & (r_i[:, None] >= r_i[None, :]), both[1], 0.0)
        G = jnp.exp(gam)                                        # <= 1
        rhs = jnp.concatenate([v, k * G], axis=-1) * b[..., None]
        solved = jax.scipy.linalg.solve_triangular(
            A + jnp.eye(C, dtype=f32), jnp.moveaxis(chunks(rhs), 2, 1),
            lower=True, unit_diagonal=True)                 # [nC, N, C, 2 D]
        return (P, solved[..., :D], solved[..., D:],
                jnp.moveaxis(chunks(q * G), 2, 1),
                jnp.moveaxis(chunks(k * _exp_le0(gam[end] - gam)), 2, 1),
                gam)

    n_pieces = jnp.sum(opens)
    shapes = jax.eval_shape(prepare)
    # a tick without such runs (most decode ticks) computes none of it
    P, u0, w, q_in, k_out, gam = lax.cond(
        n_pieces > 0, prepare,
        lambda: jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    starts = jnp.nonzero(opens, size=T, fill_value=T)[0]

    def body(carry):
        i, s, o, state = carry
        t0 = starts[i]
        c = t0 // C
        mine = (lax.dynamic_slice(piece, (c * C,), (C,))
                == piece[t0])[None, :, None]
        stored = lax.dynamic_slice(
            state, (slot[t0], 0, 0, 0), (1, N, D, D))[0]
        s0 = jnp.where(start[t0], jnp.where(fresh[t0], 0.0, stored), s)
        u = jnp.where(mine, u0[c] - jnp.einsum(
            "nrk,nkv->nrv", w[c], s0, preferred_element_type=f32), 0.0)
        out = jnp.einsum("nrk,nkv->nrv", q_in[c], s0,
                         preferred_element_type=f32) \
            + jnp.einsum("nri,niv->nrv", P[c], u,
                         preferred_element_type=f32)
        s = jnp.exp(gam[end[t0]])[..., None] * s0 + jnp.einsum(
            "nrk,nrv->nkv", jnp.where(mine, k_out[c], 0.0), u,
            preferred_element_type=f32)
        o = lax.dynamic_update_slice(
            o, jnp.where(mine, out, lax.dynamic_slice(
                o, (c, 0, 0, 0), (1, N, C, D))[0])[None], (c, 0, 0, 0))
        # the run ends in this piece: its state is its sequence's
        state = lax.dynamic_update_slice(
            state, jnp.where(last[end[t0]], s, stored)[None],
            (slot[t0], 0, 0, 0))
        return i + 1, s, o, state

    _, _, o, state = lax.while_loop(
        lambda carry: carry[0] < n_pieces, body,
        (jnp.int32(0), jnp.zeros((N, D, D), f32),
         jnp.zeros((nC, N, C, D), f32), state))
    return jnp.moveaxis(o, 1, 2).reshape(T, N, D)[:T0], state
