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
  triangular solve a chunk and matrix products), a Mosaic kernel under the
  named scope ``kda_chunk`` whose grid is the PIECES of chunks that hold a
  row of a run, and no more: a run's matrix is read at its first piece,
  carried in VMEM from piece to piece and written after its last, and never
  exists a row at a time. :func:`kda_chunk_reference` is the same in plain
  XLA (all of the bucket's chunks at once, then a loop over the pieces):
  the CPU path and the kernel's oracle.

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
def kda_chunk_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        g: jax.Array, b: jax.Array, runs, rows: jax.Array,
                        state: jax.Array, slot: jax.Array
                        ) -> Tuple[jax.Array, jax.Array]:
    """The rule over the runs of ``rows`` [T] bool (whole runs), chunkwise,
    in plain XLA (the CPU path and the oracle of :func:`kda_chunk`).
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


def _pieces(rows, start, last, fresh, slot, C):
    """The pieces of the runs of ``rows`` [T] (T a multiple of ``C``; the
    others [T] as ``hybrid.Runs`` has them), in row order, as the kernel's
    scalars: how many there are [1], then a piece each [T] (most are
    unused): its chunk, its first and last row in the chunk, its
    sequence's row of state, and bits 1 | 2 | 4: it opens its run, that run
    starts from zero, it closes its run."""
    T = rows.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    # a row opens a piece where its run starts or a chunk does
    opens = rows & (start | (t % C == 0))
    piece = jnp.where(rows, jnp.cumsum(opens), -1)
    closes = rows & jnp.concatenate([piece[1:] != piece[:-1],
                                     jnp.ones((1,), bool)])
    end = lax.cummin(jnp.where(closes, t, T - 1), reverse=True)  # its last
    t0 = jnp.nonzero(opens, size=T, fill_value=0)[0].astype(jnp.int32)
    flag = sum(x.astype(jnp.int32) * bit for x, bit in (
        (start[t0], 1), (fresh[t0], 2), (last[end[t0]], 4)))
    return (jnp.sum(opens, dtype=jnp.int32)[None], t0 // C, t0 % C,
            end[t0] % C, slot[t0].astype(jnp.int32), flag)


def count_pieces(runs) -> int:
    """The grid steps :func:`kda_chunk` runs for ``runs``, (first row, rows)
    of each run it is given, by the kernel's own rule: a piece a chunk of
    ``CHUNK`` rows of the tick that a run has a row in."""
    return sum((first + n - 1) // CHUNK - first // CHUNK + 1
               for first, n in runs)


def _dot(x, y, dims=((1,), (0,))):
    """A product of float32 operands at float32's precision."""
    return lax.dot_general(x, y, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _chunk_kernel(n_ref, chunk_ref, lo_ref, hi_ref, slot_ref, flag_ref,
                  q_ref, k_ref, v_ref, g_ref, b_ref, s_hbm, o_ref, s_out,
                  s_buf, sem):
    """One piece a grid step, every head of it: see :func:`kda_chunk`. The
    rows of the piece's chunk lie head-minor ([C N, D], row r of head h at
    ``r N + h``); ``s_buf`` [N, D, D] carries the run's matrices."""
    del n_ref, chunk_ref
    f32 = jnp.float32
    p = pl.program_id(0)
    N, D, _ = s_buf.shape
    C, nS = CHUNK, CHUNK // SUB
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

    r = lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    mine = (r >= lo) & (r <= hi)                           # [C, 1]
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    low = (ci >= lo) & (ci <= ri) & (ri <= hi)             # i <= r, both mine
    strict = low & (ci < ri)
    eye = jnp.where(ri == ci, 1.0, 0.0).astype(f32)
    ones_low = jnp.where(ri >= ci, 1.0, 0.0).astype(f32)
    in_sub = ri // SUB == ci // SUB
    in_half = ri // (2 * SUB) == ci // (2 * SUB)
    col = lax.broadcasted_iota(jnp.int32, (SUB // 2, C), 1)
    head_lane = lax.broadcasted_iota(jnp.int32, (C, N), 1)
    diag = (lax.broadcasted_iota(jnp.int32, (D, D), 0)
            == lax.broadcasted_iota(jnp.int32, (D, D), 1))

    def head(h, carry):
        at = pl.ds(h, C, stride=N)
        q, k, v, g = (jnp.where(mine, x[at, :], 0.0)
                      for x in (q_ref, k_ref, v_ref, g_ref))
        b = jnp.where(mine, jnp.sum(
            jnp.where(head_lane == h, b_ref[...], 0.0), axis=1,
            keepdims=True), 0.0)                           # [C, 1]
        # running log-decay within the piece, a row's own included (g is
        # masked: the rows past the piece keep its whole)
        gam = _dot(ones_low, g)                            # [C, D]
        gam_end = gam[C - 1:C]                             # [1, D]
        # the log-decay just before each sub-block's first row
        refs = [gam[s * SUB:s * SUB + 1] - g[s * SUB:s * SUB + 1]
                for s in range(nS)]
        to_ref = _exp_le0(gam - jnp.concatenate(
            [jnp.broadcast_to(x, (SUB, D)) for x in refs], axis=0))
        kq_ref = (k * to_ref, q * to_ref)
        m_k, m_q = [], []           # rows of the two pair matrices [., C]
        for s in range(nS):
            rows_s = slice(s * SUB, (s + 1) * SUB)
            if s:
                # across sub-blocks: row r through its sub-block's
                # reference, column i (an earlier sub-block's) against it
                k_from = jnp.where(r < s * SUB,
                                   k * _exp_le0(refs[s] - gam), 0.0)
                across = _dot(jnp.concatenate(
                    [x[rows_s] for x in kq_ref], axis=0), k_from,
                    ((1,), (1,)))                          # [2 SUB, C]
            # within a sub-block: the ratio of every pair, a channel; the
            # rows of its upper half meet the columns of that half alone
            g_s, k_s, q_s = gam[rows_s], k[rows_s], q[rows_s]
            H = SUB // 2
            w = [[jnp.zeros((H, C), f32) for _ in range(2)]
                 for _ in range(2)]                        # [k | q][half]
            for i in range(SUB):
                halves = (0, 1) if i < H else (1,)
                for half in halves:
                    rr = slice(half * H, (half + 1) * H)
                    e = _exp_le0(g_s[rr] - g_s[i:i + 1]) * k_s[i:i + 1]
                    for x, x_s in enumerate((k_s, q_s)):
                        w[x][half] = jnp.where(
                            col == s * SUB + i,
                            jnp.sum(x_s[rr] * e, axis=1, keepdims=True),
                            w[x][half])
            for m, w_x, rows_x in ((m_k, w[0], slice(0, SUB)),
                                   (m_q, w[1], slice(SUB, 2 * SUB))):
                within = jnp.concatenate(w_x, axis=0)
                m.append(within + across[rows_x] if s else within)
        A = jnp.where(strict, jnp.concatenate(m_k, axis=0), 0.0) * b
        P = jnp.where(low, jnp.concatenate(m_q, axis=0), 0.0)
        # (I + A)^-1: the diagonal sub-blocks' by the exact product of a
        # nilpotent matrix, (I - X)(I + X^2)(I + X^4)(I + X^8), X^16 = 0;
        # then the blocks below them, a level a time: (I + Y + Z)^-1 =
        # (I + Y)^-1 - (I + Y)^-1 Z (I + Y)^-1 where that Z's square is 0
        X = jnp.where(in_sub, A, 0.0)
        X2 = _dot(X, X)
        X4 = _dot(X2, X2)
        inv = eye - X + X2 - _dot(X, X2)
        inv = inv + _dot(inv, X4)
        inv = inv + _dot(inv, _dot(X4, X4))
        for Z in (jnp.where(in_half & ~in_sub, A, 0.0),
                  jnp.where(in_half, 0.0, A)):
            inv = inv - _dot(_dot(inv, Z), inv)
        G = jnp.exp(gam)                                   # <= 1
        solved = _dot(inv, jnp.concatenate([v, k * G], axis=1) * b)
        s0 = s_buf[h]                                      # [D keys, D]
        through = _dot(jnp.concatenate([solved[:, D:], q * G], axis=0), s0)
        u = solved[:, :D] - through[:C]
        out = through[C:] + _dot(P, u)
        o_ref[at, :] = jnp.where(mine, out, o_ref[at, :])
        # S_end = diag(G_end) S_0 + sum_i (k_i G_end / G_i) u_i^T
        s_buf[h] = _dot(
            jnp.concatenate([k * _exp_le0(gam_end - gam), jnp.where(
                diag, jnp.broadcast_to(jnp.exp(gam_end), (D, D)), 0.0)],
                axis=0),
            jnp.concatenate([u, s0], axis=0), ((0,), (0,)))
        return carry

    lax.fori_loop(0, N, head, 0)

    @pl.when(closes)
    def _():
        copy = pltpu.make_async_copy(s_buf, s_out.at[slot_ref[p]], sem.at[0])
        copy.start()
        copy.wait()


@functools.partial(jax.jit, inline=True,
                   static_argnames=("interpret", "name"))
def _chunk_call(pieces, q, k, v, g, b, state, *, interpret, name):
    TN, D = q.shape
    N = b.shape[1]
    rows = pl.BlockSpec((CHUNK * N, D), lambda p, n, c, *_: (c[p], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(pieces),
        # a step a piece: as many as the tick's runs make, none for a tick
        # without (no bound on them is static: every row may be a run)
        grid=(pieces[0][0],),
        in_specs=[rows, rows, rows, rows,
                  pl.BlockSpec((CHUNK, N), lambda p, n, c, *_: (c[p], 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[rows, pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((N, D, D), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))])
    compiler_params = None
    if not interpret:
        # a chunk's rows of q, k, v, g and o, each twice, and the run's
        # matrices
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(10 * CHUNK + D) * N * D * 4 + (16 << 20))
    return pl.pallas_call(
        _chunk_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((TN, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # the store is updated in place (operands count the prefetched)
        input_output_aliases={len(pieces) + 5: 1},
        compiler_params=compiler_params, interpret=interpret, name=name,
    )(*pieces, q, k, v, g, b, state)


@jax.named_scope("kda_chunk")
def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              b: jax.Array, runs, rows: jax.Array, state: jax.Array,
              slot: jax.Array, interpret: Optional[bool] = None, *,
              name: str = "kda_chunk") -> Tuple[jax.Array, jax.Array]:
    """The rule over the runs of ``rows`` [T] bool (whole runs; no two of
    one sequence), chunkwise, a Mosaic kernel. Operands and results as
    :func:`kda_chunk_reference`, whose doc-string is the mathematics; what
    differs is what is computed when. The grid is the tick's PIECES and
    nothing else: a step loads its chunk's rows (the block stays where the
    piece before had the same chunk), reads the run's matrices from the
    store where the piece opens its run (zero where the run starts at
    position 0), and a head after the other forms the piece's ``A`` and
    ``P`` from the rows' running log-decay (the sum within the piece as a
    product with a triangle of ones), inverts ``I + A`` (the 16-row
    diagonal blocks as the exact product of a nilpotent matrix, the blocks
    below them by two levels of ``M^-1 - M^-1 Z M^-1``), and carries the
    head's matrix in VMEM to the run's next piece; the piece that closes
    its run writes them to the store. A bucket of 2,048 rows of which 770
    are prompt rows pays for ~13 pieces, not 32 chunks, and a tick without
    such runs for none. Nothing of ``[T, ...]`` is built around the call
    but the result's mask."""
    if interpret is None:
        interpret = _use_interpret()
    f32 = jnp.float32
    T0, N, D = q.shape
    # heads as wide as the lanes; the inverse is written for four
    # sub-blocks of 16 (X^16 = 0, two levels below the diagonal)
    assert D % 128 == 0 and (CHUNK, SUB) == (64, 16), (D, CHUNK, SUB)
    T = -(-T0 // CHUNK) * CHUNK

    def padded(x, fill=0):
        return jnp.pad(x, [(0, T - T0)] + [(0, 0)] * (x.ndim - 1),
                       constant_values=fill)

    rows = padded(rows, False)
    pieces = _pieces(rows, padded(runs.start, True), padded(runs.last, True),
                     padded(runs.fresh, True), padded(slot), CHUNK)
    q, k, v, g = (padded(x.astype(f32)).reshape(T * N, D)
                  for x in (q, k, v, g))
    b = padded(b.astype(f32))

    def call(carry):
        _, q, state = carry
        o, state = _chunk_call(pieces, q, k, v, g, b, state,
                               interpret=interpret, name=name)
        return False, o, state

    # a tick without such runs (most decode ticks) starts no kernel. A loop
    # of at most one trip and not a ``cond``: XLA's rematerialization takes
    # a call that updates the store in place for a second store (3.4 GB)
    # unless a loop carries it, and then spends ~1 ms a decode tick
    # re-laying what else is live to make room it does not need. The
    # queries hold the result's place in the loop (a buffer of zeros would
    # be 100 us of a 2,048-row tick to fill): every row is masked below
    _, o, state = lax.while_loop(lambda carry: carry[0], call,
                                 (pieces[0][0] > 0, q, state))
    # a row no piece holds is whatever its buffer held
    o = jnp.where(rows[:, None, None], o.reshape(T, N, D), 0.0)
    return o[:T0], state
