"""Flash attention as a Pallas TPU kernel (forward + backward).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/`` softmax/transform kernels behind
``DeepSpeedTransformerLayer``, ``ops/transformer/transformer.py:296``, and the
triton flash path ``ops/transformer/inference/triton/attention.py``). Online
(blockwise) softmax never materializes the [S, S] score matrix in HBM:

* forward: grid (batch*q_heads, q_blocks, kv_blocks); kv innermost so the
  running max/denominator/accumulator live in VMEM scratch across kv steps;
* backward: two kernels (dq; dk+dv) recomputing probabilities from the saved
  logsumexp — the standard flash-attention-2 decomposition;
* GQA: kv tensors stay at [batch*kv_heads, S, D]; the q-head → kv-head
  mapping happens in the BlockSpec index maps (no ``jnp.repeat`` in HBM, and
  VJP residuals hold the small kv tensors);
* a grid step does only what its block needs: a block above the diagonal
  or wholly behind a ``window`` is neither computed nor copied (the index
  maps hold the moving block where it was); a block goes tile by tile, with
  no mask where every column is live, and a diagonal, window-edge or edge
  block skips its tiles above the diagonal, behind the window or past a
  length; :func:`step_account` counts all of it from the shapes;
* CPU fallback = ``interpret=True`` (the role the reference's CPU op builders
  play for its CUDA ops).
"""
from __future__ import annotations

import functools
import inspect
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params():
    if not _use_interpret():
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return None


def _block_mask(q_start, kv_start, shape, causal, kv_len, q_len=None,
                q_axis=0, window=0):
    """Which scores of a block count; ``q_axis`` 1: of a block laid keys
    down and queries across. ``window``: a row sees its last ``window``
    columns, itself included (0: every one)."""
    row = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    col = kv_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = col < kv_len
    if q_len is not None:
        mask = jnp.logical_and(mask, row < q_len)
    if causal:
        mask = jnp.logical_and(mask, col <= row)
    if window:
        mask = jnp.logical_and(mask, col > row - window)
    return mask


# --------------------------------------------------------------------------- #
# which steps of a grid are live, which of those need a mask, and which block
# a step names: ONE set of rules for the kernels' bodies, their index maps and
# ``step_account``. Every function takes Python ints (the account) or traced
# scalars (a kernel, an index map).
# --------------------------------------------------------------------------- #

def _least(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _most(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _step_kind(q_start, kv_start, *, causal, kv_len, q_len, block_q, block_kv,
               window=0):
    """``(live, masked)`` of the block or tile at ``(q_start, kv_start)``:
    live if any of its columns counts for any of its rows; masked if some do
    not (it crosses the diagonal, the edge of the ``window`` or an unpadded
    length), else every one does and the body builds no mask. A block of a grid always starts inside the
    lengths (they are padded to the next block, no further); a tile of an
    edge block may lie past them. ``q_len`` None: rows past the length are
    computed and sliced off (forward, ``dq``)."""
    live = kv_start < kv_len
    masked = kv_start + block_kv > kv_len
    if q_len is not None:
        live = live & (q_start < q_len)
        masked = masked | (q_start + block_q > q_len)
    if causal:
        live = live & (kv_start <= q_start + block_q - 1)
        masked = masked | (kv_start + block_kv - 1 > q_start)
    if window:
        # row r sees the columns r - window < c <= r
        live = live & (kv_start + block_kv - 1 > q_start - window)
        masked = masked | (kv_start <= q_start + block_q - 1 - window)
    return live, masked


# rows and columns of the tiles a block is computed in: a block is what a step
# copies, a tile what one product covers, and a tile of a diagonal or edge
# block with no live column is skipped like a dead block of the grid
_TILE = 512


def _tiles(q_start, kv_start, tile, *, causal, kv_len, q_len, block_q,
           block_kv, window=0):
    """``(row offset, column offset, rows, columns, live)`` of each tile of
    the block at ``(q_start, kv_start)``, rows outermost (a row's tiles in
    the order of their columns)."""
    tq = tile if block_q % tile == 0 else block_q
    tkv = tile if block_kv % tile == 0 else block_kv
    return [(r, c, tq, tkv, _step_kind(
        q_start + r, kv_start + c, causal=causal, kv_len=kv_len, q_len=q_len,
        block_q=tq, block_kv=tkv, window=window)[0])
        for r in range(0, block_q, tq) for c in range(0, block_kv, tkv)]


def _kv_block(i, j, *, causal, block_q, block_kv, window=0):
    """The K / V block that step ``(i, j)`` of ``fwd`` / ``dq`` names: ``j``
    held at the last block the row's diagonal reaches, so that a dead step
    names the block the step before it had and nothing is copied; under a
    ``window`` held too at the first block the row's window reaches (the
    dead steps ahead of it name the block the first live step wants)."""
    if window:
        j = _most(j, _most(i * block_q - window + 1, 0) // block_kv)
    return _least(j, ((i + 1) * block_q - 1) // block_kv) if causal else j


def _q_block(i, j, *, causal, q_len, block_q, block_kv, window=0):
    """The Q / dO / lse / delta block that step ``(j, i)`` of ``dkv`` names:
    ``i`` held at or under the column's diagonal (a dead step above it names
    the first live block, which the next live step wants anyway; keys past
    the last query, Skv > S, have none and name the last block) and, under
    a ``window``, at or above the last block whose rows still see the
    column block (the dead steps past it name the block the last live step
    had)."""
    if not causal:
        return i
    if window:
        i = _least(i, ((j + 1) * block_kv + window - 2) // block_q)
    return _least(_most(i, (j * block_kv) // block_q), (q_len - 1) // block_q)


def step_account(S: int, Skv: int, causal: bool, block_q: int, block_kv: int,
                 rep: int = 1, window: int = 0):
    """What the three grids do at these blocks, from the shapes alone (the
    grids are static): ``{kernel: {"steps", "live", "masked", "open",
    "fetched", "computed"}}`` for ONE index of the grid's leading axis (a
    query head for ``flash_fwd`` / ``flash_dq``, a KV head with its ``rep``
    query heads for ``flash_dkv``). ``masked``: live steps that build a mask
    (diagonal or edge) and go tile by tile, ``open``: live steps that do not;
    ``fetched``: steps whose moving block differs from the step before (a
    copy from HBM); ``computed``: score elements multiplied out (open blocks
    whole, masked blocks' live tiles)."""
    n_q = -(-S // block_q)
    n_kv = -(-Skv // block_kv)
    shape = dict(causal=causal, block_q=block_q, block_kv=block_kv,
                 window=window)

    def walk(steps, q_len):
        out = dict(steps=0, live=0, masked=0, open=0, fetched=0, computed=0)
        before = None
        at = dict(kv_len=Skv, q_len=q_len, **shape)
        for i, j, block in steps:
            live, masked = _step_kind(i * block_q, j * block_kv, **at)
            out["steps"] += 1
            out["live"] += live
            out["masked"] += live and masked
            out["open"] += live and not masked
            out["fetched"] += block != before
            if live and masked:
                out["computed"] += sum(
                    tq * tkv * t_live for _, _, tq, tkv, t_live in _tiles(
                        i * block_q, j * block_kv, _TILE, **at))
            elif live:
                out["computed"] += block_q * block_kv
            before = block
        return out

    rows = walk(((i, j, _kv_block(i, j, **shape))
                 for i in range(n_q) for j in range(n_kv)), None)
    cols = walk(((i, j, (h, _q_block(i, j, q_len=S, **shape)))
                 for j in range(n_kv) for h in range(rep)
                 for i in range(n_q)), S)
    return {"flash_fwd": rows, "flash_dq": dict(rows), "flash_dkv": cols}


def _set_gauges(kernels, *shape):
    """The account of the calls being traced (``step_account``'s arguments),
    in the registry."""
    from deepspeed_tpu import telemetry

    gauge = telemetry.gauge(
        "flash_steps", "grid steps of the last traced flash kernel call for "
        "one index of its leading axis, by kind (step_account)")
    account = step_account(*shape)
    for kernel in kernels:
        for kind, n in account[kernel].items():
            gauge.set(n, kernel=_call_name(kernel, shape[-1]), kind=kind)


def _run_step(tile, q_start, kv_start, tile_size, **shape):
    """A live step of the block at ``(q_start, kv_start)``, tile after tile.
    An open block builds no mask and runs straight through,
    ``tile(rows, cols, None)`` (in the forward, where a tile's exp waits for
    its rows' max, 1,024 x 1,024 as four tiles takes 8 % less than as one
    product; the backward kernels take the same either way); a diagonal or
    edge block goes under its mask, ``tile(rows, cols, (the tile's q_start,
    kv_start))``, its dead tiles skipped."""
    live, masked = _step_kind(q_start, kv_start, **shape)
    tiles = _tiles(q_start, kv_start, tile_size, **shape)

    @pl.when(jnp.logical_and(live, jnp.logical_not(masked)))
    def _open():
        for r, c, tq, tkv, _ in tiles:
            tile(pl.ds(r, tq), pl.ds(c, tkv), None)

    @pl.when(jnp.logical_and(live, masked))
    def _tile_by_tile():
        for r, c, tq, tkv, t_live in tiles:
            pl.when(t_live)(functools.partial(
                tile, pl.ds(r, tq), pl.ds(c, tkv),
                (q_start + r, kv_start + c)))


def _f32(x):
    return x.astype(jnp.float32)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_LANES = 128


def _lanes(x, n):
    """``x [rows, 128]``, every lane the same, as ``[rows, n]``: a row's
    statistic beside each of its ``n`` columns without a lane broadcast."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return jnp.concatenate([x] * (n // _LANES), axis=1)


_NT = ((1,), (1,))     # a @ b.T
_NN = ((1,), (0,))     # a @ b


# --------------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------------- #

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, kv_len: int,
                block_q: int, block_kv: int, tile: int, window: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(rows, cols, mask_at):
        # operands widened, probabilities left float32: the MXU rounds
        # them as a cast would (results equal to the bit) and a cast of
        # p / ds costs 1-2 % of a call. The scale stays on the scores:
        # folded into bfloat16 queries it would round them.
        s = _dot(_f32(q_ref[0, rows, :]), _f32(k_ref[0, cols, :]),
                 _NT) * scale
        if mask_at is not None:
            s = jnp.where(_block_mask(*mask_at, s.shape, causal, kv_len,
                                      window=window), s, NEG_INF)

        # the statistics lie replicated over their 128 lanes: no step
        # slices a lane out of them or broadcasts one back
        m_prev = m_ref[rows, :]                           # [tq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))        # [tq, tkv]
        alpha = jnp.exp(m_prev - m_new)                   # [tq, 128]
        l_ref[rows, :] = alpha * l_ref[rows, :] + jnp.sum(
            p, axis=1, keepdims=True)
        m_ref[rows, :] = m_new

        pv = _dot(p, _f32(v_ref[0, cols, :]), _NN)        # [tq, d]
        acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, pv.shape[1]) + pv

    _run_step(_tile, i * block_q, j * block_kv, tile, causal=causal,
              kv_len=kv_len, q_len=None, block_q=block_q, block_kv=block_kv,
              window=window)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / _lanes(l_safe, acc_ref.shape[1])).astype(
            o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_ref[...] + jnp.log(l_safe))
        lse_ref[0] = lse[:, 0:1]


def _row_specs(rep, D, block_q, block_kv, kv_block):
    """Block specs of a ``(BN, n_q, n_kv)`` grid: a row's own block and the
    moving K / V block."""
    kv_of = _kv_index(rep)
    row = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    stat = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv = pl.BlockSpec((1, block_kv, D),
                      lambda b, i, j: (kv_of(b), kv_block(i, j), 0))
    return row, stat, kv


def _call_name(kernel: str, window: int) -> str:
    """The call's name in a trace: a window call carries one of its own, so
    that a reader tells it from a full one (its live area differs)."""
    return f"window_{kernel}" if window else kernel


# Each call sits in an inlined inner ``jit``: a training step traces the
# forward and the backward rule several times over (linearize, remat, the
# transpose), and a body of eight tiles traced anew each time cost 3-5 s of
# the one-chip cell's ``setup_s``; the inner trace is cached by shapes.
def _inlined_call(build):
    static = tuple(
        name for name, arg in inspect.signature(build).parameters.items()
        if arg.kind is arg.KEYWORD_ONLY)
    return jax.jit(build, inline=True, static_argnames=static)


@_inlined_call
def _fwd(q, k, v, *, scale, causal, kv_len, rep, block_q, block_kv, tile,
         interpret, window=0):
    BN, S_pad, D = q.shape
    BK, Skv_pad, _ = k.shape
    shape = dict(causal=causal, block_q=block_q, block_kv=block_kv,
                 window=window)
    row, stat, kv = _row_specs(rep, D, block_q, block_kv,
                               functools.partial(_kv_block, **shape))

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, kv_len=kv_len, tile=tile,
                          **shape),
        grid=(BN, S_pad // block_q, Skv_pad // block_kv),
        in_specs=[row, kv, kv],
        out_specs=[row, stat],
        out_shape=[
            jax.ShapeDtypeStruct((BN, S_pad, D), q.dtype),
            # per-row logsumexp; trailing dim 1 == array dim keeps the TPU
            # tiling rules happy without lane-broadcasting into HBM
            jax.ShapeDtypeStruct((BN, S_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=_call_name("flash_fwd", window),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------------- #
# backward kernels
# --------------------------------------------------------------------------- #

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale: float, causal: bool, kv_len: int,
                   block_q: int, block_kv: int, tile: int, window: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _tile(rows, cols, mask_at):
        k = _f32(k_ref[0, cols, :])
        s = _dot(_f32(q_ref[0, rows, :]), k, _NT) * scale
        p = jnp.exp(s - lse_ref[0, rows, :])               # [tq, tkv]
        if mask_at is not None:
            p = jnp.where(_block_mask(*mask_at, s.shape, causal, kv_len,
                                      window=window), p, 0.0)
        dp = _dot(_f32(do_ref[0, rows, :]), _f32(v_ref[0, cols, :]), _NT)
        # the scale: once, at _finalize
        ds = p * (dp - delta_ref[0, rows, :])
        acc_ref[rows, :] += _dot(ds, k, _NN)

    _run_step(_tile, i * block_q, j * block_kv, tile, causal=causal,
              kv_len=kv_len, q_len=None, block_q=block_q, block_kv=block_kv,
              window=window)

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale: float, causal: bool, kv_len: int, q_len: int,
                    n_q: int, block_q: int, block_kv: int, tile: int,
                    window: int):
    j = pl.program_id(1)       # kv block (outer)
    inner = pl.program_id(2)   # (q-head-in-group, q block) flattened (inner)
    n_inner = pl.num_programs(2)
    i = inner % n_q

    @pl.when(inner == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile(rows, cols, mask_at):
        # scores and probabilities TRANSPOSED, keys down and queries
        # across: dv and dk take them as they lie (no transposed left
        # operand), and a query's lse / delta is a lane's, laid beside the
        # keys by a sublane broadcast
        q = _f32(q_ref[0, rows, :])
        do = _f32(do_ref[0, rows, :])
        s = _dot(_f32(k_ref[0, cols, :]), q, _NT) * scale  # [tkv, tq]
        p = jnp.exp(s - lse_ref[0, 0, :, rows])
        if mask_at is not None:
            p = jnp.where(_block_mask(*mask_at, s.shape, causal, kv_len,
                                      q_len, q_axis=1, window=window),
                          p, 0.0)
        dv_acc[cols, :] += _dot(p, do, _NN)
        dp = _dot(_f32(v_ref[0, cols, :]), do, _NT)        # [tkv, tq]
        # the scale: once, at _finalize
        ds = p * (dp - delta_ref[0, 0, :, rows])
        dk_acc[cols, :] += _dot(ds, q, _NN)

    _run_step(_tile, i * block_q, j * block_kv, tile, causal=causal,
              kv_len=kv_len, q_len=q_len, block_q=block_q, block_kv=block_kv,
              window=window)

    @pl.when(inner == n_inner - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _kv_index(rep: int):
    """Map a q-batch grid index (batch*q_heads) to the kv-batch index
    (batch*kv_heads) for GQA: consecutive groups of ``rep`` q-heads share one
    kv head. With rep == 1 this is the identity."""
    if rep == 1:
        return lambda b: b

    def kv_of(b):
        # b = batch * N + h; N = K * rep  →  kv = batch * K + h // rep
        return b // rep

    return kv_of


@_inlined_call
def _bwd_dq(q, k, v, do, lse, delta, *, scale, causal, kv_len, rep, block_q,
            block_kv, tile, interpret, window=0):
    BN, S_pad, D = q.shape
    Skv_pad = k.shape[1]
    shape = dict(causal=causal, block_q=block_q, block_kv=block_kv,
                 window=window)
    row, stat, kv = _row_specs(rep, D, block_q, block_kv,
                               functools.partial(_kv_block, **shape))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, kv_len=kv_len,
                          tile=tile, **shape),
        grid=(BN, S_pad // block_q, Skv_pad // block_kv),
        in_specs=[row, kv, kv, row, stat, stat],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((BN, S_pad, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=_call_name("flash_dq", window),
    )(q, k, v, do, lse, delta)


@_inlined_call
def _bwd_dkv(q, k, v, do, lse, delta, *, scale, causal, kv_len, q_len, rep,
             block_q, block_kv, tile, interpret, window=0):
    """dk/dv: the grid's leading dim is the KV batch; the inner dim flattens
    (q-head-in-group × q-block) so the accumulator sums the whole GQA
    group."""
    S_pad, D = q.shape[1:]
    BK, Skv_pad, _ = k.shape
    n_q = S_pad // block_q
    q_block = functools.partial(_q_block, causal=causal, q_len=q_len,
                                block_q=block_q, block_kv=block_kv,
                                window=window)

    moving = pl.BlockSpec(
        (1, block_q, D),
        lambda b, j, t: (b * rep + t // n_q, q_block(t % n_q, j), 0))
    # lse and delta as ROWS, a q block's to itself: ``[BN, n_q, 1, block_q]``
    # (the same bytes in HBM), whose block's last two dims are the array's
    # whatever ``block_q`` is
    stat = pl.BlockSpec(
        (1, 1, 1, block_q),
        lambda b, j, t: (b * rep + t // n_q, q_block(t % n_q, j), 0, 0))
    lse = lse.reshape(lse.shape[0], n_q, 1, block_q)
    delta = delta.reshape(delta.shape[0], n_q, 1, block_q)
    col = pl.BlockSpec((1, block_kv, D), lambda b, j, t: (b, j, 0))
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          kv_len=kv_len, q_len=q_len, n_q=n_q,
                          block_q=block_q, block_kv=block_kv, tile=tile,
                          window=window),
        grid=(BK, Skv_pad // block_kv, rep * n_q),
        in_specs=[moving, col, col, moving, stat, stat],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((BK, Skv_pad, D), k.dtype),
            jax.ShapeDtypeStruct((BK, Skv_pad, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=_call_name("flash_dkv", window),
    )(q, k, v, do, lse, delta)


# --------------------------------------------------------------------------- #
# public entry — custom VJP over the padded [B*heads, S, D] layout
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, scale, causal, kv_len, q_len, rep, block_q, block_kv,
           window):
    return _flash_fwd(q, k, v, scale, causal, kv_len, q_len, rep, block_q,
                      block_kv, window)[0]


def _flash_fwd(q, k, v, scale, causal, kv_len, q_len, rep, block_q, block_kv,
               window):
    _set_gauges(("flash_fwd",), q_len, kv_len, causal, block_q, block_kv, rep,
                window)
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, kv_len=kv_len, rep=rep,
                  block_q=block_q, block_kv=block_kv, tile=_TILE,
                  interpret=_use_interpret(), window=window)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, kv_len, q_len, rep, block_q, block_kv, window,
               residuals, do):
    q, k, v, o, lse = residuals
    # delta_r = rowsum(dO * O) — cheap elementwise, let XLA fuse it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BN, S_pad, 1]
    shape = dict(scale=scale, causal=causal, kv_len=kv_len, rep=rep,
                 block_q=block_q, block_kv=block_kv, tile=_TILE,
                 interpret=_use_interpret(), window=window)
    _set_gauges(("flash_dq", "flash_dkv"), q_len, kv_len, causal, block_q,
                block_kv, rep, window)
    dq = _bwd_dq(q, k, v, do, lse, delta, **shape)
    dk, dv = _bwd_dkv(q, k, v, do, lse, delta, q_len=q_len, **shape)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _mesh_partition(B: int, N: int, K: int):
    """How to run the kernel on the live mesh → ``(mesh, spec,
    axis_names)``, or ``None`` for a direct call.

    Mosaic kernels cannot be partitioned by GSPMD: on more than one chip a
    ``pallas_call`` is only accepted inside a ``shard_map`` that is manual
    over EVERY mesh axis (interpret mode on the CPU lowers to plain XLA
    and hides this). Each (batch row, head) is independent, so the batch
    maps over the data-parallel axes and the heads over ('tensor', 'seq')
    — the layouts the engine and Ulysses already use — with no
    collective. A dim an axis does not divide stays replicated over it
    (every shard computes it; correct, redundant)."""
    from deepspeed_tpu.comm import mesh as M

    if not M.mesh_is_initialized():
        return None
    mesh = M.get_mesh()
    manual = M.already_manual_axes()
    free = tuple(a for a in mesh.axis_names if a not in manual)
    if not free or (mesh.size == 1 and not manual):
        return None     # an enclosing shard_map owns every axis / one chip

    def fit(axes, *dims):
        kept, n = (), 1
        for a in axes:
            size = mesh.shape[a]
            if a in free and size > 1 and all(
                    d % (n * size) == 0 for d in dims):
                kept, n = kept + (a,), n * size
        return kept[0] if len(kept) == 1 else (kept or None)

    spec = P(fit((M.DATA_AXIS, M.ZSHARD_AXIS, M.EXPERT_AXIS), B), None,
             fit((M.TENSOR_AXIS, M.SEQ_AXIS), N, K), None)
    if manual:   # nested: build on the context's mesh, which records them
        return jax.sharding.get_abstract_mesh(), spec, frozenset(free)
    return mesh, spec, frozenset()


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    segment_mask: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    window: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Drop-in for ``models.transformer.dot_product_attention``.

    q: [B, S, N, D]; k, v: [B, S, K, D] (K divides N → GQA via kernel index
    maps, no repetition in HBM). ``window`` (a Python int, closed over like
    ``causal``; 0: none): a row sees its last ``window`` positions, itself
    included; the calls then carry a name of their own
    (``window_flash_fwd`` / ``_dq`` / ``_dkv``). ``scale``: the scores'
    factor (None: ``D ** -0.5``). Arbitrary masks fall back
    to the XLA reference implementation (the Pallas kernel handles causal,
    causal under a window, and full).

    ``block_q`` / ``block_kv``: the three kernels' blocks, for a caller that
    names them; left out, they are chosen from the shapes
    (:func:`choose_blocks`). Blocks are capped to the (pow2-rounded)
    sequence length for short sequences. Under a multi-device mesh the
    kernel runs per shard (:func:`_mesh_partition`).
    """
    if segment_mask is not None:
        from deepspeed_tpu.models.transformer import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal,
                                     segment_mask=segment_mask,
                                     window=window, scale=scale)
    if window and not causal:
        raise ValueError("a window is the last positions a causal row sees: "
                         f"window={window} needs causal=True")

    B, _, N, _ = q.shape
    K = k.shape[2]
    if N % K != 0:
        raise ValueError(f"q heads {N} not divisible by kv heads {K}")
    local = functools.partial(_flash_local, causal=causal, block_q=block_q,
                              block_kv=block_kv, window=int(window),
                              scale=scale)
    part = _mesh_partition(B, N, K)
    if part is None:
        return local(q, k, v)
    mesh, spec, axis_names = part
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=axis_names,
                         check_vma=False)(q, k, v)


def choose_blocks(S: int, Skv: int):
    """``(block_q, block_kv)`` of the three kernels from what a call can see.

    1,024 x 1,024, capped to the (pow2-rounded) lengths. A grid step costs
    0.3 us whatever it holds and a block's time goes with its area, so the
    fewest steps win; what a large block wastes on the diagonal it gets back
    by skipping its dead 512-wide tiles. Timed on a v5e at 1 x 4,096 x 32 / 8
    heads and 2 x 2,048 x 32 / 32 heads of 128 in bfloat16, forward, ``dq``
    and ``dk/dv`` each, nine shapes from 256 x 256 to 2,048 x 512: the three
    kernels want the same (PERF.md section 5, PR 36). Heads of 128 and 256
    in bfloat16 and float32 compile for the v5e at these blocks
    (``tests/unit/test_chip_compile.py``)."""
    return min(1024, _round_pow2(S)), min(1024, _round_pow2(Skv))


def _flash_local(q, k, v, *, causal, block_q, block_kv, window=0,
                 scale=None):
    B, S, N, D = q.shape
    rep = N // k.shape[2]
    Skv = k.shape[1]
    chosen = choose_blocks(S, Skv)
    block_q = min(block_q or chosen[0], _round_pow2(S))
    block_kv = min(block_kv or chosen[1], _round_pow2(Skv))

    # [B, S, H, D] → [B*H, S, D]
    def to_bn(x):
        b, s, n, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b * n, s, d)

    qb = _pad_seq(to_bn(q), block_q)
    kb = _pad_seq(to_bn(k), block_kv)
    vb = _pad_seq(to_bn(v), block_kv)

    if scale is None:
        scale = 1.0 / math.sqrt(D)
    o = _flash(qb, kb, vb, scale, causal, Skv, S, rep, block_q, block_kv,
               window)
    o = o[:, :S]
    return o.reshape(B, N, S, D).transpose(0, 2, 1, 3)


def _pad_seq(x, block):
    s = x.shape[1]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


def _round_pow2(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p
