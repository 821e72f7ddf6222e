"""Block-sparse attention as a Pallas TPU kernel (forward + backward).

Parity: reference ``deepspeed/ops/sparse_attention/`` (triton ``matmul.py`` /
``softmax.py`` block-sparse kernels + ``sparsity_config.py`` layout builders:
Dense, Fixed, BigBird, BSLongformer, Variable) and ``csrc/sparse_attention``.

TPU design: one flash-style online-softmax kernel whose kv-block loop is gated
by a **block layout** — an ``[num_q_blocks, num_kv_blocks]`` {0,1} matrix held
in SMEM. Inactive blocks skip the QK^T/PV matmuls entirely (``pl.when``), so
MXU work scales with layout density; the backward pass recomputes
probabilities from the saved logsumexp (flash-attention-2 decomposition) under
the same gating. Rows whose every block is inactive produce zero output (and
lse = -inf), matching the reference softmax semantics for fully-masked rows.

Layout builders are host-side numpy (they are config, not compute) and mirror
the reference's ``SparsityConfig.make_layout`` family.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# layout builders (reference ops/sparse_attention/sparsity_config.py)
# --------------------------------------------------------------------------- #

def dense_layout(n_blocks: int) -> np.ndarray:
    return np.ones((n_blocks, n_blocks), np.int32)


def fixed_layout(n_blocks: int, local_window: int = 4,
                 global_stride: int = 4) -> np.ndarray:
    """'Fixed' pattern: local banded window + periodic global columns
    (reference ``FixedSparsityConfig``)."""
    lay = np.zeros((n_blocks, n_blocks), np.int32)
    for i in range(n_blocks):
        lo = max(0, i - local_window + 1)
        lay[i, lo:i + 1] = 1
    lay[:, ::global_stride] = 1
    return np.ascontiguousarray(np.tril(lay) + np.triu(lay, 1) * lay)


def bigbird_layout(n_blocks: int, num_random: int = 2, num_local: int = 3,
                   num_global: int = 1, seed: int = 0) -> np.ndarray:
    """BigBird: global + sliding window + random blocks
    (reference ``BigBirdSparsityConfig``)."""
    rng = np.random.RandomState(seed)
    lay = np.zeros((n_blocks, n_blocks), np.int32)
    half = num_local // 2
    for i in range(n_blocks):
        lay[i, max(0, i - half):min(n_blocks, i + half + 1)] = 1
        if num_random > 0:
            lay[i, rng.choice(n_blocks, size=min(num_random, n_blocks),
                              replace=False)] = 1
    lay[:num_global, :] = 1
    lay[:, :num_global] = 1
    return lay


def bslongformer_layout(n_blocks: int, window: int = 3,
                        global_blocks: Tuple[int, ...] = (0,)) -> np.ndarray:
    """BSLongformer: symmetric sliding window + designated global blocks
    (reference ``BSLongformerSparsityConfig``)."""
    lay = np.zeros((n_blocks, n_blocks), np.int32)
    half = window // 2
    for i in range(n_blocks):
        lay[i, max(0, i - half):min(n_blocks, i + half + 1)] = 1
    for g in global_blocks:
        lay[g, :] = 1
        lay[:, g] = 1
    return lay


def variable_layout(n_blocks: int, local_windows: Tuple[int, ...] = (4,),
                    global_indices: Tuple[int, ...] = (0,)) -> np.ndarray:
    """Variable: per-row local windows cycling through ``local_windows`` +
    global columns (reference ``VariableSparsityConfig``)."""
    lay = np.zeros((n_blocks, n_blocks), np.int32)
    for i in range(n_blocks):
        w = local_windows[i % len(local_windows)]
        lay[i, max(0, i - w + 1):i + 1] = 1
    for g in global_indices:
        lay[:, g] = 1
    return lay


def causal_layout(layout: np.ndarray) -> np.ndarray:
    """Restrict any layout to the lower block triangle (decoder use)."""
    return np.ascontiguousarray(np.tril(layout).astype(np.int32))


# --------------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------------- #

def _fwd_kernel(lay_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, seq_len: int,
                block_q: int, block_kv: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    active = lay_ref[i, j] > 0

    @pl.when(active)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < seq_len
        if causal:
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m_ref[...] + jnp.log(safe_l), NEG_INF)
        lse_ref[0] = lse[:, 0].astype(jnp.float32)


def _fwd(q, k, v, layout, *, scale, causal, seq_len, block_q, block_kv,
         interpret):
    bh, sq, d = q.shape
    n_q, n_kv = sq // block_q, k.shape[1] // block_kv
    grid = (bh, n_q, n_kv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, seq_len=seq_len,
        block_q=block_q, block_kv=block_kv)
    lay_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            lay_spec,
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="block_sparse_fwd",
    )(layout, q, k, v)


# --------------------------------------------------------------------------- #
# backward kernels
# --------------------------------------------------------------------------- #

def _bwd_dq_kernel(lay_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref,
                   *, scale: float, causal: bool, seq_len: int,
                   block_q: int, block_kv: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(lay_ref[i, j] > 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0].astype(jnp.float32)[:, None]
        delta = delta_ref[0].astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < seq_len
        if causal:
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == n_kv - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lay_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                    *, scale: float, causal: bool, seq_len: int,
                    block_q: int, block_kv: int):
    j = pl.program_id(1)   # kv block (outer)
    i = pl.program_id(2)   # q block (inner)
    n_q = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    @pl.when(lay_ref[i, j] > 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0].astype(jnp.float32)[:, None]
        delta = delta_ref[0].astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        row = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < seq_len
        if causal:
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _bwd(scale, causal, seq_len, block_q, block_kv, interpret,
         res, do):
    q, k, v, o, lse, layout = res
    bh, sq, d = q.shape
    n_q, n_kv = sq // block_q, k.shape[1] // block_kv
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    lay_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q), lambda b, i, j: (b, i))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q, block_kv=block_kv),
        grid=(bh, n_q, n_kv),
        in_specs=[lay_spec, q_spec, kv_spec, kv_spec, q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="block_sparse_dq",
    )(layout, q, k, v, do, lse, delta)

    # dkv grid: kv outer, q inner — index maps swap (i, j) roles
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, block_q), lambda b, j, i: (b, i))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q, block_kv=block_kv),
        grid=(bh, n_kv, n_q),
        in_specs=[lay_spec, q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        interpret=interpret,
        name="block_sparse_dkv",
    )(layout, q, k, v, do, lse, delta)
    return dq, dk, dv, None


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_attn(q, k, v, layout, scale, causal, block_q, block_kv):
    seq_len = q.shape[1]
    o, _ = _fwd(q, k, v, layout, scale=scale, causal=causal, seq_len=seq_len,
                block_q=block_q, block_kv=block_kv,
                interpret=_use_interpret())
    return o


def _sparse_attn_fwd(q, k, v, layout, scale, causal, block_q, block_kv):
    seq_len = q.shape[1]
    o, lse = _fwd(q, k, v, layout, scale=scale, causal=causal,
                  seq_len=seq_len, block_q=block_q, block_kv=block_kv,
                  interpret=_use_interpret())
    return o, (q, k, v, o, lse, layout)


def _sparse_attn_bwd(scale, causal, block_q, block_kv, res, do):
    q = res[0]
    return _bwd(scale, causal, q.shape[1], block_q, block_kv,
                _use_interpret(), res, do)


_sparse_attn.defvjp(_sparse_attn_fwd, _sparse_attn_bwd)


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           layout: jax.Array, block_size: int = 128,
                           causal: bool = True,
                           scale: Optional[float] = None) -> jax.Array:
    """Block-sparse attention over a [n_blocks, n_blocks] {0,1} layout.

    q/k/v: [batch, heads, seq, head_dim] (seq must be a multiple of
    ``block_size``; pad the inputs otherwise). Returns [batch, heads, seq, dim].
    Layout rows with no active block produce zero output rows.
    """
    b, h, s, d = q.shape
    if s % block_size:
        raise ValueError(f"seq len {s} not a multiple of block {block_size}")
    n_blocks = s // block_size
    if layout.shape != (n_blocks, n_blocks):
        raise ValueError(f"layout {layout.shape} != {(n_blocks, n_blocks)}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    layout = jnp.asarray(layout, jnp.int32)

    def bn(x):
        return x.reshape(b * h, s, x.shape[-1])

    out = _sparse_attn(bn(q), bn(k), bn(v), layout, scale, causal,
                       block_size, block_size)
    return out.reshape(b, h, s, d)


def block_sparse_attention_reference(q, k, v, layout, block_size=128,
                                     causal=True, scale=None):
    """jnp reference (materializes the full mask) for numerics tests."""
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    mask = jnp.repeat(jnp.repeat(jnp.asarray(layout, bool), block_size, 0),
                      block_size, 1)
    if causal:
        mask = jnp.logical_and(mask, jnp.tril(jnp.ones((s, s), bool)))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * scale
    sc = jnp.where(mask, sc, NEG_INF)
    row_any = jnp.any(mask, axis=-1)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(row_any[None, None, :, None], p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
