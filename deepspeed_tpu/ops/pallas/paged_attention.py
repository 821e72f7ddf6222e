"""Pallas paged-attention kernel: per-token block-table KV gather + online
softmax, without materializing the gathered context in HBM.

Parity: reference ``inference/v2/kernels/ragged_ops`` (blocked flash attention
over the blocked KV cache, ``linear_blocked_kv_rotary`` etc.) — the CUDA tree
walks each sequence's block list; here the block list is a SCALAR-PREFETCH
argument so the BlockSpec ``index_map`` itself chases the table: grid step
(t, j) streams block ``tables[t, j]`` of the pool through VMEM for token t.

Decode attention is HBM-bandwidth-bound (read each live sequence's KV once);
the win over the XLA reference path (``models/paged.py
paged_attention_reference``) is avoiding the [T, MB*bs, K, D] gathered copy
in HBM — the kernel reads pool blocks directly.

Shapes: q [T, N, D]; kpool/vpool [NB, bs, K, D]; tables [T, MB] int32;
lengths [T] int32 (context length per token, pos+1). GQA via in-kernel
head-group batching (N = K * rep).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _kernel(tables_ref, lengths_ref,           # scalar prefetch
            q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref,
            *, bs: int, rep: int, n_blocks_per_seq: int):
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lengths_ref[t]
    run = j * bs < length

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # [N, D]
        k = k_ref[0].astype(jnp.float32)                  # [bs, K, D]
        v = v_ref[0].astype(jnp.float32)
        N, D = q.shape
        K = k.shape[1]
        scale = 1.0 / jnp.sqrt(jnp.float32(D))

        q3 = q.reshape(K, rep, D)
        kt = jnp.swapaxes(k, 0, 1)                        # [K, bs, D]
        s = jax.lax.dot_general(
            q3, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [K, rep, bs]
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col < length, s, NEG_INF)

        s2 = s.reshape(N, bs)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True))
        p = jnp.exp(s2 - m_new)                           # [N, bs]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0:1] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, 0:1] = m_new

        vt = jnp.swapaxes(v, 0, 1)                        # [K, bs, D]
        pv = jax.lax.dot_general(
            p.reshape(K, rep, bs), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # [K, rep, D]
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(N, D)

    @pl.when(j == n_blocks_per_seq - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def paged_attention(q: jax.Array, kpool: jax.Array, vpool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Drop-in for ``models.paged.paged_attention_reference``."""
    if interpret is None:
        interpret = _use_interpret()
    Tn, N, D = q.shape
    NB, bs, K, D2 = kpool.shape
    assert D == D2 and N % K == 0
    rep = N // K
    MB = tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Tn, MB),
        in_specs=[
            pl.BlockSpec((1, N, D), lambda t, j, tbl, ln: (t, 0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda t, j, tbl, ln: (tbl[t, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, K, D),
                         lambda t, j, tbl, ln: (tbl[t, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, N, D), lambda t, j, tbl, ln: (t, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N, D), jnp.float32),
            pltpu.VMEM((N, 128), jnp.float32),
            pltpu.VMEM((N, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, rep=rep, n_blocks_per_seq=MB)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tn, N, D), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="paged_attention",
    )(tables, lengths, q, kpool, vpool)
