"""Evoformer (biased, gated) attention as a Pallas TPU kernel.

TPU-native replacement for the reference's CUTLASS Evoformer kernels
(``csrc/deepspeed4science/evoformer_attn`` — 14.9k LoC fwd/bwd behind
``DS4Sci_EvoformerAttention``): attention with an additive attention bias
(mask + pair biases, summed by the caller) computed flash-style — online
softmax over kv blocks, the [S, S] biased score matrix never materializes in
HBM; only the bias itself (which the model owns anyway: the pair
representation) is read tile by tile.

Backward: ``jax.vjp`` of the jnp reference (``ops/evoformer_attn.py``) —
correct by construction, including the pair-bias gradient the reference's
bwd kernels produce; it rematerializes scores per (batch, head) in XLA.
Wrap training calls in ``jax.checkpoint`` for flash-class total memory. The
sigmoid gating stays outside the kernel (XLA fuses the elementwise epilogue).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import (_block_mask,
                                                      _compiler_params,
                                                      _use_interpret,
                                                      NEG_INF)


def _evo_fwd_kernel(q_ref, k_ref, v_ref, b_ref, o_ref,
                    acc_ref, m_ref, l_ref,
                    *, scale: float, kv_len: int,
                    block_q: int, block_kv: int):
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    kv_start = j * block_kv

    @pl.when(kv_start < kv_len)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + b_ref[0].astype(jnp.float32)
        mask = _block_mask(i * block_q, kv_start, s.shape, False, kv_len)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(j == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _evo_flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
                   block_q: int, block_kv: int) -> jax.Array:
    """q/k/v: [G, S, N, D]; bias: [Gb, N, S, S] with Gb ∈ {1, G}."""
    G, S, N, D = q.shape
    Gb = bias.shape[0]
    scale = 1.0 / math.sqrt(D)
    block_q = min(block_q, max(128, 1 << (S - 1).bit_length()))
    block_kv = min(block_kv, max(128, 1 << (S - 1).bit_length()))

    # [G, S, N, D] → [G*N, S, D]; bias [Gb, N, S, S] → [Gb*N, S, S]
    qh = _pad_to(q.transpose(0, 2, 1, 3).reshape(G * N, S, D), 1, block_q)
    kh = _pad_to(k.transpose(0, 2, 1, 3).reshape(G * N, S, D), 1, block_kv)
    vh = _pad_to(v.transpose(0, 2, 1, 3).reshape(G * N, S, D), 1, block_kv)
    bh = _pad_to(_pad_to(bias.reshape(Gb * N, S, S), 1, block_q),
                 2, block_kv)
    Sq, Skv = qh.shape[1], kh.shape[1]

    def bias_row(b):
        # broadcast over the leading batch (MSA-rows) dim when Gb == 1
        return b if Gb == G else b % N

    grid = (G * N, Sq // block_q, Skv // block_kv)
    out = pl.pallas_call(
        functools.partial(_evo_fwd_kernel, scale=scale, kv_len=S,
                          block_q=block_q, block_kv=block_kv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, block_kv),
                         lambda b, i, j: (bias_row(b), i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G * N, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_use_interpret(),
        name="evoformer_attention",
    )(qh, kh, vh, bh)
    return out[:, :S].reshape(G, N, S, D).transpose(0, 2, 1, 3)


def _reference(q, k, v, bias):
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("gqnd,gknd->gnqk", q, k).astype(jnp.float32) * scale
    s = s + bias.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("gnqk,gknd->gqnd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def evoformer_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: jax.Array, block_q: int = 128,
                    block_kv: int = 128) -> jax.Array:
    """Flash-style biased attention. q/k/v: [G, S, N, D]; bias broadcastable
    to [G, N, S, S] on its leading dim (pass [1, N, S, S] to share the pair
    bias across MSA rows — it is read tile-wise, never expanded)."""
    return _evo_flash_fwd(q, k, v, bias, block_q, block_kv)


def _evo_vjp_fwd(q, k, v, bias, block_q, block_kv):
    return _evo_flash_fwd(q, k, v, bias, block_q, block_kv), (q, k, v, bias)


def _evo_vjp_bwd(block_q, block_kv, res, g):
    q, k, v, bias = res
    # reference-program VJP: includes the pair-bias gradient (summed over
    # the broadcast leading dim automatically by jax.vjp)
    _, pull = jax.vjp(_reference, q, k, v, bias)
    return pull(g)


evoformer_flash.defvjp(_evo_vjp_fwd, _evo_vjp_bwd)
