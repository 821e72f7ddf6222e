"""Blockwise int8 quantize / fused dequant-reduce as Pallas TPU kernels.

TPU-native replacement for the reference's quantization kernel tree
(``csrc/quantization``): ``swizzled_quantize.cu`` (quantize + comm-layout
reorder) and ``quant_reduce.cu`` (fused dequantize-and-reduce consumed by the
qgZ quantized gradient path, ``runtime/comm/coalesced_collectives.py:31``).

* :func:`quantize_int8_blocks` — one VMEM pass per tile: amax, scale, round,
  int8 write. The reference's "swizzle" (reordering quantized output into
  per-rank-contiguous comm layout) is the caller's [world, chunk] reshape —
  XLA lays that out for free, so no separate swizzle kernel is needed.
* :func:`dequant_reduce` — the quant_reduce.cu analog: all ranks' int8
  chunks are dequantized and accumulated in fp32 in ONE pass over the int8
  data; the [world, chunk] fp32 intermediate the jnp path materializes never
  exists.

CPU fallback = interpret mode (same numerics).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows of quantization blocks processed per grid step
_ROW_TILE = 8


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# quantize
# --------------------------------------------------------------------------- #

def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                     # [R, B]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def quantize_int8_blocks(x: jax.Array, block: int = 2048
                         ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization of a flat array.

    → (q int8 [N], scale fp32 [N/block]); ``block`` must divide N.
    Same contract as the jnp ``ops.quantization.quantize_int8``.
    """
    N = x.shape[0]
    if N % block:
        raise ValueError(f"size {N} must be a multiple of block={block}")
    rows = N // block
    tile = min(_ROW_TILE, rows)
    if rows % tile:
        tile = 1
    x2 = x.reshape(rows, block)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile, block), lambda i: (i, 0)),
                   pl.BlockSpec((tile, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, block), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=_use_interpret(),
        name="quantize_int8_blocks",
    )(x2)
    return q.reshape(-1), s[:, 0]


# --------------------------------------------------------------------------- #
# fused dequant + reduce (quant_reduce.cu analog)
# --------------------------------------------------------------------------- #

def _dequant_reduce_kernel(q_ref, s_ref, o_ref, *, world: int, mean: bool):
    acc = jnp.zeros(o_ref.shape, jnp.float32)              # [R, B]
    for w in range(world):                                  # static unroll
        acc = acc + q_ref[w].astype(jnp.float32) * s_ref[w]
    if mean:
        acc = acc / world
    o_ref[...] = acc


def dequant_reduce(q: jax.Array, scales: jax.Array, block: int = 2048,
                   mean: bool = False) -> jax.Array:
    """Sum W ranks' int8 contributions without materializing fp32 copies.

    q: int8 [W, C] (rank-major, C % block == 0); scales: fp32 [W, C/block].
    → fp32 [C] = Σ_w dequant(q[w]). One pass over the int8 data.
    """
    W, C = q.shape
    if C % block:
        raise ValueError(f"chunk {C} must be a multiple of block={block}")
    rows = C // block
    tile = min(_ROW_TILE, rows)
    if rows % tile:
        tile = 1
    q3 = q.reshape(W, rows, block)
    s3 = scales.reshape(W, rows, 1)
    out = pl.pallas_call(
        functools.partial(_dequant_reduce_kernel, world=W, mean=mean),
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((W, tile, block), lambda i: (0, i, 0)),
                  pl.BlockSpec((W, tile, 1), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
        interpret=_use_interpret(),
        name="dequant_reduce",
    )(q3, s3)
    return out.reshape(-1)
