"""RMSNorm / LayerNorm as Pallas TPU kernels (forward) with analytic VJPs.

TPU-native replacement for the reference's norm kernels
(``csrc/transformer/ds_layer_norm.cu``, ``csrc/transformer/inference/csrc/
layer_norm.cu`` / ``rms_norm.cu``). One grid step normalizes a block of rows
held in VMEM: the row is read once, stats (mean/var) accumulate in fp32, the
scaled result is written once — an HBM-bandwidth-bound op done at one
read + one write. Backward is a jnp expression (XLA fuses it into the
surrounding backward graph, which is where the reference's dedicated bwd
kernels spend their time too).

CPU fallback = interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROW_BLOCK = 256


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def _ln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    o_ref[...] = ((x - mean) * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _run_rows(name, kernel, x2d, *params):
    R, H = x2d.shape
    pad = (-R) % _ROW_BLOCK
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    grid = (x2d.shape[0] // _ROW_BLOCK,)
    in_specs = [pl.BlockSpec((_ROW_BLOCK, H), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec((H,), lambda i: (0,)) for _ in params]
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((_ROW_BLOCK, H), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=_use_interpret(),
        name=name,
    )(x2d, *params)
    return out[:R] if pad else out


# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """x [..., H] * rsqrt(mean(x^2)) * scale, fp32 stats."""
    shape = x.shape
    out = _run_rows("rms_norm", functools.partial(_rms_kernel, eps=eps),
                    x.reshape(-1, shape[-1]), scale)
    return out.reshape(shape)


def _rms_fwd(x, scale, eps):
    return rms_norm(x, scale, eps), (x, scale)


def _rms_bwd(eps, res, g):
    x, scale = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x32 * inv
    gs = g32 * scale.astype(jnp.float32)
    H = x.shape[-1]
    dx = inv * (gs - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum((g32 * xhat).reshape(-1, H), axis=0)
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    shape = x.shape
    out = _run_rows("layer_norm", functools.partial(_ln_kernel, eps=eps),
                    x.reshape(-1, shape[-1]), scale, bias)
    return out.reshape(shape)


def _ln_fwd(x, scale, bias, eps):
    return layer_norm(x, scale, bias, eps), (x, scale)


def _ln_bwd(eps, res, g):
    x, scale = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * inv
    gs = g32 * scale.astype(jnp.float32)
    H = x.shape[-1]
    dx = inv * (gs - jnp.mean(gs, axis=-1, keepdims=True)
                - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum((g32 * xhat).reshape(-1, H), axis=0)
    dbias = jnp.sum(g32.reshape(-1, H), axis=0)
    return dx.astype(x.dtype), dscale.astype(scale.dtype), dbias.astype(scale.dtype)


layer_norm.defvjp(_ln_fwd, _ln_bwd)
