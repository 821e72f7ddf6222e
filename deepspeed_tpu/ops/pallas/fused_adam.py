"""Fused Adam update as a Pallas TPU kernel.

TPU-native replacement for the reference's multi-tensor CUDA Adam
(``csrc/adam/multi_tensor_adam.cu`` behind ``ops/adam/fused_adam.py:18``): one
kernel updates param + both moments in a single pass over VMEM blocks, so the
four HBM streams (p, g, m, v) are each read/written exactly once. The
multi-tensor-apply machinery (kernel-arg chunking) is unnecessary — the caller
flattens the param pytree into one contiguous view per dtype and the grid
tiles it.

CPU fallback = interpret mode (the reference's CPU op-builder role).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK = 4096  # elements per grid step (multiple of the 8x128 vreg tile)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, scal_ref,
                 p_out, m_out, v_out, *, adam_w: bool):
    lr = scal_ref[0]
    b1 = scal_ref[1]
    b2 = scal_ref[2]
    eps = scal_ref[3]
    wd = scal_ref[4]
    bc1 = scal_ref[5]
    bc2 = scal_ref[6]

    p = p_ref[...]
    g = g_ref[...].astype(jnp.float32)
    if not adam_w:
        g = g + wd * p
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w:
        upd = upd + wd * p
    p_out[...] = p - lr * upd
    m_out[...] = m
    v_out[...] = v


def fused_adam_flat(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                    lr, step, betas: Tuple[float, float] = (0.9, 0.999),
                    eps: float = 1e-8, weight_decay: float = 0.0,
                    adam_w: bool = True, bias_correction: bool = True
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Adam on flat fp32 views. p/m/v: [N] fp32, g: [N] (any float dtype).
    Returns (new_p, new_m, new_v)."""
    N = p.shape[0]
    b1, b2 = betas
    sf = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - b1 ** sf if bias_correction else jnp.float32(1.0)
    bc2 = 1.0 - b2 ** sf if bias_correction else jnp.float32(1.0)
    scal = jnp.stack([jnp.asarray(lr, jnp.float32), jnp.float32(b1),
                      jnp.float32(b2), jnp.float32(eps),
                      jnp.float32(weight_decay),
                      jnp.asarray(bc1, jnp.float32),
                      jnp.asarray(bc2, jnp.float32)])

    pad = (-N) % _BLOCK
    if pad:
        p, g, m, v = (jnp.pad(x, (0, pad)) for x in (p, g, m, v))
    n_blocks = p.shape[0] // _BLOCK

    spec = pl.BlockSpec((_BLOCK,), lambda i: (i,))
    scal_spec = pl.BlockSpec((7,), lambda i: (0,))
    kernel = functools.partial(_adam_kernel, adam_w=adam_w)
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[spec, spec, spec, spec, scal_spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 3,
        interpret=_use_interpret(),
        name="fused_adam",
    )(p, g, m, v, scal)
    if pad:
        new_p, new_m, new_v = (x[:N] for x in (new_p, new_m, new_v))
    return new_p, new_m, new_v


def fused_adam_tree(params, grads, exp_avg, exp_avg_sq, lr, step,
                    betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                    adam_w=True, bias_correction=True):
    """Pytree front-end: flatten → one kernel launch → unflatten.

    The single flat launch is the multi-tensor-apply analog: small leaves
    share grid steps instead of paying one kernel launch each."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = jax.tree_util.tree_leaves(grads)
    m_leaves = jax.tree_util.tree_leaves(exp_avg)
    v_leaves = jax.tree_util.tree_leaves(exp_avg_sq)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]

    flat = lambda ls: jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in ls])
    new_p, new_m, new_v = fused_adam_flat(
        flat(leaves), flat(g_leaves), flat(m_leaves), flat(v_leaves),
        lr, step, betas, eps, weight_decay, adam_w, bias_correction)

    def unflat(x):
        out, off = [], 0
        for size, shape in zip(sizes, shapes):
            out.append(x[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return unflat(new_p), unflat(new_m), unflat(new_v)
