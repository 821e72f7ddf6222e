"""Random weights from ``--seed``, made on the device in one jitted call,
in the type they are served in.

``T.init_params`` builds the whole tree in float32 first (14.5 GB at depth
16), which does not fit beside anything; this makes each leaf directly in
the compute type, one layer of a stacked leaf at a time. Only the tree's
*shapes* come from the program (``jax.eval_shape`` of its initialiser).
Unlike the program's initialiser, biases and norm offsets are not zero
and norm scales are not exactly one (1 + 0.1 x noise): with zeros the
bias branches of the model could be dropped unnoticed by the comparison
against the reference. The noise is uniform with the initialiser's
standard deviation, not normal: neither speed nor the comparison depends
on the law, and uniform needs no inverse error function over 3.7 billion
elements.
"""
from __future__ import annotations

import math


def init_on_device(cfg, seed: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    dt = cfg.compute_dtype
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(path, shape, key):
        name = jax.tree_util.keystr(path)
        noise = jax.random.uniform(key, shape, jnp.float32,
                                   -math.sqrt(3.0), math.sqrt(3.0))
        if "scale" in name or "norm" in name and "bias" not in name:
            return (1.0 + 0.1 * noise).astype(dt)
        return (cfg.init_std * noise).astype(dt)

    def stacked(path, shape, key):
        # a [L, ...] leaf of the layer stack: one layer at a time, so the
        # float32 temporaries are a layer's, not the model's
        if "blocks" in jax.tree_util.keystr(path) and len(shape) >= 2:
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(lambda k: make(path, shape[1:], k), keys)
        return make(path, shape, key)

    # the seed is an argument, not a constant of the program: one program
    # for every seed, so only the first run of a checkout compiles it (as a
    # constant, every new seed compiled its own, 2.5-6 s of each set-up)
    @jax.jit
    def init(seed):
        key = jax.random.key(seed, impl="rbg")
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            stacked(path, leaf.shape, keys[i])
            for i, (path, leaf) in enumerate(leaves)])

    return init(jnp.uint32(seed % 2 ** 32))
