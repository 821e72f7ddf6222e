"""Pallas call: an array nobody has written, for a device loop to fill a
tile of rows at a time (``moe/layer.py::_over_live_rows``).

XLA has two ways to hand a loop its buffer and both cost what the loop was
written to save. ``jnp.zeros`` is a memset of the whole array (0.75 ms for
``[131072, 2304]`` bfloat16, where a quarter of its rows then takes 1.1 ms to
fill). ``lax.empty`` is an ``AllocateBuffer`` custom call, which writes
nothing, but the buffers it makes share no memory with any other: the
training cell's step asked for 17.8 GB of temporaries where zeros asks for
4.5 (compiled for a described v5e, PR 50). A Mosaic call whose body is empty
and whose result lives in HBM is an ordinary result to the compiler: no
write, and memory shared like any other's.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


@functools.partial(jax.jit, inline=True, static_argnames=("shapes",))
def _unwritten(*after, shapes):
    return pl.pallas_call(
        lambda *refs: None,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(after),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(shapes),
        out_shape=[jax.ShapeDtypeStruct(*s) for s in shapes],
        name="unwritten_rows")(*after)


def unwritten(shapes: Sequence[jax.ShapeDtypeStruct],
              after: Sequence[jax.Array]) -> Tuple[jax.Array, ...]:
    """Arrays of ``shapes`` holding whatever their memory held (zeros off
    the TPU: the CPU's heap may hold a NaN where a masked product reads).
    ``after``: what the loop that fills them reads. The call takes those
    arrays where they lie and touches none, so the compiler makes the
    arrays when the loop can start and not before (with nothing to wait
    for, four layers' arrays were made at the top of the step and lived
    through it: 11.2 GB of temporaries), and two loops over different
    operands never share one call (a shared array is copied whole for its
    second loop)."""
    if jax.default_backend() != "tpu":
        return tuple(jnp.zeros(s.shape, s.dtype) for s in shapes)
    return tuple(_unwritten(*after, shapes=tuple(
        (tuple(s.shape), jnp.dtype(s.dtype)) for s in shapes)))
