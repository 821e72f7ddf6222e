"""Device mesh construction and parallel-topology state.

This layer replaces BOTH the reference's process-group factory
(``deepspeed/utils/groups.py``, 916 LoC of cached torch ProcessGroups) and its
``ProcessTopology`` named-axes rank grid (``runtime/pipe/topology.py:12``): on TPU a
single ``jax.sharding.Mesh`` with named axes *is* the topology, and "groups" are mesh
axis subsets addressed by name inside ``shard_map``/``pjit``.

Axis order is chosen so the most bandwidth-hungry axes are innermost on the ICI
torus: ``('pipe', 'data', 'expert', 'seq', 'tensor')``. On multi-slice/multi-host
deployments the outermost non-trivial axis rides DCN (hybrid mesh).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from deepspeed_tpu.utils.logging import logger

# Canonical axis names, outermost → innermost.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
ZSHARD_AXIS = "zshard"   # MiCS/hpZ replica-group subdivision of the DP width:
                         # ZeRO states shard over 'zshard' (the subgroup, inner
                         # on the ICI torus) and replicate over 'data' (the
                         # replica groups) — reference zero/mics.py:63 MiCS_Init
                         # partition groups / ZeRO++ hpZ (zero/config.py:309).
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"
DEFAULT_AXIS_ORDER: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, ZSHARD_AXIS,
                                       EXPERT_AXIS, SEQ_AXIS, TENSOR_AXIS)

# Dense-parameter gradients are averaged over every axis that replicates dense
# params: data, expert (experts-within-dp layout, reference groups.py:304) and seq
# (Ulysses ranks share parameters, reference sequence/layer.py).
DENSE_GRAD_REDUCE_AXES: Tuple[str, ...] = (DATA_AXIS, ZSHARD_AXIS, EXPERT_AXIS,
                                           SEQ_AXIS)
# Expert parameters are sharded over 'expert'; their grads reduce over the rest.
EXPERT_GRAD_REDUCE_AXES: Tuple[str, ...] = (DATA_AXIS, ZSHARD_AXIS, SEQ_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    pipe: int = 1
    data: int = -1  # -1 = absorb all remaining devices
    zshard: int = 1  # MiCS/hpZ partition size (1 = ZeRO shards over full 'data')
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {PIPE_AXIS: self.pipe, DATA_AXIS: self.data,
                 ZSHARD_AXIS: self.zshard, EXPERT_AXIS: self.expert,
                 SEQ_AXIS: self.seq, TENSOR_AXIS: self.tensor}
        fill_axes = [a for a, s in sizes.items() if s == -1]
        fixed = int(np.prod([s for s in sizes.values() if s != -1]))
        if n_devices % fixed != 0:
            raise ValueError(
                f"mesh shape {sizes} does not divide device count {n_devices}")
        remaining = n_devices // fixed
        if not fill_axes:
            if fixed != n_devices:
                raise ValueError(
                    f"mesh shape {sizes} (={fixed}) != device count {n_devices}")
        elif len(fill_axes) == 1:
            sizes[fill_axes[0]] = remaining
        else:
            raise ValueError("at most one mesh axis may be -1")
        return sizes


class MeshManager:
    """Holds the live Mesh plus derived parallel-dimension queries."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # --- sizes ---
    def axis_size(self, axis: str) -> int:
        return self.mesh.shape.get(axis, 1)

    @property
    def world_size(self) -> int:
        return self.mesh.size

    @property
    def dp_world_size(self) -> int:
        # "data parallel" in the reference's sense: number of dense-param replicas.
        return int(np.prod([self.axis_size(a) for a in
                            (DATA_AXIS, ZSHARD_AXIS, EXPERT_AXIS, SEQ_AXIS)]))

    @property
    def tp_world_size(self) -> int:
        return self.axis_size(TENSOR_AXIS)

    @property
    def pp_world_size(self) -> int:
        return self.axis_size(PIPE_AXIS)

    @property
    def ep_world_size(self) -> int:
        return self.axis_size(EXPERT_AXIS)

    @property
    def sp_world_size(self) -> int:
        return self.axis_size(SEQ_AXIS)

    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def __repr__(self) -> str:
        shape = {a: self.axis_size(a) for a in self.mesh.axis_names}
        return f"MeshManager(shape={shape})"


_GLOBAL_MESH: Optional[MeshManager] = None


def initialize_mesh(
    mesh_config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    allow_split_physical_axes: bool = False,
) -> MeshManager:
    """Create and install the global mesh.

    Uses ``jax.make_mesh`` so device ordering respects the physical ICI topology;
    for multi-slice (DCN-connected) deployments the outermost non-unit axis is laid
    out across slices by ``mesh_utils.create_hybrid_device_mesh`` when granule info
    is available.
    """
    global _GLOBAL_MESH
    mesh_config = mesh_config or MeshConfig()
    devices = list(devices) if devices is not None else jax.devices()
    sizes = mesh_config.resolve(len(devices))
    shape = tuple(sizes[a] for a in DEFAULT_AXIS_ORDER)
    mesh = jax.make_mesh(
        shape, DEFAULT_AXIS_ORDER, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(DEFAULT_AXIS_ORDER))
    _GLOBAL_MESH = MeshManager(mesh)
    logger.info(f"initialized device mesh: {_GLOBAL_MESH}")
    return _GLOBAL_MESH


def set_mesh(mesh: Mesh) -> MeshManager:
    global _GLOBAL_MESH
    _GLOBAL_MESH = MeshManager(mesh)
    return _GLOBAL_MESH


def get_mesh_manager() -> MeshManager:
    global _GLOBAL_MESH
    if _GLOBAL_MESH is None:
        initialize_mesh()
    return _GLOBAL_MESH


def get_mesh() -> Mesh:
    return get_mesh_manager().mesh


def maybe_mesh() -> Optional[Mesh]:
    """The process mesh if one can be (lazily) initialized, else None —
    THE probe idiom for layers that degrade gracefully to replicated
    execution (MoE dispatch, inference TP, AutoSP planning). The broad
    catch is deliberate and traced at debug level: mesh construction can
    fail for backend-specific reasons (no devices yet, incompatible jax
    build), and every caller treats "no mesh" as "run unsharded"."""
    try:
        return get_mesh_manager().mesh
    except Exception as e:
        logger.debug(f"mesh unavailable ({type(e).__name__}: {e}); "
                     "callers degrade to replicated execution")
        return None


def mesh_is_initialized() -> bool:
    return _GLOBAL_MESH is not None


def already_manual_axes() -> set:
    """Axes manualized by an ENCLOSING shard_map at trace time (e.g. the
    engine's compressed-collective step is manual over data/zshard; the
    pipeline over 'pipe') — a nested shard_map must not re-manualize
    them, and inside that context arrays are already per-shard on them."""
    am = jax.sharding.get_abstract_mesh()
    return {n for n, t in zip(am.axis_names, am.axis_types)
            if t == jax.sharding.AxisType.Manual}


def reset_mesh() -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = None
    for hook in _RESET_HOOKS:
        hook()


# callbacks run on reset_mesh() — lets mesh-keyed caches elsewhere (e.g.
# moe.layer._SHARDED_FN_CACHE's compiled shard_map programs) die with the
# mesh instead of leaking across re-initializations
_RESET_HOOKS = []


def on_reset_mesh(hook) -> None:
    _RESET_HOOKS.append(hook)
