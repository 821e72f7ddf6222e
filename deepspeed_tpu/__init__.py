"""deepspeed_tpu — a TPU-native distributed training & inference framework.

A from-scratch JAX/XLA/Pallas framework with the capabilities of DeepSpeed
(reference: meefs/DeepSpeed v0.19.3; structural map in SURVEY.md). The public
surface mirrors the reference (``deepspeed/__init__.py:93 initialize``,
``:328 init_inference``, ``deepspeed.comm``), while the internals are idiomatic
SPMD over a named device mesh.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

__version__ = "0.1.0"
version = __version__

from deepspeed_tpu import comm  # noqa: E402
from deepspeed_tpu import telemetry  # noqa: E402
from deepspeed_tpu.accelerator import get_accelerator  # noqa: E402
from deepspeed_tpu.models.api import (  # noqa: E402
    ModelSpec,
    causal_lm_spec,
    spec_from_hf,
)
from deepspeed_tpu.runtime.config import DeepSpeedTPUConfig, load_config  # noqa: E402
from deepspeed_tpu.runtime.engine import DeepSpeedTPUEngine  # noqa: E402
from deepspeed_tpu.utils.logging import logger  # noqa: E402


def initialize(
    args: Any = None,
    model: Optional[ModelSpec] = None,
    optimizer: Any = None,
    model_parameters: Any = None,
    training_data: Any = None,
    lr_scheduler: Any = None,
    distributed_port: Optional[int] = None,
    mpu: Any = None,
    dist_init_required: Optional[bool] = None,
    collate_fn: Any = None,
    config: Any = None,
    mesh_param: Any = None,
    config_params: Any = None,
    mesh_manager: Any = None,
) -> Tuple[DeepSpeedTPUEngine, Any, Any, Any]:
    """Initialize the engine (reference ``deepspeed.initialize`` signature,
    ``deepspeed/__init__.py:93``). Returns (engine, optimizer, dataloader,
    lr_scheduler) like the reference.

    ``mesh_manager`` (a ``comm.mesh.MeshManager``) pins the engine to an
    explicitly-built mesh instead of the config-derived one — the elastic
    agent's engine factory uses it to build a world-M engine on a host
    that physically has N devices (``initialize_mesh(cfg,
    devices=jax.devices()[:M])``)."""
    config = config if config is not None else config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config
    if model is None:
        raise ValueError("deepspeed_tpu.initialize requires a ModelSpec via `model=`")

    engine = DeepSpeedTPUEngine(
        model=model, config=config, optimizer=optimizer, lr_scheduler=lr_scheduler,
        mesh_manager=mesh_manager)

    from deepspeed_tpu.monitor.monitor import MonitorMaster

    engine.monitor = MonitorMaster(engine.config)

    # fault tolerance (config "fault_tolerance"): arm the graceful-
    # preemption SIGTERM handler and restore the newest committed
    # checkpoint before handing the engine back
    ft = engine.config.fault_tolerance
    if ft.graceful_preemption and (ft.resume_dir or ft.auto_resume):
        engine.enable_preemption_handler()
    if ft.auto_resume:
        engine.maybe_auto_resume()

    dataloader = None
    if training_data is not None:
        dataloader = engine.deepspeed_io(training_data)
    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_distributed(dist_backend: str = "jax_ici", **kwargs) -> None:
    """Reference ``deepspeed.init_distributed`` analog."""
    comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model, params=None, config=None, **kwargs):
    """Reference ``deepspeed.init_inference`` (``deepspeed/__init__.py:328``)."""
    from deepspeed_tpu.inference.engine import init_inference as _ii

    return _ii(model, params=params, config=config, **kwargs)
