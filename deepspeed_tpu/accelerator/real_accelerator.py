"""Singleton accelerator resolution.

Parity: reference ``accelerator/real_accelerator.py:51`` (``get_accelerator`` with
``DS_ACCELERATOR`` env override + auto-detection probing) and ``set_accelerator``
(:249) for injection. Detection here probes ``jax.devices()`` platforms instead of
installed torch vendor extensions.
"""
from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedTPUAccelerator

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu"]

_accelerator: Optional[DeepSpeedTPUAccelerator] = None


def _detect_name() -> str:
    override = os.environ.get("DSTPU_ACCELERATOR")
    if override:
        if override not in SUPPORTED_ACCELERATOR_LIST:
            raise ValueError(
                f"DSTPU_ACCELERATOR={override!r} is not one of {SUPPORTED_ACCELERATOR_LIST}"
            )
        return override
    import jax

    # ONE probe. A backend that cannot start (chip held by another process,
    # libtpu failing) raises out of jax.devices(): carrying on on the CPU
    # would hide the device from everything measured afterwards.
    platforms = {d.platform for d in jax.devices()}
    return "tpu" if "tpu" in platforms else "cpu"


def get_accelerator() -> DeepSpeedTPUAccelerator:
    global _accelerator
    if _accelerator is None:
        name = _detect_name()
        if name == "tpu":
            from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

            _accelerator = TPU_Accelerator()
        else:
            from deepspeed_tpu.accelerator.cpu_accelerator import CPU_Accelerator

            _accelerator = CPU_Accelerator()
    return _accelerator


def set_accelerator(accel: DeepSpeedTPUAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in SUPPORTED_ACCELERATOR_LIST
