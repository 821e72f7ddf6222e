"""Accelerator abstraction — the device-portability seam.

Parity: reference ``accelerator/abstract_accelerator.py:10`` (``DeepSpeedAccelerator``
ABC with ~50 abstract methods). The TPU-native surface is smaller because XLA devices
are synchronized-by-construction (no user-visible streams/events — the escape hatch
the reference itself defines as ``is_synchronized_device``), and "building an op" is
Pallas-kernel registration rather than nvcc compilation.

Every subsystem in this framework goes through :func:`deepspeed_tpu.accelerator.
get_accelerator` rather than touching ``jax.devices()`` directly, exactly as every
reference file calls ``get_accelerator()`` instead of ``torch.cuda``.
"""
from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional


class DeepSpeedTPUAccelerator(abc.ABC):
    """Abstract device interface. Concrete: ``TPU_Accelerator``, ``CPU_Accelerator``."""

    def __init__(self):
        self._name: str = "undefined"
        self._communication_backend_name: str = "jax_ici"

    # --- device APIs (reference abstract_accelerator.py:35-61) ---
    @abc.abstractmethod
    def is_synchronized_device(self) -> bool:
        ...

    @abc.abstractmethod
    def device_name(self, device_index: Optional[int] = None) -> str:
        ...

    @abc.abstractmethod
    def device(self, device_index: Optional[int] = None):
        ...

    @abc.abstractmethod
    def device_count(self) -> int:
        """Number of addressable (local) devices."""

    @abc.abstractmethod
    def global_device_count(self) -> int:
        """Number of devices across all hosts."""

    def set_device(self, device_index: int) -> None:
        # XLA manages placement; kept for API parity.
        return None

    def current_device(self) -> int:
        return 0

    def synchronize(self, device_index: Optional[int] = None) -> None:
        """Block the host until all outstanding work is done on every
        local device (or the one named)."""
        import jax

        devs = jax.local_devices()
        if device_index is not None:
            devs = [devs[device_index]]
        for fence in [jax.device_put(0.0, d) + 0 for d in devs]:
            fence.block_until_ready()

    # --- RNG (reference :63-90) — counter-based, functional on TPU ---
    def manual_seed(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    def initial_seed(self) -> int:
        return 0

    def default_generator(self, device_index: int = 0):
        return None

    # --- dtype support (reference :168-179) ---
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def is_triton_supported(self) -> bool:
        return False

    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.float8_e4m3fn,
                jnp.float8_e5m2]

    # --- memory stats (reference :115-166) ---
    @abc.abstractmethod
    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        ...

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return self.memory_stats(device_index).get("peak_bytes_in_use", 0)

    def reset_peak_memory_stats(self, device_index: Optional[int] = None) -> None:
        return None

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index: Optional[int] = None) -> int:
        stats = self.memory_stats(device_index)
        return max(0, stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0))

    # --- comm backend (reference :198) ---
    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # --- graphs (reference :206-217): under XLA, "graph capture" is jit ---
    def create_graph(self):
        return None

    def capture_to_graph(self, graph, **kwargs):
        import contextlib

        return contextlib.nullcontext()

    def replay_graph(self, graph) -> None:
        return None

    # --- tracing ranges (reference NVTX :186-192) ---
    def range_push(self, msg: str):
        import jax

        ctx = jax.profiler.TraceAnnotation(msg)
        ctx.__enter__()
        self._range_stack = getattr(self, "_range_stack", [])
        self._range_stack.append(ctx)

    def range_pop(self):
        stack = getattr(self, "_range_stack", [])
        if stack:
            stack.pop().__exit__(None, None, None)

    # --- pinned host memory (reference :255-261) ---
    def pin_memory(self, array, align_bytes: int = 1):
        return array  # numpy host arrays are DMA-able by the TPU runtime

    def is_pinned(self, array) -> bool:
        return True

    # --- op builder dispatch (reference :267-283) ---
    @abc.abstractmethod
    def op_builder_dir(self) -> str:
        ...

    def create_op_builder(self, class_name: str):
        builder_class = self.get_op_builder(class_name)
        return None if builder_class is None else builder_class()

    def get_op_builder(self, class_name: str):
        from deepspeed_tpu.ops import op_builder

        return getattr(op_builder, class_name, None)

    def build_extension(self):
        return None

    # --- platform predicates ---
    @abc.abstractmethod
    def is_available(self) -> bool:
        ...

    def device_kind(self) -> str:
        import jax

        devs = jax.local_devices()
        return devs[0].device_kind if devs else "unknown"

    def compile_backend(self) -> str:
        return "xla"

    def visible_devices_envs(self) -> List[str]:
        return ["JAX_PLATFORMS", "TPU_VISIBLE_DEVICES"]
