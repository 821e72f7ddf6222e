"""Concrete TPU accelerator (parity: reference ``accelerator/cuda_accelerator.py``)."""
from __future__ import annotations

from typing import Dict, Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedTPUAccelerator


class TPU_Accelerator(DeepSpeedTPUAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "jax_ici"

    def is_synchronized_device(self) -> bool:
        # XLA executes a single ordered program per device; no user-visible streams.
        return True

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index: Optional[int] = None):
        import jax

        return jax.local_devices()[device_index or 0]

    def device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        import jax

        dev = jax.local_devices()[device_index or 0]
        # a TPU always reports allocator stats; zeros in their place would
        # read as "nothing ran on this chip"
        stats = dev.memory_stats()
        if not stats:
            raise RuntimeError(f"{dev} reports no memory_stats()")
        return {key: stats[key] for key in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}

    def op_builder_dir(self) -> str:
        return "deepspeed_tpu.ops.op_builder"

    def is_available(self) -> bool:
        import jax

        try:
            return any(d.platform == "tpu" for d in jax.devices())
        except RuntimeError:   # no backend at all -> not available
            return False

    def is_triton_supported(self) -> bool:
        return False
