"""TPU accelerator (JAX backend).

Concrete accelerator for TPU devices, the analogue of the reference's
``accelerator/cuda_accelerator.py``. Device enumeration, memory stats, and
dtype support come from the JAX runtime; the communication backend name is
``"xla"`` (collectives over ICI/DCN compiled by XLA, replacing NCCL).
"""

import os

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):
    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"

    def _devices(self):
        import jax

        return jax.local_devices()

    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index=None):
        devs = self._devices()
        return devs[device_index or 0]

    def device_count(self):
        return len(self._devices())

    def global_device_count(self):
        import jax

        return jax.device_count()

    def current_device(self):
        return 0

    def is_available(self):
        try:
            return self.device_count() > 0
        except Exception:
            return False

    def synchronize(self, device_index=None):
        import jax
        import jax.numpy as jnp

        jax.block_until_ready(jnp.zeros(()))

    def memory_stats(self, device_index=None):
        try:
            return self.device(device_index).memory_stats() or {}
        except Exception:
            return {}

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        # fp16 compute is supported via XLA, though bf16 is native/preferred on TPU.
        return True

    def communication_backend_name(self):
        return self._communication_backend_name

    def create_op_builder(self, op_name):
        builder = self.get_op_builder(op_name)
        return builder() if builder else None

    def get_op_builder(self, op_name):
        from deepspeed_tpu.ops.op_builder import ALL_OPS

        return ALL_OPS.get(op_name)


class CPU_Accelerator(TPU_Accelerator):
    """CPU backend for cluster-free testing (virtual multi-device mesh via
    ``--xla_force_host_platform_device_count``); reference analogue:
    ``accelerator/cpu_accelerator.py`` + the gloo path in tests."""

    def __init__(self):
        super().__init__()
        self._name = "cpu"

    def _devices(self):
        import jax

        return jax.devices("cpu")

    def device_name(self, device_index=None):
        if device_index is None:
            return "cpu"
        return f"cpu:{device_index}"

    def is_bf16_supported(self):
        return True
