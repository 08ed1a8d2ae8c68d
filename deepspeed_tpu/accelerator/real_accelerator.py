"""Accelerator singleton detection.

Analogue of the reference ``accelerator/real_accelerator.py``
(``get_accelerator`` :51, ``DS_ACCELERATOR`` env override :59,
``set_accelerator`` :264). Detection order: explicit override env
``DS_ACCELERATOR`` ∈ {tpu, cpu} → ``device.on_tpu()``.
"""

import os

from deepspeed_tpu.utils.logging import logger

_accelerator = None


def _detect():
    from deepspeed_tpu.accelerator.tpu_accelerator import CPU_Accelerator, TPU_Accelerator

    override = os.environ.get("DS_ACCELERATOR")
    if override is not None:
        if override == "cpu":
            return CPU_Accelerator()
        if override == "tpu":
            return TPU_Accelerator()
        raise ValueError(f"DS_ACCELERATOR={override} not supported (tpu|cpu)")
    # a backend that fails to initialise is an error, never the CPU accelerator
    from deepspeed_tpu.accelerator.device import on_tpu

    return TPU_Accelerator() if on_tpu() else CPU_Accelerator()


def get_accelerator():
    global _accelerator
    if _accelerator is None:
        _accelerator = _detect()
        logger.info(f"Setting ds_accelerator to {_accelerator._name}")
    return _accelerator


def set_accelerator(accel):
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported():
    return True
