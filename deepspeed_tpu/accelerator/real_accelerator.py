"""Accelerator selection (reference ``accelerator/real_accelerator.py:45``
``get_accelerator``): ``DS_ACCELERATOR`` env override, then auto-detect —
TPU when a TPU-class backend is live, CPU otherwise."""

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.utils.logging import logger

DS_ACCELERATOR_LIST = ["tpu", "cpu"]

_accelerator: Optional[DeepSpeedAccelerator] = None


def _detect() -> str:
    """"tpu" when jax reports a TPU device, "cpu" when it reports none. A
    backend that fails to initialise raises: it is not the CPU."""
    import jax
    return "tpu" if any(d.platform == "tpu" for d in jax.devices()) else "cpu"


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    name = os.environ.get("DS_ACCELERATOR")
    if name is not None:
        name = name.lower()
        if name not in DS_ACCELERATOR_LIST:
            raise ValueError(f"DS_ACCELERATOR={name!r} not supported; "
                             f"choose from {DS_ACCELERATOR_LIST}")
    else:
        name = _detect()
    if name == "tpu":
        from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
        _accelerator = TPU_Accelerator()
    else:
        from deepspeed_tpu.accelerator.cpu_accelerator import CPU_Accelerator
        _accelerator = CPU_Accelerator()
    logger.info(f"accelerator selected: {_accelerator._name} "
                f"({'env override' if os.environ.get('DS_ACCELERATOR') else 'auto-detected'})")
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    """(reference ``set_accelerator``) — install an explicit accelerator."""
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in DS_ACCELERATOR_LIST
