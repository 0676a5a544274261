"""Accelerator singleton detection.

Reference: ``accelerator/real_accelerator.py:51-192`` — env override via
``DS_ACCELERATOR``, otherwise probe. Here the probe asks JAX which backend owns the
default devices ('tpu' vs 'cpu').
"""

import os

from deepspeed_tpu.utils.logging import logger

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu"]

ds_accelerator = None


def _validate_accelerator(accel_name):
    if accel_name not in SUPPORTED_ACCELERATOR_LIST:
        raise ValueError(f"accelerator must be one of {SUPPORTED_ACCELERATOR_LIST}, got {accel_name!r}")


def is_current_accelerator_supported():
    return get_accelerator().device_name() in SUPPORTED_ACCELERATOR_LIST


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    accelerator_name = os.environ.get("DS_ACCELERATOR", None)
    if accelerator_name is not None:
        _validate_accelerator(accelerator_name)
    else:
        # a backend that cannot be reached raises here: answering "cpu" for a
        # TPU that failed to start would let a run succeed without the chip
        import jax
        accelerator_name = "tpu" if jax.default_backend() == "tpu" else "cpu"

    set_accelerator_by_name(accelerator_name)
    return ds_accelerator


def set_accelerator_by_name(accelerator_name):
    global ds_accelerator
    _validate_accelerator(accelerator_name)
    if accelerator_name == "tpu":
        from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
    else:
        from deepspeed_tpu.accelerator.cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
    logger.info(f"Setting ds_accelerator to {accelerator_name}")
    return ds_accelerator


def set_accelerator(accel_obj):
    """Install an externally provided accelerator (reference: real_accelerator.py:195)."""
    global ds_accelerator
    ds_accelerator = accel_obj
    return ds_accelerator
