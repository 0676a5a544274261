"""Test harness.

TPU translation of the reference's ``tests/unit/common.py`` strategy (SURVEY.md §4):
instead of forking N torch.multiprocessing workers per test, we run single-controller
SPMD over a *virtual 8-device CPU mesh* (xla_force_host_platform_device_count), so
every distributed code path — ZeRO sharding, MoE all_to_all, Ulysses, pipeline
ppermute — executes real XLA collectives without TPU hardware.

The device count is an XLA flag read once, when JAX initializes its backend,
hence the top-of-conftest env mutation. Tests never use an accelerator: a chip
belongs to one process at a time, and the suite spawns many (launcher e2e,
gangs, autotuning experiments, CLI tools) — so the CPU platform is forced
through the environment, which those children inherit. The chip is exercised
by ``chip_smoke.py`` instead; what the chip's COMPILER accepts is checked here
without a chip in ``tests/unit/ops/test_tpu_compile.py``.
"""

import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# the variable is read when jax is first imported; a plugin may have done that
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest

from deepspeed_tpu.utils import groups


@pytest.fixture(autouse=True)
def reset_mesh():
    """Fresh topology per test (the reference tears down process groups per test)."""
    groups.destroy_mesh()
    yield
    groups.destroy_mesh()


@pytest.fixture
def mesh8():
    return groups.initialize_mesh(force=True)


def pytest_configure(config):
    config.addinivalue_line("markers", "world_size(n): mesh size used by the test")
    config.addinivalue_line("markers", "tpu_only: requires real TPU hardware")
    config.addinivalue_line("markers", "nightly: slow end-to-end convergence test")
