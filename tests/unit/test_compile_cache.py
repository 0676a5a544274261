"""``utils/jax_platform.enable_compile_cache``: the one place a process entry
point decides where JAX's persistent compilation cache lives."""

import os

import jax
import pytest

from deepspeed_tpu.utils import jax_platform

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.parametrize("placed", ["/somewhere/outside", None], ids=["variable-set", "variable-unset"])
def test_compile_cache_directory(monkeypatch, placed):
    """Variable set: nothing is set in code (jax has read the variable itself —
    here it was set after jax's import, so the config must stay untouched).
    Unset: one fixed path inside the checkout."""
    before = jax.config.jax_compilation_cache_dir
    if placed is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    try:
        where = jax_platform.enable_compile_cache()
        if placed is None:
            assert where == jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
        else:
            assert where == placed
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)  # tests compile what they test
