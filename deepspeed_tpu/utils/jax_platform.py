"""Where a process keeps JAX's persistent compilation cache.

Every call of the chip tool starts a fresh machine, and compiling is a large
part of a cold run (half of ``chip_smoke.py``'s two minutes); the processes of
one command (and later commands, where the machine keeps the
directory) share compiled programs through the cache. A cache that moves never
hits, so the directory is either the one the environment names or ONE fixed
path inside the checkout.
"""

import os

# <checkout>/.jax_cache — git-ignored; never a tempfile/pid/time-derived name
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and return its
    directory. For process entry points (``chip_smoke.py``,
    ``bin/dstpu_replica``, ``bin/dstpu_bench``, ``examples/*.py``) — never
    called at library import, so tests compile what they test.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it into
    ``jax_compilation_cache_dir``; nothing is set in code. Unset: the cache goes
    to :data:`COMPILE_CACHE_DIR`."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
