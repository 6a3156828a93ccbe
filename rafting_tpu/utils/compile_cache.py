"""Persistent XLA compile cache, placed from outside the process.

Every entry script (chip_smoke.py, benchmark/run.py, noderun,
__graft_entry__) calls :func:`enable_compile_cache` once before
its first jit.  A cold ``node_step`` at 100k groups compiles for about a
minute; without a persistent cache every process pays that again.

Placement rule: ``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache
lives — JAX reads the variable itself, so nothing is set in code.  Unset,
the cache goes to ONE fixed git-ignored directory inside the checkout: the
directory is part of the cache key, so a path built from a temp name, a
pid or a time would never hit.

Never called at package import: the test suite runs without a persistent
cache (an AOT compile for a described chip is written there but cannot be
read back without the chip — tests/test_tpu_compile.py).
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Cache every program: the default skips compiles under 1 s, and the
    # runtime's start-up is dozens of those.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
