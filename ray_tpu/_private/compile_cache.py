"""Where JAX keeps compiled programs between processes and runs.

One rule for every process that compiles (workers, chip_smoke.py,
perfbench/): if ``JAX_COMPILATION_CACHE_DIR`` is set, that
directory is used and no other is set in code; otherwise the cache lives
at ``<checkout>/.jax_cache``, derived from where this package sits —
never a temp name, a pid or a time, because the path is part of the
cache key and a directory that moves never hits. The choice is written
back to the environment, so every process started from here (raylet,
workers, a benchmark's children) uses the same directory.
"""

from __future__ import annotations

import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Call before the first compile. Returns the directory in use."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ[CACHE_DIR_ENV] = path
        jax = sys.modules.get("jax")
        if jax is not None:
            # imported before we ran: the env var was read already
            jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries() -> int:
    """How many compiled programs the directory holds right now."""
    try:
        return sum(1 for name in os.listdir(configure_compile_cache())
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
