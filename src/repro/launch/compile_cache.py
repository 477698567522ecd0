"""JAX's persistent compilation cache for the repo's entry points.

Called from ``main()`` of the launchers, the benchmark harness and
``chip_smoke.py`` -- never on import, so tests and library users keep
JAX's own defaults.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# <repo>/.jax_cache (listed in .gitignore).  A fixed path: the directory is
# part of what a later run must find again, so it never varies per run.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other path is set here; otherwise the cache is ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
