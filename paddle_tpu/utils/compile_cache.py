"""Where JAX's persistent compilation cache lives.

The cache directory is part of a cache entry's key, so a directory that
moves (a temp name, a pid, a timestamp) never hits. One rule, one place:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself, so
    no path is set in code. Whoever runs the program (a driver, a chip
    tool, an operator) places the cache from outside.
  * otherwise — ``<checkout>/.jax_cache``, a fixed git-ignored directory
    next to the ``paddle_tpu`` package.
"""
from __future__ import annotations

import os

__all__ = ["ENV_CACHE_DIR", "enable_compile_cache"]

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first compile."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
