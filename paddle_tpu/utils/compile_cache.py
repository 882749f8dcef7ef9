"""Where JAX's persistent compilation cache lives.

The cache directory is part of a cache entry's key, so a directory that
moves (a temp name, a pid, a timestamp) never hits. One rule, one place:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself, so
    no path is set in code. Whoever runs the program (a driver, a chip
    tool, an operator) places the cache from outside.
  * otherwise — ``<checkout>/.jax_cache``, a fixed git-ignored directory
    next to the ``paddle_tpu`` package.

Names on the device side. An executable fetched from the cache keeps the
metadata it was compiled with: by default JAX leaves source locations and
``jax.named_scope`` paths out of the key, so a program whose arithmetic did
not change would come back with the scopes (and line numbers) of whichever
checkout compiled it first, and a device trace would name its operations
by them. The key therefore includes the metadata, with this checkout's own
path removed from every source file, so that the same code in another
directory still hits.
"""
from __future__ import annotations

import os
import re

__all__ = ["ENV_CACHE_DIR", "enable_compile_cache"]

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first compile."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
