"""JAX's persistent compilation cache at a place that can be set from
outside.

Every pow2 capacity class of a join is its own program, so a cold
process spends much of its time compiling.  `enable_compile_cache` keeps
compiled programs on disk:

  * where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    the directory is left as it is;
  * otherwise the cache lives at `<checkout>/.jax_cache` (git-ignored).
    The path is fixed, never a temporary name, because it is part of
    what a later process looks up.

The minimum compile time for an entry is lowered to zero: most of these
programs compile in well under JAX's default of one second, and would
otherwise never be cached.  `persistent_cache_off` keeps a block out of
the cache, for a timed compile or one for a described, absent device.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@contextlib.contextmanager
def persistent_cache_off():
    """Neither read nor write the persistent cache inside the block."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
