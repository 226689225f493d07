"""Where JAX keeps this checkout's persistent compilation cache.

Every entry point that turns the cache on calls ``enable_compile_cache``, and
nothing else in the repo sets ``jax_compilation_cache_dir``.  A cache only
hits when its directory stays put between runs, so the default is a fixed
path inside the checkout, never one built from a temp name, a pid or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    this leaves it alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    Programs that compile in under 0.2 s are cached too (JAX's default is
    1 s), which is what keeps the test lane's many small programs warm."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
