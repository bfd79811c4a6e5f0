"""Where the persistent XLA compilation cache lives.

A cache entry's key includes the cache directory, so the directory must be
the same from one run to the next: it is never made from a temp name, a pid
or the time.  `JAX_COMPILATION_CACHE_DIR`, when set, wins and JAX reads it
itself; otherwise the cache sits at `<checkout>/.jax_cache` (git-ignored).
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
