"""Persistent XLA compilation cache for the repo's entry points."""
from __future__ import annotations

import os


def enable_compile_cache(checkout) -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.  Entry points call this once at start-up, never at import.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path, because a cache that moves between runs never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
