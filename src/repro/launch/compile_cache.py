"""Where JAX keeps its persistent compilation cache.

The cache directory is part of each entry's key, so it must not move
between runs: a path with a pid, a time or a temporary name never hits.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout's root (src/repro/launch/ -> three levels up)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is set here; otherwise the cache goes to
    ``<repo root>/.jax_cache``. Call it from an entry point's ``main()``,
    before the first compile — never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
