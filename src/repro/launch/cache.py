"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
alone; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(ignored by git). Called at the start of each entry point's ``main()``,
never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
