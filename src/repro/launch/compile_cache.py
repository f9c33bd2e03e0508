"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself), otherwise one fixed directory in the checkout, listed in
``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (src/repro/launch/ -> checkout root)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
