"""JAX persistent compilation cache for the repository's entry points.

`enable_compile_cache()` is called at the start of a run (never at import):
it points JAX's persistent cache at `JAX_COMPILATION_CACHE_DIR` when that
variable is set, and otherwise at the fixed `<checkout>/.jax_cache`
directory (git-ignored).  The path is part of the cache's key, so it must
not move between runs for a compile to be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/runtime/compile_cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
