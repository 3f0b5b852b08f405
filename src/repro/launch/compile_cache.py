"""JAX's persistent compilation cache for the entry points.

Call ``enable_compile_cache()`` from a ``main``, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and no
other directory is set. Otherwise the cache is the fixed ``.jax_cache``
directory at the repository root: the path is part of each entry's key,
so a directory that moves would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
