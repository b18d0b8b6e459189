"""Where JAX keeps its persistent compilation cache.

A compile cache only hits when its directory stays put — the path is
part of the cache key — so there is one fixed place for it: the
directory ``JAX_COMPILATION_CACHE_DIR`` names, when it is set (JAX reads
that variable itself, and nothing is set here), otherwise ``.jax_cache``
at the root of the checkout. Entry points call `place_compile_cache`
before their first compile; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
