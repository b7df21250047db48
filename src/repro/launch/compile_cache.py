"""Where JAX keeps its persistent compilation cache for this repo's runs.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at the
repo root — a fixed path, because the path is part of the cache key and a
directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
