"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing. ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself). Otherwise the cache lives at a
fixed directory inside the checkout, ``<repo>/.jax_cache`` (gitignored):
the path is part of what a later run must find again, so it is never
derived from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (src/repro/launch -> repo root)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
