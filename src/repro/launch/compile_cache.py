"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start-up; nothing calls it at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
cache there and this sets no other.  Otherwise the cache goes to one fixed
directory in the checkout: the path is part of what the cache is keyed on,
so a temp, pid or time-based path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (gitignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
