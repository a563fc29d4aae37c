"""Persistent compilation cache placement for entry-point scripts.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/*.py``)
call ``enable_compile_cache()`` once, before their first compile.  Library
modules never call it at import, and tests never call it: the cache is a
property of the process that runs a program, not of the code it imports.

The directory is part of the cache key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself), otherwise the fixed ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
