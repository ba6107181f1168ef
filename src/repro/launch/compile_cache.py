"""Where JAX keeps its persistent compilation cache.

Entry points (``serve.main``, ``train.main``, ``chip_smoke.py``, the
benchmark runner) call ``enable_compile_cache()`` once before they compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, because the directory is part of what a later run must find
again (and listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
