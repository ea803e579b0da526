"""The one place that turns on JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/dryrun.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile; no module calls it at import. When ``JAX_COMPILATION_CACHE_DIR`` is
set, that directory is the cache and no other is set. Otherwise the cache
lives at ``<checkout>/.jax_cache`` (gitignored): a fixed path, never a temp
name, because the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` if set, else the
    fixed in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return the directory. Call before the process's first compile."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
