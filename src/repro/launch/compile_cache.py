"""JAX's persistent compilation cache at one fixed place.

The cache key includes the directory, so a directory that moves from run to
run never hits. ``enable_compile_cache`` is called by every entry point that
compiles a served program (``launch/serve.py``, ``benchmarks/serving_bench.py``,
``chip_smoke.py``) before its first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  else is set;
* otherwise the cache goes to ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
