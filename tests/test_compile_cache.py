"""launch/compile_cache.py: JAX's persistent compilation cache goes to
``JAX_COMPILATION_CACHE_DIR`` when it is set and to ``<repo>/.jax_cache``
otherwise. Each case runs in a fresh interpreter, so the config this test
process uses is untouched."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(4.0)).block_until_ready()
"""


def _probe(env_dir, compile_=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO / "src")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    return out.stdout.split()[-2:]


def test_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    cache = tmp_path / "cache"
    repo_cache = REPO / ".jax_cache"
    before = set(repo_cache.iterdir()) if repo_cache.exists() else set()
    returned, configured = _probe(cache, compile_=True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir())
    after = set(repo_cache.iterdir()) if repo_cache.exists() else set()
    assert after == before


def test_default_is_the_fixed_repo_dir():
    returned, configured = _probe(None)
    assert returned == configured == str(REPO / ".jax_cache")
