"""CPU tests of the chip benchmark: ``python -m pytest benchmarks/chip/tests``.

The harness's modules sit beside the tests' parent directory and the server
under ``src/``; both go on the path. Programs these tests compile go to a
temporary cache, not the checkout's."""
from __future__ import annotations

import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = Path(__file__).resolve().parent / "data" / "tiny"


@pytest.fixture(autouse=True)
def _cpu_cache(tmp_path_factory, monkeypatch):
    import harness
    monkeypatch.setattr(harness, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")
