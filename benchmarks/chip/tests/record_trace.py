#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace_reduce.py`` reads: the tiny
batch test cell (``data/tiny``) served on the chip for a second, its last 30 ms
traced, saved as ``data/tiny.xplane.pb`` (under 1 MB).

    python3 benchmarks/chip/tests/record_trace.py    # on a TPU machine
"""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness          # noqa: E402
import trace_reduce     # noqa: E402


def main() -> int:
    cell = harness.load_cell("t.batch", HERE / "data" / "tiny")
    harness.start_jax(cell.chips)
    _, eng = harness.build_engine(cell, 5)
    harness.run_window(cell, eng, 5, 1.0, trace=True, trace_s=0.03)
    src = trace_reduce.find_trace(harness.TRACE_DIR)
    dst = HERE / "data" / "tiny.xplane.pb"
    shutil.copyfile(src, dst)
    print(dst, dst.stat().st_size)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
