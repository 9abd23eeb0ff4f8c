#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, on the chip, in one
process: for each seed, the window, then the widest and mean logit gaps of
the served tokens and of the controls (the reference at int8 and at fp8,
``check.py``) on the same sample. One JSON line per seed.

    python3 benchmarks/chip/calibrate.py --workload danube.chat \\
        --seeds 101,102,103 --seconds 10

Each limit in ``checks/<cell>.json`` lies above the largest served reading
over a dozen seeds or more and below the smallest control reading, nearer
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import check
import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.start_jax(cell.chips)
    ref = harness.load_reference(cell.config["reference"])
    mix = cell.mix
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        params, eng = harness.build_engine(cell, seed)
        win = harness.run_window(cell, eng, seed, args.seconds)
        del eng
        gc.collect()
        got = check.compare(ref, cell.model, params,
                            check.sample(win.records, seed,
                                         cell.check["sample_tokens"]),
                            mix["prompt"]["max"] + mix["output"]["max"],
                            mix["output"]["max"],
                            controls=("int8", "fp8"))
        del params
        gc.collect()
        print(json.dumps({"seed": seed, **got,
                          "errored": sum(r.errored for r in win.records),
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
