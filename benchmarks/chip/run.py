#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose TPU chips this process may
take. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number the check compared, with
its limit. The same numbers end standard error. Off a TPU, with fewer chips
than the cell asks for, or with a file missing, it exits 2 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse         # noqa: E402
import gc               # noqa: E402
import json             # noqa: E402
import math             # noqa: E402
import sys              # noqa: E402

import harness          # noqa: E402
from harness import BenchError, log   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        log(f"run.py: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root=harness.ROOT, require_tpu: bool = True) -> dict:
    """One run; ``root`` and ``require_tpu`` let a CPU test drive the rest
    of a run at a tiny size."""
    import check
    import roofline
    cell = harness.load_cell(workload, root)
    device = harness.start_jax(cell.chips, require_tpu=require_tpu)
    peak = roofline.peaks(device["kind"]) if require_tpu else {
        "flops_per_s": math.nan, "hbm_bytes_per_s": math.nan}
    compiles = harness.CompileCount()
    params, eng = harness.build_engine(cell, seed)
    setup_s = time.perf_counter() - T_START
    win = harness.run_window(cell, eng, seed, seconds, trace=trace,
                             compile_count=compiles)
    device["memory_peak_bytes"] = harness.memory_peak(cell.chips)
    ctx = harness.RunContext(cell, win, setup_s, peak)
    metrics = harness.read_metrics(
        ctx, cell.per_layer if trace else cell.end_to_end)
    attempted = len(win.records)
    failed = sum(1 for r in win.records if not r.ok and r.state.t_done
                 is not None)
    errored = sum(1 for r in win.records if r.errored)
    lags = sorted(win.lags) or [0.0]
    log(f"window: {seconds} s, {attempted} requests, {failed} failed, "
        f"{errored} errored; {win.counters}")
    log(f"submit lag behind schedule: p50 {harness.pct(lags, .5):.6f} s, "
        f"max {lags[-1]:.6f} s")
    log(f"compiles in the window: {win.compiles}")
    log(f"set-up: {setup_s:.3f} s")
    out = {"correct": False, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace and win.trace:
        t = win.trace
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        out["breakdown"] = {"device_ops": [list(x) for x in t["device_ops"]],
                            "idle_gaps": [list(x) for x in t["idle_gaps"]]}
        log("device programs (s):", json.dumps(t["programs"]))
        log("program calls:", json.dumps(t["program_calls"]))
        ticks, _, _, by_memory = ctx.decode_work()
        log(f"decode roofline: {by_memory} of {ticks} traced ticks bound by "
            "memory, the rest by compute")
    del eng
    gc.collect()            # the server's cache goes before the reference

    t0 = time.perf_counter()
    picked = check.sample(win.records, seed, cell.check["sample_tokens"])
    ref = harness.load_reference(cell.config["reference"])
    mix = cell.mix
    got = check.compare(ref, cell.model, params, picked,
                        mix["prompt"]["max"] + mix["output"]["max"],
                        mix["output"]["max"])
    log(f"check: {got['requests']} requests, {got['tokens']} served tokens, "
        f"{time.perf_counter() - t0:.3f} s")
    compared = {k: {"value": got[k], "limit": lim}
                for k, lim in cell.check["limits"].items()}
    compared["errored_requests"] = {"value": errored, "limit": 0}
    out["correct"] = bool(picked) and all(
        v["value"] <= v["limit"] for v in compared.values())
    out["compared"] = compared
    for name, v in out["compared"].items():
        log(f"{name} {v['value']} limit {v['limit']}")
    return out


if __name__ == "__main__":
    sys.exit(main())
