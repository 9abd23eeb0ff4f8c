#!/usr/bin/env python3
"""Find an open-loop cell's knee: the window at each of several fixed rates
and seeds, in one process on the chip, one JSON line per window and a last
line with the knee.

    python3 benchmarks/chip/sweep.py --workload danube.chat \\
        --rates 1.6,1.8,2.0 --seeds 1,2 --seconds 51

The knee is the highest rate at which the queue does not grow across the
window, on every seed, with every lower rate holding too. A window holds
when the mean backlog (requests due and not yet given their first token) of
its last third is at most one request above its first third's, and the
drain after the window ends within ``DRAIN_S``. The cell's traffic file then
takes 0.8 x the knee as its fixed rate.
"""
from __future__ import annotations

import argparse
import json
import math

import numpy as np

import harness

DRAIN_S = 5.0


def _first(r) -> float:
    return r.times[0] if r.ok else math.inf


def thirds(records, t0: float, seconds: float) -> list[float]:
    """Median time to first token of the requests due in each third."""
    out = []
    for k in range(3):
        lo, hi = t0 + k * seconds / 3, t0 + (k + 1) * seconds / 3
        out.append(harness.pct([r.ttft for r in records if lo <= r.due < hi],
                               0.5))
    return out


def backlog_thirds(records, t0: float, seconds: float) -> list[float]:
    """Mean backlog over each third of the window, sampled every 50 ms."""
    due = np.array([r.due for r in records])
    first = np.array([_first(r) for r in records])
    out = []
    for k in range(3):
        ts = t0 + np.arange(k * seconds / 3, (k + 1) * seconds / 3, 0.05)
        out.append(float(np.mean([((due <= t) & (first > t)).sum()
                                  for t in ts])))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.mix["loop"] != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    seeds = [int(s) for s in args.seeds.split(",")]
    harness.start_jax(cell.chips)
    _, eng = harness.build_engine(cell, seeds[0])
    held = {}
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        for seed in seeds:
            win = harness.run_window(cell, eng, seed, args.seconds, mix=mix)
            recs = win.records
            back = backlog_thirds(recs, win.t0, args.seconds)
            drain = win.t_end - win.t1
            ok = back[2] <= back[0] + 1.0 and drain <= DRAIN_S
            held[rate] = held.get(rate, True) and ok
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "requests": len(recs),
                "failed": sum(not r.ok for r in recs), "held": ok,
                "backlog_by_third": back, "drain_s": drain,
                "ttft_p50_s": harness.pct([r.ttft for r in recs], 0.5),
                "ttft_p90_s": harness.pct([r.ttft for r in recs], 0.9),
                "tpot_p90_ms": 1e3 * harness.pct([r.tpot for r in recs],
                                                 0.9),
                "ttft_p50_by_third_s": thirds(recs, win.t0, args.seconds),
                "tokens_per_s": sum(r.n for r in recs) / (win.t_end
                                                          - win.t0),
                "submit_lag_max_s": max(win.lags) if win.lags else math.nan,
            }), flush=True)
    knee = None
    for rate in sorted(held):
        if not held[rate]:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee, "held": held,
                      "rate_per_s": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
