"""Every metric reader on a hand-made window, against its definition."""
from __future__ import annotations

import math
from types import SimpleNamespace as NS

import pytest

import harness
import roofline

PEAK = roofline.peaks("TPU v5 lite")


def _rec(due, submit, admit, times, budget=None):
    r = NS(due=due, submit=submit, admit=admit, times=times,
           tokens=list(range(len(times))))
    r.n = len(times)
    r.ok = budget is None or budget == r.n
    r.ttft = times[0] - due if r.ok else math.inf
    r.tpot = (times[-1] - times[0]) / (r.n - 1) if r.ok else math.inf
    return r


def _ctx(trace=True):
    recs = [_rec(0.0, 0.1, 0.2, [0.5, 0.6, 0.7]),          # ttft 0.5
            _rec(1.0, 1.0, 1.1, [1.2, 1.4, 1.6, 1.8]),     # ttft 0.2
            _rec(2.0, 2.1, 2.5, [3.0, 3.5]),               # ttft 1.0
            _rec(3.0, 3.0, 3.0, [3.1, 3.2], budget=5)]     # failed
    counters = {"dispatches": 22, "decode_steps": 10,
                "active_row_steps": 30}
    steps = [{"index": 0, "decode": [(300, 1, 8), (500, 5, 8)],
              "prefill": [(0, 256, False)], "ticks": 8},
             {"index": 1, "decode": [(300, 9, 1)], "prefill": [],
              "ticks": 1},
             {"index": 2, "decode": [(10, 1, 4)], "prefill": [],
              "ticks": 4}]                         # not in the trace
    tr = {"steps": [(0, 0, 1), (1, 1, 2)],
          "programs": {"jit_block": 0.09, "jit_prefill_chunks_batched": 0.06},
          "program_calls": {"jit_prefill_chunks_batched": 2},
          "window_s": 0.2, "busy_s": 0.18} if trace else None
    win = NS(records=recs, counters=counters, trace=tr, steps=steps,
             t0=0.0, t1=2.0)
    return harness.RunContext(harness.load_cell("danube.chat"), win, 12.5,
                              PEAK)


def read(name, ctx):
    return harness.load_reader(name).read(ctx)


def test_end_to_end_readers():
    ctx = _ctx()
    assert read("setup_s", ctx) == 12.5
    assert read("ttft_p50_s", ctx) == pytest.approx(0.5)  # 2nd of 4
    assert read("ttft_p90_s", ctx) == math.inf            # the failure
    assert read("tpot_p90_ms", ctx) == math.inf
    assert read("tokens_per_s", ctx) == 7 / 2.0           # stamps < 2.0 s


def test_counter_readers():
    ctx = _ctx()
    assert read("queue_wait_p90_s", ctx) == pytest.approx(0.4)
    assert read("batch_occupancy", ctx) == pytest.approx(75.0)
    assert read("dispatches_per_token.open", ctx) == 22 / 11


def test_trace_readers():
    ctx = _ctx()
    assert ctx.decode_work()[0] == 9                      # step 2 untraced
    assert read("decode_tick_ms.open", ctx) == pytest.approx(10.0)
    assert read("prefill_chunk_ms", ctx) == pytest.approx(30.0)
    assert read("device_idle_share.open", ctx) == pytest.approx(10.0)
    m = ctx.model
    # tick t: rows (300, 1) and (500, 5) at positions 300+t and 504+t,
    # then one tick of row (300, 9) at 308
    flops = sum(roofline.decode_tick(m, [300 + t, 504 + t])[0]
                for t in range(8)) + roofline.decode_tick(m, [308])[0]
    assert read("decode_mfu.open", ctx) == pytest.approx(
        100 * flops / (0.09 * PEAK["flops_per_s"]))
    least = sum(roofline.bound_seconds(*roofline.decode_tick(m, pos),
                                       PEAK)[0]
                for pos in [[300 + t, 504 + t] for t in range(8)] + [[308]])
    assert read("decode_roofline.open", ctx) == pytest.approx(
        100 * least / 0.09)
    pf = roofline.prefill_chunk(m, 0, 256, False)[0]
    assert read("step_mfu.open", ctx) == pytest.approx(
        100 * (flops + pf) / (0.2 * PEAK["flops_per_s"]))


def test_trace_readers_silent_without_trace():
    ctx = _ctx(trace=False)
    for name in ("decode_tick_ms.open", "decode_roofline.open",
                 "decode_mfu.open", "step_mfu.open", "prefill_chunk_ms",
                 "device_idle_share.open"):
        assert read(name, ctx) is None


@pytest.mark.parametrize("name", ["decode_tick_ms", "decode_roofline",
                                  "decode_mfu", "step_mfu",
                                  "device_idle_share", "dispatches_per_token"])
def test_split_metrics_share_their_base_reader(name):
    base = harness.reader_path(name)
    assert base.name == f"{name}.py"
    for suffix in (".open", ".batch"):
        assert harness.reader_path(name + suffix) == base


def test_metric_without_reader_refused():
    with pytest.raises(harness.BenchError):
        harness.reader_path("no_such_metric.open")
