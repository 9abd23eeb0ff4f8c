"""Trace reduction: the interval arithmetic on hand-made events, and the
whole reduction on a trace recorded on the chip (``data/tiny.xplane.pb``,
made by ``record_trace.py``)."""
from __future__ import annotations

from pathlib import Path

import pytest

import trace_reduce as tr

RECORDED = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def test_union_merges_and_sorts():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)]) == [
        (0, 3), (5, 9)]


def test_clip_and_gaps():
    busy = tr.union(tr.clip([(0, 2), (3, 5), (8, 12)], 1, 10))
    assert busy == [(1, 2), (3, 5), (8, 10)]
    assert tr.gaps(busy, 1, 10) == [(2, 3), (5, 8)]
    assert tr.gaps([], 0, 4) == [(0, 4)]
    assert tr.gaps([(0, 4)], 0, 4) == []


def test_program_name():
    assert tr.program_name("jit_block(123)") == "jit_block"
    assert tr.program_name("jit_prefill_chunks_batched") == \
        "jit_prefill_chunks_batched"


US = 1000.0        # ns


def _raw():
    # host: two engine steps; device: a prefill program (two ops with a 2 us
    # seam between them) and a decode program (a loop holding two ops); idle
    # 100..200 us (in step 0, under a dispatch event) and 300..400 us (in
    # harness.wait)
    host = [("engine.step", 0.0, 250 * US, {"step": 0}),
            ("PjitFunction(block)", 90 * US, 210 * US, {}),
            ("harness.wait", 250 * US, 400 * US, {}),
            ("engine.step", 400 * US, 1000 * US, {"step": 1})]
    modules = [("jit_prefill_chunks_batched(7)", 0.0, 100 * US),
               ("jit_block(9)", 200 * US, 300 * US),
               ("jit_block(9)", 400 * US, 1100 * US)]
    ops = [("%fusion.1 = bf16[4] fusion(...)", 0.0, 49 * US),
           ("%fusion.5 = bf16[4] fusion(...)", 51 * US, 100 * US),
           ("%convert.2 = bf16[8] convert(...)", 200 * US, 300 * US),
           ("%while.3 = (s32[]) while(...)", 400 * US, 1100 * US),
           ("%convert.2 = bf16[8] convert(...)", 400 * US, 700 * US),
           ("%dot.4 = f32[8] dot(...)", 702 * US, 1100 * US)]
    return {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops}},
            "host": host}


def test_reduce_arithmetic():
    r = tr.reduce(_raw())
    assert r["interval"] == (0.0, 1000 * US)
    assert r["window_s"] == pytest.approx(1e-3)
    assert r["busy_s"] == pytest.approx(798e-6)   # 98 + 100 + 600
    assert r["programs"] == pytest.approx(
        {"jit_prefill_chunks_batched": 100e-6, "jit_block": 700e-6})
    assert r["program_calls"] == {"jit_prefill_chunks_batched": 1,
                                  "jit_block": 2}
    assert [s[0] for s in r["steps"]] == [0, 1]
    # self time: the loop's 700 us less its two body ops; the last op runs
    # past the interval and still counts whole, as one op of one program
    assert dict(r["device_ops"]) == pytest.approx({
        "jit_block/convert.2": 400e-6, "jit_block/dot.4": 398e-6,
        "jit_prefill_chunks_batched/fusion.1": 49e-6,
        "jit_prefill_chunks_batched/fusion.5": 49e-6,
        "jit_block/while.3": 2e-6})
    assert dict(r["idle_gaps"]) == pytest.approx({
        "engine.step/PjitFunction(block)": 100e-6,
        "harness.wait": 100e-6, "seams between ops": 2e-6})


def test_self_times_nest():
    got = tr.self_times([("loop", 0.0, 10.0), ("a", 1.0, 3.0),
                         ("b", 4.0, 9.0), ("c", 5.0, 6.0), ("d", 12.0, 13.0)])
    assert sorted(got) == [("a", 1.0, 2.0), ("b", 4.0, 4.0),
                           ("c", 5.0, 1.0), ("d", 12.0, 1.0),
                           ("loop", 0.0, 3.0)]


def test_op_name():
    assert tr.op_name("%fusion.168 = s32[3]{0} fusion(s32[4,3] %x)") == \
        "fusion.168"


def test_reduce_needs_device_and_steps():
    raw = _raw()
    assert tr.reduce(dict(raw, devices={})) is None
    assert tr.reduce(dict(raw, host=[])) is None


def test_recorded_chip_trace():
    assert RECORDED.is_file() and RECORDED.stat().st_size < 1 << 20
    raw = tr.read_planes(RECORDED)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    r = tr.reduce(raw)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["programs"].get("jit_block", 0) > 0
    assert sum(r["programs"].values()) <= r["window_s"] * 1.0001
    assert r["steps"] and r["program_calls"].get("jit_block", 0) >= 1
    assert r["device_ops"] and r["idle_gaps"]
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-9
