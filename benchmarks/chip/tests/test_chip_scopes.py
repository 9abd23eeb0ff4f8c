"""The scope and span reduction (``scopes.py``) and the readers built on it:
the protobuf metadata reader on the chip trace recorded before the program
had scopes (``data/tiny.xplane.pb``), the whole reduction on one recorded
after (``data/tiny_scoped.xplane.pb``, made by ``record_trace.py`` and
saved under that name), the arithmetic on hand-made events, and each
reader's silence where the program or the trace lacks what it reads."""
from __future__ import annotations

import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import harness
import roofline
import scopes
import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
UNSCOPED_TRACE = DATA / "tiny.xplane.pb"
SCOPED_TRACE = DATA / "tiny_scoped.xplane.pb"
PEAK = roofline.peaks("TPU v5 lite")
READERS = ("decode_attention_ms", "decode_attention_roofline",
           "decode_matmul_roofline", "decode_unscoped_ms", "engine_idle_ms")


def read(name, ctx):
    return harness.load_reader(name).read(ctx)


def test_metadata_reader_finds_the_ops_paths():
    paths = scopes.op_paths(UNSCOPED_TRACE)
    assert list(paths) == ["/device:TPU:0"]
    (module,) = tr.read_planes(UNSCOPED_TRACE)["devices"]["/device:TPU:0"][
        "modules"]
    pid = int(module[0].rsplit("(", 1)[1].rstrip(")"))
    hits = [v for (p, op), v in paths["/device:TPU:0"].items()
            if op.startswith("%fusion.167 ")]
    assert len(hits) == 1 and hits[0].startswith("jit(block)/")
    assert "dot_general" in [c.split(":")[0] for c in hits[0].split("/")]
    assert all(p == pid for p, _ in paths["/device:TPU:0"])


def test_scope_of_matches_whole_components():
    assert scopes.scope_of("jit(block)/while/body/attention/dot_general:") \
        == "attention"
    assert scopes.scope_of("jit(block)/mlp/x/attention/add:") == "attention"
    assert scopes.scope_of("jit(block)/decode_attention/dot:") == \
        scopes.UNSCOPED
    assert scopes.scope_of("jit(block)/while/body/sample:") == "sample"
    assert scopes.scope_of("") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED


US = 1000.0        # ns


def _raw():
    # one jit_block(7) run holding a loop (unscoped) with three body ops,
    # a prefill program op that no decode bucket may take, and idle gaps:
    # 100..200 us inside serve.sync, 400..405 us (a seam), 600..700 us
    # inside serve.retire, 900..1000 us outside any serve.* span
    host = [("engine.step", 0.0, 1000 * US, {"step": 0}),
            ("serve.decode", 10 * US, 90 * US, {}),
            ("serve.sync", 90 * US, 590 * US, {}),
            ("serve.retire", 590 * US, 890 * US, {})]
    modules = [("jit_block(7)", 0.0, 600 * US),
               ("jit_prefill_chunks_batched(8)", 700 * US, 900 * US)]
    ops = [("%while.1 = w", 0.0, 100 * US),
           ("%fusion.2 = a", 0.0, 60 * US),
           ("%dot.3 = b", 60 * US, 100 * US),
           ("%copy.4 = c", 200 * US, 400 * US),
           ("%fusion.5 = d", 405 * US, 600 * US),
           ("%fusion.6 = e", 700 * US, 900 * US)]
    paths = {"/device:TPU:0": {
        (7, "%fusion.2 = a"): "jit(block)/while/body/attention/mul:",
        (7, "%dot.3 = b"): "jit(block)/while/body/mlp/dot_general:",
        (7, "%fusion.5 = d"): "jit(block)/while/body/mlp/add:",
        (8, "%fusion.6 = e"): "jit(prefill)/attention/dot_general:"}}
    return ({"devices": {"/device:TPU:0": {"modules": modules,
                                           "ops": ops}},
             "host": host}, paths)


def test_reduce_arithmetic():
    raw, paths = _raw()
    r = scopes.reduce(raw, paths, (0.0, 1000 * US))
    assert r["scoped"]
    assert r["scopes"] == pytest.approx({"attention": 60e-6,
                                         "mlp": 235e-6, "unscoped": 200e-6})
    assert sum(r["scopes"].values()) == pytest.approx(
        tr.reduce(raw)["programs"]["jit_block"] - 105e-6)   # its idle
    assert r["span_idle"] == pytest.approx({"serve.sync": 100e-6,
                                            "serve.retire": 100e-6})
    assert r["decode_spans"] == 1
    # an op of another program with the same name is not this one's
    paths["/device:TPU:0"][(8, "%fusion.2 = a")] = \
        paths["/device:TPU:0"].pop((7, "%fusion.2 = a"))
    assert scopes.reduce(raw, paths, (0.0, 1000 * US))["scopes"][
        "unscoped"] == pytest.approx(260e-6)


def _ctx(trace, steps=None):
    steps = steps if steps is not None else [
        {"index": 0, "decode": [(300, 1, 8), (500, 5, 8)],
         "prefill": [], "ticks": 8},
        {"index": 1, "decode": [(300, 9, 1)], "prefill": [], "ticks": 1}]
    win = NS(records=[], counters={}, trace=trace, steps=steps,
             t0=0.0, t1=1.0)
    return harness.RunContext(harness.load_cell("qwen3.longctx"), win, 1.0,
                              PEAK)


def test_readers_silent_without_a_trace():
    ctx = _ctx(None)
    for name in READERS:
        for suffix in (".open", ".batch"):
            assert read(name + suffix, ctx) is None


def test_readers_arithmetic(monkeypatch):
    ctx = _ctx({"steps": [(0, 0, 1), (1, 1, 2)], "interval": (0, 2)})
    fake = {"scopes": {"attention": 0.018, "qkv": 0.01, "attn_out": 0.004,
                       "mlp": 0.03, "lm_head": 0.002, "sample": 0.001,
                       "unscoped": 0.045},
            "scoped": True, "span_idle": {"serve.sync": 0.002,
                                          "serve.retire": 0.004},
            "decode_spans": 2}
    monkeypatch.setattr(scopes, "of", lambda c: fake)
    m = ctx.model
    ticks = [[300 + t, 504 + t] for t in range(8)] + [[308]]
    assert list(scopes.tick_rows(ctx)) == ticks
    assert read("decode_attention_ms.batch", ctx) == pytest.approx(2.0)
    assert read("decode_unscoped_ms.batch", ctx) == pytest.approx(5.0)
    assert read("engine_idle_ms.batch", ctx) == pytest.approx(3.0)
    att = 0.0
    for pos in ticks:
        fl = sum(roofline.attention(m, [p])[0] for p in pos)
        by = sum(roofline.attention(m, [p])[1] for p in pos)
        att += roofline.bound_seconds(fl, by, PEAK)[0]
    assert read("decode_attention_roofline.batch", ctx) == pytest.approx(
        100 * att / 0.018)
    mm = sum(roofline.bound_seconds(
        roofline.projection(m, len(pos))[0] + roofline.head(m, len(pos))[0],
        roofline.projection(m, len(pos))[1] + roofline.head(m, len(pos))[1],
        PEAK)[0] for pos in ticks)
    assert read("decode_matmul_roofline.batch", ctx) == pytest.approx(
        100 * mm / 0.046)
    # a program with no scopes, or a trace with no serve.* spans, reads
    # nothing, as the parent of the scopes does
    fake.update(scoped=False, span_idle={}, decode_spans=0)
    for name in READERS:
        assert read(name + ".batch", ctx) is None


def _traced_ctx(monkeypatch, tmp_path, trace_file):
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    shutil.copyfile(trace_file, run_dir / "vm.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    t = tr.reduce(tr.read_planes(trace_file))
    steps = [{"index": i, "decode": [(40, 1, 4)], "prefill": [],
              "ticks": 4} for i, _, _ in t["steps"]]
    return _ctx(t, steps), t


def test_trace_of_a_program_without_scopes_reads_nothing(monkeypatch,
                                                           tmp_path):
    ctx, _ = _traced_ctx(monkeypatch, tmp_path, UNSCOPED_TRACE)
    r = scopes.of(ctx)
    assert r is not None and not r["scoped"] and not r["span_idle"]
    for name in READERS:
        assert read(name + ".batch", ctx) is None


def test_recorded_scoped_trace(monkeypatch, tmp_path):
    assert SCOPED_TRACE.is_file() and SCOPED_TRACE.stat().st_size < 1 << 20
    ctx, t = _traced_ctx(monkeypatch, tmp_path, SCOPED_TRACE)
    r = scopes.of(ctx)
    assert r["scoped"]
    # every scope of the decode program ran, and the buckets with the
    # unscoped one account for the program's device time
    assert set(scopes.SCOPES) | {scopes.UNSCOPED} == set(r["scopes"])
    total = sum(r["scopes"].values())
    assert total == pytest.approx(t["programs"]["jit_block"], rel=0.01)
    # idle time charged to the engine's spans is idle time of the interval
    assert r["decode_spans"] >= 1 and r["span_idle"]
    assert set(r["span_idle"]) <= {"serve.admit", "serve.prefill",
                                   "serve.first_token", "serve.decode",
                                   "serve.sync", "serve.retire"}
    assert sum(r["span_idle"].values()) <= t["window_s"] - t["busy_s"]
    ks = scopes.span_args(SCOPED_TRACE, "serve.decode", "k", t["interval"])
    assert len(ks) == r["decode_spans"] and all(k >= 1 for k in ks)
    for name in READERS:
        assert read(name + ".batch", ctx) > 0
