"""Every file the harness finds by name loads, and BENCHMARK.json keeps to
the benchmark's contract."""
from __future__ import annotations

import json
import re

import pytest

import harness
import traffic

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10


def test_entries_and_names():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/")
        assert (harness.ROOT / c["file"]).is_file()
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(c["reduced"]) <= set(cfg["overrides"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + metrics]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for text in [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_and_loads(cell):
    c = harness.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.load_reader(m["name"]).read)
    assert harness.load_reference(c.config["reference"]).make_params
    assert c.check["limits"] and all(
        isinstance(v, (int, float)) for v in c.check["limits"].values())
    assert c.check["sample_tokens"] >= 1 and c.check["readings"]
    assert traffic.longest_request(c.mix) <= c.pool["max_len"]
    harness.server_config(c)        # the server runs the sizes stated


def test_every_file_is_used():
    used = {p["file"].split("/")[-1] for p in BENCH["configs"]}
    assert used == {p.name for p in (harness.BENCH / "configs").glob("*.json")}
    mixes = {w["traffic"] + ".json" for w in BENCH["workloads"]}
    assert mixes == {p.name for p in (harness.BENCH / "traffic").glob("*.json")}
    checks = {w + ".json" for w in CELLS}
    assert checks == {p.name for p in (harness.BENCH / "checks").glob("*.json")}
    readers = {harness.reader_path(m["name"]).name
               for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert readers == {p.name for p in (harness.BENCH / "metrics").glob("*.py")}
