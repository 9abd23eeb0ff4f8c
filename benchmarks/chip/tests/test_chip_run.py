"""A whole run of the harness on the CPU at a tiny size (``data/tiny``):
the look for a chip is skipped, everything else runs. A sound run comes out
correct; with the timed path broken underneath, or the reference at fp8 in
the server's place (the control), it does not."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

import check
import harness
import run
from conftest import ROOT, TINY

SEED = 2**31 + 77


def _run(cell, trace=False, seconds=3.0):
    return run.run(cell, SEED, seconds, trace, root=TINY, require_tpu=False)


@pytest.mark.parametrize("cell", ["t.open", "t.batch"])
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 10 and out["failed"] == 0
    want = {m["name"] for m in harness.load_cell(cell, TINY).end_to_end}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "compared"
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reports_counter_metrics():
    out = _run("t.open", trace=True)
    assert out["correct"]
    # no device plane on the CPU: the trace's metrics stay silent
    assert set(out["metrics"]) == {"ttft_p90_s", "queue_wait_p90_s",
                                   "dispatches_per_token.open"}


def _break_decode(monkeypatch, fault):
    from repro.models.transformer import TransformerLM
    real = TransformerLM.decode_multi

    def broken(self, params, tok, cache, *a, **kw):
        toks, active, emitted, new = real(self, params, tok, cache, *a, **kw)
        if fault == "token":        # a token altered where it is produced
            toks = jnp.where(toks >= 0, (toks + 1) % self.cfg.vocab_size,
                             toks)
        elif fault == "state":      # the step returns its state unchanged
            new = cache
        return toks, active, emitted, new
    monkeypatch.setattr(TransformerLM, "decode_multi", broken)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_decode_is_not_correct(monkeypatch, fault):
    _break_decode(monkeypatch, fault)
    out = _run("t.batch")
    assert not out["correct"], out["compared"]


def test_fp8_control_fails_the_limits():
    cell = harness.load_cell("t.batch", TINY)
    harness.start_jax(1, require_tpu=False)
    params, eng = harness.build_engine(cell, SEED)
    win = harness.run_window(cell, eng, SEED, 3.0)
    del eng
    mix = cell.mix
    got = check.compare(harness.load_reference("dense_decoder"), cell.model,
                        params, check.sample(win.records, SEED,
                                             cell.check["sample_tokens"]),
                        mix["prompt"]["max"] + mix["output"]["max"],
                        mix["output"]["max"], controls=("fp8",))
    limits = cell.check["limits"]
    assert all(got[k] <= v for k, v in limits.items())
    assert all(got["control_fp8_" + k] > v for k, v in limits.items())


def test_no_tpu_no_result(tmp_path):
    """Off a TPU the command exits non-zero and prints nothing on stdout,
    also from a directory that holds only the benchmark's files."""
    bare = tmp_path / "bare"
    shutil.copytree(harness.BENCH, bare / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for where in (ROOT, bare):
        p = subprocess.run(
            [sys.executable, "benchmarks/chip/run.py", "--workload",
             "danube.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=where, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0 and p.stdout == "", (where, p.stderr)
        assert "no TPU" in p.stderr
