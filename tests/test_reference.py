"""The plain f32 reference forward (models/reference.py) against the model's
own forward and against the served chunked-prefill logits.

The reference shares no code with TransformerLM, so agreement here is what
licenses chip_smoke.py to hold the served path at published widths to it.
All runs are f32 on CPU: the only differences are summation order and the
blockwise single-pass softmax, so the tolerances are f32-roundoff sized.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.models.reference import dense_reference_logits
from repro.serving import ContinuousBatchingEngine, Request

DENSE = ["h2o-danube-1.8b", "qwen3-8b", "gemma-2b", "llama2-7b",
         "chatglm-6b", "mistral-nemo-12b"]


def _setup(arch, seed=0):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    return cfg, model, params


def _rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("arch", DENSE)
def test_reference_matches_model_forward(arch):
    """Covers GQA/MQA/MHA, qk-norm, partial rotary, tied embeddings, plain
    and gated FFNs, and (h2o-danube, reduced window 32) sliding windows:
    the 80-token prompt runs past the window."""
    cfg, model, params = _setup(arch)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (80,), 0,
                                cfg.vocab_size)
    want = dense_reference_logits(cfg, params, tokens)
    got, _ = model.forward(params, tokens[None], remat=False)
    assert np.all(np.isfinite(np.asarray(want)))
    assert _rel_err(got[0], want) < 1e-5


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "h2o-danube-1.8b+ring",
                                  "qwen3-8b"])
def test_served_prefill_logits_match_reference(arch):
    """The engine's chunked prefill (3 chunks of 16, a ragged last chunk;
    past the reduced window of 32, and wrapping the ring for +ring) gives
    the reference's last-token logits."""
    cfg, model, params = _setup(arch)
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=128,
                                   chunk=16)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (41,), 0,
                                           cfg.vocab_size))
    got = eng.prefill_logits(prompt)
    want = dense_reference_logits(cfg, params, prompt)[-1]
    assert _rel_err(got, want) < 1e-5


def test_prefill_logits_leaves_engine_as_it_found_it():
    """A served run, the logits probe, and the same run again: identical
    tokens, and every slot free afterwards."""
    cfg, model, params = _setup("h2o-danube-1.8b")
    eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=128,
                                   chunk=16, decode_ticks=4).warmup()
    rng = np.random.default_rng(3)
    trace = [Request(prompt=rng.integers(0, cfg.vocab_size, n)
                     .astype(np.int32), max_new_tokens=m, rid=i)
             for i, (n, m) in enumerate([(20, 9), (37, 5), (8, 12)])]

    def tokens(rep):
        return {r["rid"]: r["tokens"] for r in rep["requests"]}

    before = tokens(eng.run(trace))
    eng.prefill_logits(trace[1].prompt)
    assert eng.pool.n_used == 0
    assert tokens(eng.run(trace)) == before
