"""The benchmark's plain reference against the server repository's own f32
reference forward, at a reduced size on the CPU, with the query blocks
smaller than the sequence."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

REF = harness.load_reference("dense_decoder")


def _small(**kw):
    m = {"family": "dense", "vocab_size": 97, "d_model": 48, "n_layers": 3,
         "n_heads": 6, "n_kv_heads": 2, "head_dim": 8, "d_ff": 80,
         "window": None, "act": "silu", "gated_mlp": True, "qk_norm": False,
         "rope_base": 10000.0, "rotary_frac": 1.0, "norm_eps": 1e-5,
         "tie_embeddings": False, "compute_dtype": "float32",
         "param_dtype": "float32"}
    m.update(kw)
    return m


def _server_logits(m, params, toks):
    from repro.models.reference import dense_reference_logits
    return dense_reference_logits(_server_config(m), params, toks)


@pytest.mark.parametrize("kw", [
    {},
    {"qk_norm": True, "rope_base": 1e6},
    {"window": 20},
    {"window": 20, "qk_norm": True, "gated_mlp": False},
], ids=["gqa", "qk_norm", "window", "window_plain_mlp"])
def test_matches_server_reference(kw):
    m = _small(**kw)
    params = REF.make_params(m, jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (64,), 0, 97)
    want = np.asarray(_server_logits(m, params, toks))
    got = np.asarray(REF.logits(m, params,
                                REF.hidden(m, params, toks, q_block=16)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_padding_leaves_earlier_rows():
    m = _small(window=20)
    params = REF.make_params(m, jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (48,), 0, 97)
    padded = jnp.concatenate([toks, jnp.zeros((16,), toks.dtype)])
    a = REF.hidden(m, params, toks, q_block=16)
    b = REF.hidden(m, params, padded, q_block=16)[:48]
    # equal up to f32 rounding: the longer matmuls sum in another order
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_int8_control_is_near_but_not_equal():
    m = _small()
    params = REF.make_params(m, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(8), (32,), 0, 97)
    f32 = REF.logits(m, params, REF.hidden(m, params, toks, q_block=16))
    i8 = REF.logits(m, params, REF.hidden(m, params, toks, q_block=16,
                                          precision="int8"),
                    precision="int8")
    rel = float(jnp.linalg.norm(i8 - f32) / jnp.linalg.norm(f32))
    assert 1e-3 < rel < 0.1


def test_layout_matches_the_server():
    from repro.models.api import build_model
    m = _small(qk_norm=True)
    cfg = _server_config(m)
    want = jax.eval_shape(build_model(cfg).init_params, jax.random.PRNGKey(0))
    made = jax.eval_shape(lambda k: REF.make_params(m, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(made) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(made)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def _server_config(m):
    from repro.configs import get_config
    return get_config("qwen3-8b", reduced=True).replace(
        **{k: m[k] for k in harness.MODEL_KEYS_OF_CONFIG},
        head_dim=m["head_dim"])
