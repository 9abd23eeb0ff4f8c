"""Multi-tick decode blocks (``TransformerLM.decode_multi`` + the engine's
adaptive tick horizon): seeded temperature>0 streams must be
*tick-horizon-invariant* (sampler keys are request-intrinsic — (seed,
serial, token index) — so the draw for token i cannot depend on how ticks
were blocked); on-device EOS/budget retirement must match the host's
replay; and the dispatch accounting must actually show the round-trip
collapse. The per-family greedy-equivalence sweep at decode_ticks 1 and 8
lives in the shared harness of ``test_serving_conformance.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.serving import ContinuousBatchingEngine, Request, poisson_trace

jax.config.update("jax_platform_name", "cpu")

TICK_HORIZONS = (1, 4, 8)


def _build(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def dense_model():
    return _build("llama2-7b")


def test_sampled_stream_invariant_across_tick_horizons(dense_model):
    """Seeded temperature>0 replay: the same (seed, trace) draws the same
    tokens at decode_ticks 1, 4, and 8. This is true *by construction* —
    the Gumbel key for a request's token i is fold_in(fold_in(seed_key,
    admission serial), i), none of which depends on the tick horizon — and
    this test proves the construction survives the scan."""
    cfg, model, params = dense_model
    trace = poisson_trace(n_requests=5, vocab_size=cfg.vocab_size,
                          prompt_len=(3, 18), max_new=(4, 10), seed=3)

    def run(ticks, seed=7):
        eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=64,
                                       chunk=8, temperature=0.8, seed=seed,
                                       decode_ticks=ticks)
        eng.warmup()
        rep = eng.run(list(trace))
        return {r["rid"]: r["tokens"] for r in rep["requests"]}

    streams = {t: run(t) for t in TICK_HORIZONS}
    assert streams[1] == streams[4] == streams[8]
    assert run(4, seed=9) != streams[4]      # a different seed differs


def test_on_device_eos_retires_mid_block_and_backfills(dense_model):
    """A row whose sampled token hits eos_id mid-block flips inactive on
    device (remaining ticks park its writes); the host replay retires it
    from the token block alone, and a queued request backfills the slot."""
    cfg, model, params = dense_model
    prompt = np.arange(5, dtype=np.int32)
    probe = ContinuousBatchingEngine(model, params, n_slots=1, max_len=64,
                                     chunk=8)
    free = probe.run([Request(prompt=prompt, max_new_tokens=8, rid="probe")])
    toks = free["requests"][0]["tokens"]
    eos = toks[1]

    eng = ContinuousBatchingEngine(model, params, n_slots=1, max_len=64,
                                   chunk=8, eos_id=eos, decode_ticks=8)
    report = eng.run([Request(prompt=prompt, max_new_tokens=8, rid="a"),
                      Request(prompt=prompt + 1, max_new_tokens=3, rid="b")])
    by_rid = {r["rid"]: r for r in report["requests"]}
    assert by_rid["a"]["tokens"] == toks[:2]    # EOS emitted, then retired
    assert by_rid["a"]["finish_reason"] == "eos"
    assert by_rid["b"]["n_tokens"] >= 1
    assert eng.pool.n_free == 1


def test_dispatch_accounting_shows_collapse(dense_model):
    """The optimization must be measurable: at decode_ticks=8 the engine
    launches strictly fewer decode programs than it executes ticks, and
    dispatches_per_token drops vs the single-tick engine on the same
    trace."""
    cfg, model, params = dense_model
    trace = poisson_trace(n_requests=4, vocab_size=cfg.vocab_size,
                          prompt_len=(3, 10), max_new=(8, 16), seed=2)

    def agg(ticks):
        eng = ContinuousBatchingEngine(model, params, n_slots=2, max_len=64,
                                       chunk=8, decode_ticks=ticks)
        return eng.run(list(trace))["aggregate"]

    one, eight = agg(1), agg(8)
    assert one["decode_dispatches"] == one["decode_steps"]
    assert eight["decode_dispatches"] < eight["decode_steps"]
    assert eight["dispatches_per_token"] < one["dispatches_per_token"]
    assert eight["host_syncs"] < one["host_syncs"]
    assert eight["generated_tokens"] == one["generated_tokens"]
    # block-granularity honesty: the multi-tick report carries the note
    assert "itl_note" in eight and "itl_effective_ms" in eight
    assert "itl_note" not in one


def test_decode_multi_rejects_bad_ticks(dense_model):
    cfg, model, params = dense_model
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(model, params, n_slots=2, max_len=64,
                                 chunk=8, decode_ticks=0)


def test_batched_prefill_single_dispatch(dense_model):
    """All mid-prefill slots advance in one prefill_chunks_batched launch
    per engine step: with 4 multi-chunk prompts and 4 slots the engine must
    launch far fewer prefill programs than chunk advances."""
    cfg, model, params = dense_model
    trace = [Request(prompt=np.arange(24, dtype=np.int32) + i,
                     max_new_tokens=3, rid=i) for i in range(4)]
    eng = ContinuousBatchingEngine(model, params, n_slots=4, max_len=64,
                                   chunk=8, decode_ticks=4)
    agg = eng.run(trace)["aggregate"]
    assert agg["prefill_chunks"] == 12          # 4 prompts x 3 chunks
    assert agg["prefill_dispatches"] == 3       # one per step, not per slot


# the ragged families of the conformance suite, and the int8 cache on a ring
IN_PLACE_ARCHS = ["qwen3_8b", "qwen3_8b+w4a8", "h2o_danube_1p8b+ring",
                  "h2o_danube_1p8b+ring+w4a8", "hymba_1p5b",
                  "llama32_vision_90b", "olmoe_1b_7b", "whisper_small"]


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a


@pytest.mark.parametrize("arch", IN_PLACE_ARCHS)
def test_decode_step_writes_only_each_rows_token(arch):
    """One ragged ``decode_step`` changes each self-attention layer's K/V
    (and an int8 cache's scale planes) only where a row's new token lands:
    an active row's write position in every layer, and an inactive row's
    reserved tail row in a full cache. An inactive ring row does not change
    at all. Every other element is its input, bit for bit."""
    cfg, model, params = _build(arch)
    ring = bool(cfg.kv_ring and cfg.window)
    slots = 4
    source = cfg.source_len if cfg.cross_attn_every else None
    cache = model.init_cache(slots, 256 if ring else 64, source,
                             n_sources=slots if source else None)
    size = cache["k"].shape[2]
    lengths = (np.array([size + 2, 3, 2 * size - 1, size + 9]) if ring
               else np.array([5, 3, 17, 9]))
    active = np.array([True, False, True, False])
    rng = np.random.default_rng(0)
    kv_keys = [k for k in ("k", "v", "k_scale", "v_scale") if k in cache]
    for key in kv_keys + [k for k in ("src_k", "src_v") if k in cache]:
        a = cache[key]
        fill = (rng.integers(-127, 128, a.shape) if a.dtype == jnp.int8
                else rng.uniform(0.5, 1.5, a.shape) if "scale" in key
                else rng.standard_normal(a.shape))
        cache[key] = jnp.asarray(fill, a.dtype)
    if source:
        cache["src_len"] = jnp.full((slots,), source // 2, jnp.int32)
        cache["src_index"] = jnp.arange(slots, dtype=jnp.int32)
    cache["len"] = jnp.asarray(lengths, jnp.int32)
    before = {k: _bits(cache[k]) for k in kv_keys}
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, slots), jnp.int32)
    logits, out = jax.jit(model.decode_step)(params, tokens, cache,
                                             jnp.asarray(active))
    assert np.isfinite(np.asarray(logits)).all()

    for key in kv_keys:
        new, old = _bits(out[key]), before[key]
        pos_last = "scale" in key             # [L, B, Hkv, S]: position last
        assert new.shape == old.shape, key
        may_change = np.zeros(old.shape, bool)
        for b in range(slots):
            if active[b]:
                at = lengths[b] % size
            elif ring:
                continue
            else:
                at = size - 1                 # the reserved tail row
            if pos_last:
                may_change[:, b, :, at] = True
            else:
                may_change[:, b, at] = True
        assert np.array_equal(new[~may_change], old[~may_change]), key
        for b in np.flatnonzero(active):      # the token landed in every layer
            at = lengths[b] % size
            for layer in range(old.shape[0]):
                got = new[layer, b, :, at] if pos_last else new[layer, b, at]
                was = old[layer, b, :, at] if pos_last else old[layer, b, at]
                assert not np.array_equal(got, was), (key, layer, b)
