"""Profiler instrumentation of the serving path: the engine's phase spans
(``repro.serving.telemetry.SPAN_NAMES``) as recorded by ``jax.profiler``,
and the named scopes (``repro.models.transformer.SCOPE_NAMES``) on the
compiled decode and prefill programs' ops."""
from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.models.api import build_model
from repro.models.transformer import SCOPE_NAMES
from repro.serving import ContinuousBatchingEngine, Request
from repro.serving.telemetry import SPAN_NAMES

jax.config.update("jax_platform_name", "cpu")

CALLER = "caller.step"


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("llama2-7b", reduced=True)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params, n_slots=3, max_len=128,
                                   chunk=16, decode_ticks=4, seed=0)
    return eng.warmup()


def _host_events(log_dir: Path, names) -> list[tuple[str, int, int, dict]]:
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in pd.planes if plane.name.startswith("/host")
                   for line in plane.lines for e in line.events
                   if e.name in names), key=lambda e: (e[1], -e[2]))


def test_engine_spans_nest_in_order_and_count_the_work(engine, tmp_path):
    eng = engine
    eng._zero_counters()
    rng = np.random.default_rng(1)
    for i, (plen, budget) in enumerate([(40, 9), (5, 14), (23, 6), (17, 11),
                                        (33, 5)]):
        eng.submit(Request(prompt=rng.integers(1, 500, plen, np.int32),
                           max_new_tokens=budget, rid=f"r{i}"))
    steps = 0
    with jax.profiler.trace(str(tmp_path)):
        while True:
            with TraceAnnotation(CALLER, step=steps):
                worked = eng.step()
            steps += 1
            if not worked:
                break
    assert len(eng.sched.retired) == 5 + 1      # the warm-up request too

    evs = _host_events(tmp_path, set(SPAN_NAMES) | {CALLER})
    callers = [e for e in evs if e[0] == CALLER]
    spans = [e for e in evs if e[0] != CALLER]
    assert len(callers) == steps
    grammar = re.compile(r"A(P(F)*)?(DSR)?$")
    letter = {"serve.admit": "A", "serve.prefill": "P",
              "serve.first_token": "F", "serve.decode": "D",
              "serve.sync": "S", "serve.retire": "R"}
    inside = 0
    for _, a, b, _ in callers:
        mine = [e for e in spans if a <= e[1] and e[2] <= b]
        inside += len(mine)
        assert grammar.match("".join(letter[e[0]] for e in mine)), mine
        # each span closes before the next opens
        assert all(x[2] <= y[1] for x, y in zip(mine, mine[1:]))
    assert inside == len(spans)                  # none outside a step

    def args(name, key):
        return [e[3][key] for e in spans if e[0] == name]

    ks, rows = args("serve.decode", "k"), args("serve.decode", "rows")
    assert len(ks) == eng.decode_dispatches > 0
    # no eos: every issued tick of a block has a live row
    assert sum(ks) == eng.decode_steps
    assert sum(k * r for k, r in zip(ks, rows)) == eng.issued_ticks
    assert args("serve.decode", "block") == list(range(len(ks)))
    assert args("serve.sync", "block") == args("serve.retire", "block") \
        == args("serve.decode", "block")
    assert sum(args("serve.retire", "emitted")) == eng.active_row_steps
    assert len(args("serve.prefill", "rows")) == eng.prefill_dispatches
    assert sum(args("serve.prefill", "rows")) == eng.prefill_chunks
    assert len(args("serve.first_token", "slot")) == 5
    assert sum(args("serve.admit", "admitted")) == 5


def _op_name_parts(hlo_text: str) -> set[str]:
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/")}


def test_compiled_programs_carry_every_scope(engine):
    eng = engine
    n = eng.pool.n_slots
    i32 = np.zeros((n,), np.int32)
    decode = eng._decode_fn(4).lower(
        eng.params, jnp.asarray(i32), eng.cache,
        jnp.zeros((n,), bool), jnp.asarray(i32), jnp.asarray(i32),
        jnp.asarray(i32)).compile()
    parts = _op_name_parts(decode.as_text())
    assert set(SCOPE_NAMES) <= parts, set(SCOPE_NAMES) - parts
    prefill = eng._prefill_batched.lower(
        eng.params, jnp.zeros((n, eng.chunk), jnp.int32), eng.cache,
        jnp.asarray(i32), jnp.asarray(i32), jnp.asarray(i32),
        jnp.zeros((n,), bool)).compile()
    parts = _op_name_parts(prefill.as_text())
    # a prefill chunk samples nothing: its first token is picked apart
    want = set(SCOPE_NAMES) - {"sample"}
    assert want <= parts, want - parts
    # the scopes change op metadata, never the programs' names
    assert decode.as_text().startswith("HloModule jit_block")
    assert prefill.as_text().startswith(
        "HloModule jit_prefill_chunks_batched")
