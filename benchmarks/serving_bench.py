"""Serving benchmark: continuous batching vs the lock-step baseline.

Replays the same ragged Poisson trace (mixed prompt / output lengths)
through both engines and compares useful-token throughput:

* **lock-step** — FIFO groups of ``n_slots`` requests through
  ``ServingEngine``: prompts right-padded to a uniform length, every group
  decodes for its longest member's budget (the padding + convoy waste this
  subsystem exists to remove);
* **continuous** — ``ContinuousBatchingEngine``: chunked slot prefill,
  per-slot retirement, immediate backfill, and multi-tick decode blocks
  (``--decode-ticks K``: K fused ticks per dispatch with on-device
  retirement — the host syncs once per K tokens; see
  ``repro.serving.continuous``). The JSON carries the engine's dispatch
  accounting (``dispatches_per_token``, ``host_syncs``) so the round-trip
  collapse is measurable, not just inferable from wall clock, plus the
  ``kv_bytes_per_slot`` / ``kv_rows_per_slot`` memory line — the O(window)
  win of ring-KV archs (``--arch <swa-arch>+ring``, e.g.
  ``h2o-danube-1.8b+ring``) is a reported number.

Both engines run the same jit'd model; tokens are counted as each request's
``max_new_tokens`` (useful tokens only — lock-step's over-generated padding
rows don't count). Emits a ``BENCH_serving.json`` summary.

Cross-attention archs (whisper-small, llama-3.2-vision-90b) get a mixed
trace of source-bearing requests with **heterogeneous source lengths**
(``--source-min/--source-max``) and shared source ids
(``--source-share N``: N consecutive requests per source — think N
questions about one image). The continuous engine serves them through the
source-KV pool (one encoder ingest per distinct source id, refcount-shared;
``source_ingests`` / ``source_shares`` land in the JSON) while lock-step
re-encodes per group — both paths mask per-row source lengths, so rows with
different encoder lengths batch together on either engine.

``--arch`` takes a comma-separated list (the JSON becomes a list of per-arch
results), and ``--verify`` re-checks the continuous engine's greedy outputs
token-for-token against per-request ``ServingEngine.generate`` (each
cross-attention request replayed with its own padded + length-masked
source) — the per-request-equivalence contract that covers the
recurrent-state (rwkv6-3b, hymba-1.5b) and MoE (olmoe-1b-7b) families and
holds at every tick horizon.

Every BENCH entry is stamped with ``schema_version``, the arch/config, the
trace seed, the jax version, and ``git describe`` so
``benchmarks/check_regression.py`` can gate fresh runs against the
checked-in ``benchmarks/baselines/*.json`` (and refuse cross-schema or
cross-workload comparisons). ``--trace-out x.trace.json`` adds a third
interleaved pass with telemetry enabled: the enabled-vs-disabled
throughput delta is reported (and gated under ``--check``) as the
telemetry overhead, the token streams are checked identical, and the
pass's event stream is written as a Chrome/Perfetto trace.

``--verify-agreement`` is the quantized (``+w4a8``) leg's replacement for
``--verify``: quantized decode is deliberately not token-exact vs fp32, so
instead of equality it scores greedy token **agreement** between the
continuous engine and per-request lock-step generation on the *same*
quantized model (both engines quantize identically, so this isolates
batching effects from quantization noise), gates it at
``AGREEMENT_TARGET`` under ``--check``, and reports the quantized
``kv_bytes_per_slot`` as a ratio of the fp32 twin's (same arch minus the
``+w4a8`` axis; gated at ``KV_RATIO_TARGET``) plus an informational
prefill-logits MAE probe vs the fp32 twin — the ``quant`` section of the
JSON, pinned by ``check_regression.py`` like the other legs.

``--faults`` switches to the chaos leg: fault-free run, seeded-FaultPlan
run, and exact replay on one engine (invariant auditor on), gating
victim-only quarantine, unaffected-stream byte-identity, deterministic
replay, and zero slot/source leaks — the recovery contract as a pinned
regression surface (``bench: "serving_chaos"``).

    PYTHONPATH=src python benchmarks/serving_bench.py --reduced
    PYTHONPATH=src python benchmarks/serving_bench.py --reduced --verify \
        --arch rwkv6-3b,hymba-1.5b,olmoe-1b-7b --decode-ticks 8
    PYTHONPATH=src python benchmarks/serving_bench.py --reduced --check \
        --verify --faults --trace-shape bursty --rate 200 \
        --json BENCH_serving_chaos.json
    PYTHONPATH=src python benchmarks/serving_bench.py --reduced --verify \
        --arch whisper_small --json BENCH_serving_xattn.json
    PYTHONPATH=src python benchmarks/serving_bench.py --reduced \
        --trace-out serving.trace.json   # open at https://ui.perfetto.dev
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model, needs_source
from repro.serving import (ContinuousBatchingEngine, EngineAuditor,
                           FaultPlan, ServingEngine, Telemetry,
                           poisson_trace)
from repro.serving.workload import TRACE_SHAPES

SPEEDUP_TARGET = 1.3
# quant (+w4a8) leg gates: greedy continuous-vs-lockstep token agreement on
# the same quantized model, and the int8 cache's byte footprint vs the fp32
# twin (int8 rows + bf16 scales = 0.25 + 0.5/Dh — 0.28125 at the reduced
# configs' Dh = 16, under the 0.3x budget)
AGREEMENT_TARGET = 0.90
KV_RATIO_TARGET = 0.3
# BENCH entry schema, stamped into every JSON so check_regression.py can
# refuse cross-schema comparisons (keep in sync with
# benchmarks/check_regression.py; bump on any semantic change to entries)
SCHEMA_VERSION = 2
TELEMETRY_OVERHEAD_MAX_PCT = 3.0


def _git_describe() -> str:
    try:
        out = subprocess.run(
            ["git", "describe", "--always", "--dirty"],
            capture_output=True, text=True, timeout=10,
            cwd=Path(__file__).resolve().parent).stdout.strip()
        return out or "unknown"
    except Exception:
        return "unknown"


def _padded_sources(group, src_max, d_model, n_rows):
    """Right-pad a group's heterogeneous sources to [n_rows, src_max, d]
    plus the [n_rows] true lengths (lock-step's uniform-shape form of what
    the continuous engine masks per slot)."""
    src = np.zeros((n_rows, src_max, d_model), np.float32)
    lens = np.zeros((n_rows,), np.int32)
    for j, r in enumerate(group):
        if r.source is not None:
            src[j, :len(r.source)] = r.source
            lens[j] = len(r.source)
    return jnp.asarray(src), jnp.asarray(lens)


def lockstep_runner(model, params, trace, *, n_slots, max_len, pad_id=0):
    """One timed lock-step pass: FIFO groups of ``n_slots``, prompts padded
    to the trace-wide max (one prefill compile), each group decoding
    max(max_new) steps. Cross-attention traces pad each group's sources to
    the pool row size and mask per-row lengths (and the encoder reruns per
    group even when requests share a source — the padding + convoy +
    re-encode waste continuous batching removes). Returns a closure so
    passes can interleave with the continuous engine's (shared host-load
    phases hit both fairly)."""
    cfg = model.cfg
    with_src = needs_source(cfg) and any(r.source is not None for r in trace)
    src_max = cfg.source_len if with_src else None
    eng = ServingEngine(model, params, max_len=max_len, batch=n_slots,
                        source_len=src_max)
    pmax = max(len(r.prompt) for r in trace)
    warm_kw = {}
    if with_src:
        warm_kw = dict(source=jnp.zeros((n_slots, src_max, cfg.d_model),
                                        jnp.float32),
                       source_len=jnp.zeros((n_slots,), jnp.int32))
    # warmup/compile with the shapes the timed loop uses
    eng.generate(jnp.full((n_slots, pmax), pad_id, jnp.int32), steps=2,
                 **warm_kw)

    def one_pass():
        t0 = time.perf_counter()
        useful = 0
        for i in range(0, len(trace), n_slots):
            group = trace[i:i + n_slots]
            prompts = np.full((n_slots, pmax), pad_id, np.int32)
            for j, r in enumerate(group):
                prompts[j, :len(r.prompt)] = r.prompt  # right-pad to uniform
            kw = {}
            if with_src:
                kw["source"], kw["source_len"] = _padded_sources(
                    group, src_max, cfg.d_model, n_slots)
            steps = max(r.max_new_tokens for r in group)
            out = eng.generate(jnp.asarray(prompts), steps=steps, **kw)
            jax.block_until_ready(out)
            useful += sum(r.max_new_tokens for r in group)
        wall = time.perf_counter() - t0
        return {"wall_s": round(wall, 3),
                "tokens_per_s": round(useful / wall, 1),
                "useful_tokens": useful,
                "groups": -(-len(trace) // n_slots),
                "padded_prompt_len": pmax}
    return one_pass


def continuous_runner(model, params, trace, *, n_slots, max_len, chunk, seed,
                      decode_ticks, telemetry=None):
    eng = ContinuousBatchingEngine(model, params, n_slots=n_slots,
                                   max_len=max_len, chunk=chunk, seed=seed,
                                   decode_ticks=decode_ticks,
                                   telemetry=telemetry)
    eng.warmup()
    holder = {}

    def one_pass():
        report = eng.run([r for r in trace])
        holder["report"] = report      # full per-request report for --verify
        return report["aggregate"]
    one_pass.holder = holder
    return one_pass


def verify_equivalence(model, params, trace, report, *, max_len) -> list:
    """Greedy continuous-batching outputs must equal per-request lock-step
    generation token-for-token; returns the rids that differ. Cross-
    attention requests replay each with its own (padded + length-masked)
    source, so heterogeneous-source batching must also be invisible."""
    cfg = model.cfg
    with_src = needs_source(cfg) and any(r.source is not None for r in trace)
    ref = ServingEngine(model, params, max_len=max_len, batch=1,
                        source_len=cfg.source_len if with_src else None)
    by_rid = {r["rid"]: r for r in report["requests"]}
    bad = []
    for req in trace:
        kw = {}
        if with_src and req.source is not None:
            kw["source"], kw["source_len"] = _padded_sources(
                [req], cfg.source_len, cfg.d_model, 1)
        want = np.asarray(ref.generate(jnp.asarray(req.prompt)[None],
                                       steps=req.max_new_tokens, **kw))[0]
        if by_rid[req.rid]["tokens"] != want.tolist():
            bad.append(req.rid)
    return bad


def verify_agreement(model, params, trace, report, *, max_len) -> tuple:
    """Quantized (``+w4a8``) twin of :func:`verify_equivalence`: score the
    continuous engine's greedy outputs against per-request lock-step
    generation on the **same quantized model** as a token agreement rate
    instead of demanding equality. Both engines quantize the same params in
    ``__init__``, so single-chunk prompts agree bit-exactly and multi-chunk
    prompts diverge only through the chunked prefill's int8 prefix re-read
    (see tests/test_serving_conformance.py for the two-tier contract).
    Returns ``(rate, matched, total)``."""
    cfg = model.cfg
    with_src = needs_source(cfg) and any(r.source is not None for r in trace)
    ref = ServingEngine(model, params, max_len=max_len, batch=1,
                        source_len=cfg.source_len if with_src else None)
    by_rid = {r["rid"]: r for r in report["requests"]}
    matched = total = 0
    for req in trace:
        kw = {}
        if with_src and req.source is not None:
            kw["source"], kw["source_len"] = _padded_sources(
                [req], cfg.source_len, cfg.d_model, 1)
        want = np.asarray(ref.generate(jnp.asarray(req.prompt)[None],
                                       steps=req.max_new_tokens, **kw))[0]
        got = by_rid[req.rid]["tokens"]
        matched += sum(a == b for a, b in zip(got, want.tolist()))
        total += len(want)
    return (matched / total if total else 1.0), matched, total


def _kv_bytes_per_slot(eng) -> int:
    """Static per-slot KV footprint of an engine's cache — the same key
    set and arithmetic as ``ContinuousBatchingEngine.report()``, usable on
    a freshly built (never run) fp32 twin engine."""
    kv = [eng.cache[k] for k in ("k", "v", "k_scale", "v_scale",
                                 "cross_k", "cross_v", "src_k", "src_v",
                                 "src_k_scale", "src_v_scale")
          if k in eng.cache]
    return sum(int(a.size) * a.dtype.itemsize for a in kv) // eng.pool.n_slots


def quant_mae_probe(model, params, vocab_size: int) -> float:
    """Informational fp32-twin comparison: prefill-logits MAE on a seeded
    probe batch, normalized by the fp32 logit spread. Free-running token
    agreement vs fp32 cliffs on top-2 gaps (MoE routing, small-vocab
    reduced configs), so the fp32 comparison is pinned where quantization
    actually bounds something; the serving-level gate is
    :func:`verify_agreement` on the quantized pair."""
    from repro.models.quantized import quantize_params
    qparams = quantize_params(params)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, vocab_size, (4, 16)), jnp.int32)
    cache_fp = model.init_cache(4, 64, kv_dtype=jnp.float32)
    cache_q = model.init_cache(4, 64, kv_dtype=jnp.int8)
    lf, _ = jax.jit(model.prefill)(params, prompts, cache_fp, None, None)
    lq, _ = jax.jit(model.prefill)(qparams, prompts, cache_q, None, None)
    lf = np.asarray(lf, np.float64)
    lq = np.asarray(lq, np.float64)
    return float(np.abs(lq - lf).mean() / lf.std())


def best_of_interleaved(runners: dict, repeats: int) -> tuple[dict, list]:
    """Alternate one pass per engine, ``repeats`` rounds; keep each engine's
    fastest pass. Interleaving means a slow host phase degrades the same
    round of every engine instead of one engine's whole measurement. Also
    returns the per-round results (``rounds[i][name]``) so paired same-round
    comparisons — e.g. the telemetry overhead gate — can cancel host drift
    instead of comparing two independent bests."""
    best: dict = {}
    rounds: list[dict] = []
    for _ in range(repeats):
        this_round: dict = {}
        for name, one_pass in runners.items():
            res = one_pass()
            this_round[name] = res
            if name not in best or res["wall_s"] < best[name]["wall_s"]:
                best[name] = res
        rounds.append(this_round)
    return best, rounds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b",
                    help="architecture name, or a comma-separated list")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=32,
                    help="trace length; short traces make the tail-drain "
                         "phase (slots emptying) dominate occupancy")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=32,
                    help="prefill chunk; 32 halves per-chunk call overhead "
                         "vs 16 on this trace's prompt mix")
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=48)
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-shape", default="poisson",
                    choices=list(TRACE_SHAPES),
                    help="interarrival shape (bursty / heavy-tail stress "
                         "the queue; default poisson keeps pre-existing "
                         "baselines comparable)")
    ap.add_argument("--rate", type=float, default=None,
                    help="mean arrival rate req/s (default: backlogged)")
    ap.add_argument("--faults", action="store_true",
                    help="chaos mode: run the continuous engine fault-free, "
                         "then under a seeded FaultPlan, then replay the "
                         "plan — checks victim-only quarantine, unaffected-"
                         "stream byte-identity, deterministic replay, and "
                         "zero slot/source leaks (auditor on throughout). "
                         "Replaces the lockstep-vs-continuous comparison")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="chaos mode: FaultPlan.random seed")
    ap.add_argument("--n-faults", type=int, default=3,
                    help="chaos mode: faults per plan")
    ap.add_argument("--source-min", type=int, default=0,
                    help="cross-attention archs: min source rows per "
                         "request (default: source_len // 4)")
    ap.add_argument("--source-max", type=int, default=0,
                    help="cross-attention archs: max source rows per "
                         "request (default: the config's source_len)")
    ap.add_argument("--source-share", type=int, default=2,
                    help="cross-attention archs: consecutive requests "
                         "sharing one source id (the pool serves shares "
                         "by refcount — source_ingests/source_shares in "
                         "the JSON); 1 disables sharing")
    ap.add_argument("--decode-ticks", type=int, default=8,
                    help="fused decode ticks per dispatch (K): the host "
                         "syncs once per K tokens; on-device retirement "
                         "keeps per-request outputs exact at any K")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed passes per engine; best taken")
    ap.add_argument("--json", default="BENCH_serving.json")
    ap.add_argument("--check", action="store_true",
                    help=f"exit non-zero unless speedup >= {SPEEDUP_TARGET}x")
    ap.add_argument("--verify", action="store_true",
                    help="check continuous greedy outputs token-for-token "
                         "against per-request generation (exit non-zero on "
                         "any mismatch)")
    ap.add_argument("--verify-agreement", action="store_true",
                    help="quantized (+w4a8) archs: score continuous greedy "
                         "outputs against per-request generation on the "
                         "same quantized model as a token agreement rate "
                         f"(--check gates >= {AGREEMENT_TARGET}), and "
                         "report kv_bytes_per_slot as a ratio of the fp32 "
                         f"twin's (--check gates <= {KV_RATIO_TARGET}x) "
                         "plus an informational logits-MAE probe")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace (.trace.json) of a "
                         "telemetry-enabled continuous pass, and report the "
                         "telemetry overhead (enabled vs disabled "
                         "throughput; --check gates it at "
                         f"{TELEMETRY_OVERHEAD_MAX_PCT}%%). With multiple "
                         "archs the arch name is appended to the stem")
    args = ap.parse_args(argv)
    enable_compile_cache()

    archs = [a.strip() for a in args.arch.split(",")]
    results, rc = [], 0
    for arch in archs:
        trace_out = None
        if args.trace_out:
            p = Path(args.trace_out)
            trace_out = (p if len(archs) == 1
                         else p.with_name(f"{p.stem}.{arch}{p.suffix}"))
        result, arch_rc = (run_chaos(arch, args) if args.faults
                           else run_arch(arch, args, trace_out=trace_out))
        results.append(result)
        rc = max(rc, arch_rc)

    out = Path(args.json)
    out.write_text(json.dumps(results[0] if len(results) == 1 else results,
                              indent=1))
    print(f"wrote {out}")
    return rc


def setup_arch(arch: str, args):
    """Shared per-arch setup: config, model, params, and the feasible
    seeded trace (same filters for every bench mode, so a chaos run and a
    perf run over the same flags replay the identical workload)."""
    cfg = get_config(arch, reduced=args.reduced)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    src_kw, src_range = {}, None
    if needs_source(cfg):
        # cross-attention trace: heterogeneous source lengths + shared
        # source ids, the mixed shape vision/audio traffic has. Clamped to
        # the config's source_len (the pool row size): an oversized source
        # would be rejected by the continuous engine and overflow the
        # lock-step padding — infeasible on both engines, so it never
        # enters the trace (mirrors the prompt-budget feasibility filter)
        hi = min(args.source_max or cfg.source_len, cfg.source_len)
        src_range = (min(args.source_min or max(1, cfg.source_len // 4), hi),
                     hi)
        src_kw = dict(source_len=src_range, source_dim=cfg.d_model,
                      source_share=args.source_share)
    trace = poisson_trace(
        n_requests=args.requests, vocab_size=cfg.vocab_size,
        rate=args.rate, shape=args.trace_shape,
        prompt_len=(args.prompt_min, args.prompt_max),
        max_new=(args.gen_min, args.gen_max), seed=args.seed, **src_kw)
    # both engines must see the identical feasible workload: a request the
    # continuous engine would reject (slot capacity), or whose budget plus
    # the trace-wide padded prompt trips lock-step's p + steps <= max_len
    # assert, skews the comparison
    feasible = [r for r in trace
                if len(r.prompt) + r.max_new_tokens <= args.max_len - 1]
    pmax = max((len(r.prompt) for r in feasible), default=0)
    feasible = [r for r in feasible
                if pmax + r.max_new_tokens <= args.max_len]
    if len(feasible) < len(trace):
        print(f"  [note] dropped {len(trace) - len(feasible)} requests "
              f"exceeding max_len {args.max_len} budget")
    return cfg, model, params, feasible, src_range


def _entry_stamp(cfg, args, trace, src_range) -> dict:
    """The identity keys check_regression.py compares fresh vs baseline on.
    ``trace_shape`` / ``rate`` appear only when non-default so pre-existing
    baselines (generated before the knobs existed) stay comparable."""
    return {
        "schema_version": SCHEMA_VERSION,
        "meta": {
            "seed": args.seed, "arch_list": args.arch,
            "config": cfg.name, "jax_version": jax.__version__,
            "git_describe": _git_describe(),
        },
        "arch": cfg.name, "reduced": args.reduced,
        "n_slots": args.n_slots, "n_requests": len(trace),
        "max_len": args.max_len, "chunk": args.chunk,
        "decode_ticks": args.decode_ticks,
        "prompt_len": [args.prompt_min, args.prompt_max],
        "max_new": [args.gen_min, args.gen_max],
        **({"trace_shape": args.trace_shape}
           if args.trace_shape != "poisson" else {}),
        **({"rate": args.rate} if args.rate is not None else {}),
        **({"source_len": list(src_range),
            "source_share": args.source_share} if src_range else {}),
    }


def run_arch(arch: str, args, trace_out: Path | None = None
             ) -> tuple[dict, int]:
    cfg, model, params, trace, src_range = setup_arch(arch, args)

    print(f"[serving_bench] {cfg.name} reduced={args.reduced} "
          f"slots={args.n_slots} requests={len(trace)}")
    cont_runner = continuous_runner(model, params, trace,
                                    n_slots=args.n_slots,
                                    max_len=args.max_len,
                                    chunk=args.chunk, seed=args.seed,
                                    decode_ticks=args.decode_ticks)
    runners = {
        "lockstep": lockstep_runner(model, params, trace,
                                    n_slots=args.n_slots,
                                    max_len=args.max_len),
        "continuous": cont_runner,
    }
    tel = tel_runner = None
    if trace_out is not None:
        # a third interleaved engine with telemetry enabled: same trace,
        # same jits — the enabled-vs-disabled throughput delta IS the
        # telemetry overhead, measured not asserted
        tel = Telemetry()
        tel_runner = continuous_runner(model, params, trace,
                                       n_slots=args.n_slots,
                                       max_len=args.max_len,
                                       chunk=args.chunk, seed=args.seed,
                                       decode_ticks=args.decode_ticks,
                                       telemetry=tel)
        runners["continuous+telemetry"] = tel_runner
    best, rounds = best_of_interleaved(runners, args.repeats)
    lock, cont = best["lockstep"], best["continuous"]
    print(f"  lock-step:  {lock['tokens_per_s']:8.1f} tok/s "
          f"({lock['wall_s']}s, {lock['groups']} groups padded to "
          f"{lock['padded_prompt_len']})")
    print(f"  continuous: {cont['tokens_per_s']:8.1f} tok/s "
          f"({cont['wall_s']}s, occupancy {cont['mean_occupancy']}, "
          f"ttft p50 {cont['ttft_p50_s']}s, decode_ticks "
          f"{args.decode_ticks}, {cont['dispatches_per_token']} "
          f"dispatches/token, {cont['host_syncs']} host syncs)")
    # the O(window) accounting line: ring archs hold kv_rows_per_slot ==
    # ring_len << max_len live KV rows per slot
    print(f"  kv cache:   {cont['kv_bytes_per_slot']} B/slot "
          f"({cont['kv_rows_per_slot']} rows/slot, max_len "
          f"{cont['max_len']})")
    if "source_ingests" in cont:
        print(f"  source kv:  {cont['source_ingests']} ingests, "
              f"{cont['source_shares']} shares "
              f"({cont['src_rows_per_entry']} rows/entry)")

    speedup = round(cont["tokens_per_s"] / lock["tokens_per_s"], 3)
    status = "PASS" if speedup >= SPEEDUP_TARGET else "MISS"
    print(f"  speedup: {speedup}x (target {SPEEDUP_TARGET}x) [{status}]")

    rc = 0 if (speedup >= SPEEDUP_TARGET or not args.check) else 1
    telemetry_info = None
    if trace_out is not None:
        tel_best = best["continuous+telemetry"]
        # paired same-round comparison: each interleaved round ran both
        # engines under the same host conditions, so the per-round ratio
        # cancels drift; the min over rounds bounds the intrinsic overhead
        # (noise can only inflate a round's ratio, never deflate it). The
        # run is sub-second, so host jitter swamps the true cost at 3
        # rounds — run extra back-to-back pairs until the bound stabilizes
        ratios = [(1 - r["continuous+telemetry"]["tokens_per_s"]
                   / r["continuous"]["tokens_per_s"]) * 100
                  for r in rounds]
        for _ in range(max(0, 7 - len(ratios))):
            if min(ratios) <= 0.0:
                break                        # already at/below parity
            pair = {"continuous": cont_runner()["tokens_per_s"],
                    "continuous+telemetry": tel_runner()["tokens_per_s"]}
            ratios.append(
                (1 - pair["continuous+telemetry"] / pair["continuous"])
                * 100)
        # a negative min means the pair ran at parity within noise
        overhead = round(max(0.0, min(ratios)), 2)
        same = (
            {r["rid"]: r["tokens"]
             for r in cont_runner.holder["report"]["requests"]}
            == {r["rid"]: r["tokens"]
                for r in tel_runner.holder["report"]["requests"]})
        tel.write_chrome_trace(trace_out)
        telemetry_info = {
            "overhead_pct": overhead, "overhead_max_pct":
            TELEMETRY_OVERHEAD_MAX_PCT, "tokens_identical": same,
            "events": len(tel.events), "trace_out": str(trace_out),
            "tokens_per_s_enabled": tel_best["tokens_per_s"],
        }
        tel_ok = overhead <= TELEMETRY_OVERHEAD_MAX_PCT and same
        print(f"  telemetry:  overhead {overhead}% paired-min "
              f"(best {tel_best['tokens_per_s']} vs {cont['tokens_per_s']} "
              f"tok/s; max {TELEMETRY_OVERHEAD_MAX_PCT}%), tokens "
              f"identical: {same}, {len(tel.events)} events -> {trace_out} "
              f"[{'PASS' if tel_ok else 'FAIL'}]")
        if args.check and not tel_ok:
            rc = 1
    quant_info = None
    if args.verify_agreement:
        if not cfg.w4a8_serve:
            print(f"  [note] --verify-agreement skipped: {cfg.name} has no "
                  "+w4a8 axis (use --verify for exact equivalence)")
        else:
            rate, matched, total = verify_agreement(
                model, params, trace, cont_runner.holder["report"],
                max_len=args.max_len)
            # fp32 twin: the same arch minus the +w4a8 axis — its (never
            # run) engine's cache is the denominator of the byte ratio
            base_arch = arch.replace("+w4a8", "")
            t_cfg = get_config(base_arch, reduced=args.reduced)
            t_eng = ContinuousBatchingEngine(
                build_model(t_cfg), params, n_slots=args.n_slots,
                max_len=args.max_len, chunk=args.chunk, seed=args.seed,
                decode_ticks=args.decode_ticks)
            fp_bytes = _kv_bytes_per_slot(t_eng)
            ratio = round(cont["kv_bytes_per_slot"] / fp_bytes, 4)
            mae = round(quant_mae_probe(model, params, cfg.vocab_size), 4)
            quant_ok = rate >= AGREEMENT_TARGET and ratio <= KV_RATIO_TARGET
            quant_info = {
                "agreement_rate": round(rate, 4),
                "agreement_matched": matched,
                "agreement_total": total,
                "agreement_target": AGREEMENT_TARGET,
                "kv_bytes_per_slot_fp32": fp_bytes,
                "kv_bytes_ratio": ratio,
                "kv_ratio_max": KV_RATIO_TARGET,
                "logits_mae_over_spread": mae,     # informational
            }
            print(f"  quant: agreement {rate:.4f} ({matched}/{total} "
                  f"tokens, floor {AGREEMENT_TARGET}), kv bytes "
                  f"{cont['kv_bytes_per_slot']} vs fp32 twin {fp_bytes} "
                  f"= {ratio}x (max {KV_RATIO_TARGET}x), logits MAE/spread "
                  f"{mae} [{'PASS' if quant_ok else 'FAIL'}]")
            if args.check and not quant_ok:
                rc = 1
    result = {
        "bench": "serving_continuous_vs_lockstep",
        **_entry_stamp(cfg, args, trace, src_range),
        "lockstep": lock, "continuous": cont,
        "speedup_tokens_per_s": speedup,
        "speedup_target": SPEEDUP_TARGET,
        **({"quant": quant_info} if quant_info else {}),
        **({"telemetry": telemetry_info} if telemetry_info else {}),
    }
    if args.verify:
        bad = verify_equivalence(model, params, trace,
                                 cont_runner.holder["report"],
                                 max_len=args.max_len)
        result["verify_mismatched_rids"] = bad
        print(f"  verify: {len(trace) - len(bad)}/{len(trace)} requests "
              f"token-for-token equal to per-request generation "
              f"[{'PASS' if not bad else 'FAIL: ' + str(bad)}]")
        rc = max(rc, 1 if bad else 0)
    return result, rc


def run_chaos(arch: str, args) -> tuple[dict, int]:
    """Chaos leg (``--faults``): one continuous engine with the invariant
    auditor on, three runs over the identical trace — fault-free, under a
    seeded :class:`FaultPlan`, and a replay of the same plan — then the
    recovery contract, checked not asserted:

    * only the plan's fired victims end ERRORED (victim-only quarantine);
    * every non-victim token stream is byte-identical to the fault-free
      run, and each victim's partial stream is a prefix of its fault-free
      stream;
    * the replay run reproduces the faulted run exactly (tokens + errored
      set) — fault handling is deterministic, so failures are debuggable;
    * zero slot / source-entry leaks after the faulted run, and a full
      post-run auditor check passes.

    All gates are deterministic for a given (seed, fault-seed) pair, so
    ``check_regression.py`` pins them exactly against the checked-in
    ``BENCH_serving_chaos.json`` baseline."""
    cfg, model, params, trace, src_range = setup_arch(arch, args)
    print(f"[serving_bench --faults] {cfg.name} reduced={args.reduced} "
          f"slots={args.n_slots} requests={len(trace)} "
          f"shape={args.trace_shape}")
    auditor = EngineAuditor()
    eng = ContinuousBatchingEngine(
        model, params, n_slots=args.n_slots, max_len=args.max_len,
        chunk=args.chunk, seed=args.seed, decode_ticks=args.decode_ticks,
        auditor=auditor)
    eng.warmup()
    clean = eng.run(list(trace))

    kinds = ("poison_nan", "dispatch_fail", "tick_delay")
    if needs_source(cfg):
        kinds += ("ingest_fail",)
    # max_block=0: every fault fires at its seam's first opportunity —
    # poison at the victim's first decode block — so the fired set is
    # request-relative and stays deterministic under timed bursty arrivals
    plan = FaultPlan.random(args.fault_seed, [r.rid for r in trace],
                            n_faults=args.n_faults, kinds=kinds, max_block=0)
    eng.faults = plan
    faulted = eng.run(list(trace))
    eng.faults = plan.replay()
    replayed = eng.run(list(trace))
    eng.faults = None

    def toks(report):
        return {r["rid"]: r["tokens"] for r in report["requests"]}

    def errored(report):
        return sorted(r["rid"] for r in report["requests"]
                      if r["status"] == "errored")

    ct, ft, rt = toks(clean), toks(faulted), toks(replayed)
    victims = sorted(plan.victims())
    err = errored(faulted)
    victim_only = err == victims
    unaffected = all(ft[rid] == t for rid, t in ct.items()
                     if rid not in victims)
    prefix_ok = all(ft[rid] == ct[rid][:len(ft[rid])] for rid in victims)
    replay_identical = (ft == rt and err == errored(replayed))
    slot_leaks = eng.pool.n_used
    src_leaks = eng.src_pool.n_used if eng.src_pool is not None else 0
    try:
        auditor.check(eng)
        audit_clean = True
    except AssertionError as e:
        audit_clean = False
        print(f"  [audit] post-run violation: {e}")

    agg = faulted["aggregate"]
    chaos = {
        "plan": plan.to_json(),
        "victims": victims, "errored": err,
        "n_errored": agg["n_errored"], "n_shed": agg["n_shed"],
        "generated_tokens": agg["generated_tokens"],
        "faults_fired": agg["faults_fired"],
        "dispatch_retries": agg.get("dispatch_retries", 0),
        "audit_checks": agg["audit_checks"],
        "victim_only_quarantine": victim_only,
        "unaffected_identical": unaffected,
        "victim_prefix_ok": prefix_ok,
        "replay_identical": replay_identical,
        "slot_leaks": slot_leaks, "src_leaks": src_leaks,
        "audit_clean": audit_clean,
    }
    ok = (victim_only and unaffected and prefix_ok and replay_identical
          and slot_leaks == 0 and src_leaks == 0 and audit_clean
          and agg["audit_checks"] > 0)
    print(f"  plan: {plan!r} -> victims {victims}, errored {err} "
          f"[{'OK' if victim_only else 'FAIL'}]")
    print(f"  recovery: unaffected identical {unaffected}, victim prefix "
          f"{prefix_ok}, replay identical {replay_identical}")
    print(f"  ledger: {slot_leaks} slot leaks, {src_leaks} source leaks, "
          f"{agg['audit_checks']} audit checks, clean {audit_clean}")
    print(f"  tokens: {agg['generated_tokens']} retired "
          f"({agg['n_errored']} errored, {agg['n_shed']} shed, "
          f"{chaos['dispatch_retries']} dispatch retries) "
          f"[{'PASS' if ok else 'FAIL'}]")

    rc = 0 if (ok or not args.check) else 1
    result = {
        "bench": "serving_chaos",
        **_entry_stamp(cfg, args, trace, src_range),
        "fault_seed": args.fault_seed, "n_faults": args.n_faults,
        "clean": clean["aggregate"], "faulted": agg,
        "chaos": chaos,
    }
    if args.verify:
        bad = verify_equivalence(model, params, trace, clean,
                                 max_len=args.max_len)
        result["verify_mismatched_rids"] = bad
        print(f"  verify: {len(trace) - len(bad)}/{len(trace)} fault-free "
              f"requests token-for-token equal to per-request generation "
              f"[{'PASS' if not bad else 'FAIL: ' + str(bad)}]")
        rc = max(rc, 1 if bad else 0)
    return result, rc


if __name__ == "__main__":
    sys.exit(main())
