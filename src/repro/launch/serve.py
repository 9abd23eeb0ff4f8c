"""Serving driver: prefill + per-token decode (the paper's workload).

Two modes over host devices (reduced configs) or a production mesh:

* **lock-step** (default) — the ``ServingEngine`` batch: uniform-length
  prompts, prefill once, decode in lock-step. The decode step is the unit
  the dry-run lowers for the ``decode_*`` shape cells.
* **continuous** (``--continuous``) — the ragged continuous-batching
  subsystem (``repro.serving.continuous``): KV slot pool + source-KV pool +
  request scheduler + chunked slot prefill + multi-tick decode blocks
  (``--decode-ticks``), driven by a Poisson or file trace, with per-request
  TTFT / inter-token latency, slot-occupancy, and dispatch-accounting
  metrics.
  Covers **every** family: dense-KV, ring-KV SWA (``<arch>+ring``),
  recurrent-state (ssm / hybrid: rwkv6-3b, hymba-1.5b), MoE (olmoe-1b-7b,
  llama4-scout), and cross-attention stacks (vlm / audio: whisper-small,
  llama-3.2-vision-90b — Poisson traces get heterogeneous-length sources
  with shared source ids, served through the source-KV pool).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --batch 4 --prompt-len 32 --gen 64
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --reduced \
        --continuous --requests 16 --n-slots 4 --max-len 256
    PYTHONPATH=src python -m repro.launch.serve --arch whisper-small \
        --reduced --continuous --requests 8 --n-slots 2 --max-len 64
"""
from __future__ import annotations

import argparse
import json
import logging
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import build_model, needs_source
from repro.serving import (ContinuousBatchingEngine, EngineAuditor,
                           OverloadConfig, ServingEngine, Telemetry,
                           load_trace, poisson_trace)
from repro.serving.scheduler import SHED_POLICIES
from repro.serving.workload import TRACE_SHAPES

log = logging.getLogger("repro.launch.serve")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=64)
    ap.add_argument("--max-len", type=int, default=0, help="default: pow2 fit")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-impl", default=None,
                    choices=["blockwise", "tokenwise", "kernel", "naive"])
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--metrics-out")
    # --- continuous batching ---
    ap.add_argument("--continuous", action="store_true",
                    help="ragged continuous batching over a request trace "
                         "(every family: dense, ring, ssm, hybrid, MoE, "
                         "and cross-attention via the source-KV pool)")
    ap.add_argument("--n-slots", type=int, default=0,
                    help="KV slot pool size (default: --batch)")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous: trace length")
    ap.add_argument("--chunk", type=int, default=16,
                    help="continuous: prefill chunk size")
    ap.add_argument("--decode-ticks", type=int, default=1,
                    help="continuous: fused decode ticks per dispatch (K) — "
                         "the host syncs once per K tokens; on-device "
                         "EOS/budget retirement keeps outputs exact at any "
                         "K, the adaptive horizon drops to 1 while prefill "
                         "chunks are waiting")
    ap.add_argument("--rate", type=float, default=None,
                    help="continuous: mean arrival rate req/s "
                         "(default: backlogged)")
    ap.add_argument("--trace-shape", default="poisson",
                    choices=list(TRACE_SHAPES),
                    help="continuous: interarrival shape — poisson "
                         "(well-behaved), bursty (near-simultaneous "
                         "clumps), heavy-tail (Lomax gaps); overload "
                         "control is exercised by the latter two")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="continuous: bound the admission queue "
                         "(overload control; default unbounded)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=list(SHED_POLICIES),
                    help="continuous: what a full queue does — reject the "
                         "incoming request, shed the oldest queued one, or "
                         "degrade everyone's decode budget")
    ap.add_argument("--audit", action="store_true",
                    help="continuous: run the engine invariant auditor "
                         "after every decode block")
    ap.add_argument("--trace", default=None,
                    help="continuous: JSON trace file instead of generated "
                         "arrivals")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None,
                    help="continuous: write the run's telemetry as a "
                         "Chrome/Perfetto trace (open the .trace.json at "
                         "https://ui.perfetto.dev — one lane per slot)")
    ap.add_argument("--events-out", default=None,
                    help="continuous: stream raw telemetry events as JSONL "
                         "(convert later with tools/trace_viewer.py)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.decode_impl:
        cfg = cfg.replace(decode_impl=args.decode_impl)
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())

    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)

    if args.continuous:
        return _run_continuous(args, cfg, model, params, mesh)
    return _run_lockstep(args, cfg, model, params, mesh)


def _run_lockstep(args, cfg, model, params, mesh):
    need = args.prompt_len + args.gen
    max_len = args.max_len or (1 << (need - 1).bit_length())
    src = None
    if needs_source(cfg):
        src = jax.random.normal(
            jax.random.PRNGKey(1),
            (args.batch, cfg.source_len, cfg.d_model),
            jnp.dtype(cfg.compute_dtype)) * 0.02

    with mesh:
        eng = ServingEngine(model, params, max_len=max_len, batch=args.batch,
                            source_len=cfg.source_len if src is not None
                            else None)
        prompts = jax.random.randint(
            jax.random.PRNGKey(2), (args.batch, args.prompt_len), 0,
            cfg.vocab_size, jnp.int32)
        # warmup (compile)
        _ = eng.generate(prompts, steps=2, temperature=args.temperature,
                         source=src)
        t0 = time.perf_counter()
        out = eng.generate(prompts, steps=args.gen,
                           temperature=args.temperature, source=src)
        wall = time.perf_counter() - t0

    toks = args.batch * args.gen
    metrics = {"arch": args.arch, "batch": args.batch,
               "prompt_len": args.prompt_len, "generated": args.gen,
               "wall_s": round(wall, 3), "tokens_per_s": round(toks / wall, 1),
               "ms_per_token_step": round(1e3 * wall / args.gen, 2)}
    log.info("%s", metrics)
    print(json.dumps(metrics))
    if args.metrics_out:
        Path(args.metrics_out).write_text(json.dumps(metrics, indent=1))
    return out, metrics


def _run_continuous(args, cfg, model, params, mesh):
    n_slots = args.n_slots or args.batch
    max_len = args.max_len or 256
    if args.trace:
        trace = load_trace(args.trace, cfg.vocab_size)
    else:
        src_kw = {}
        if needs_source(cfg):
            # cross-attention stacks: heterogeneous source lengths + a
            # shared source id every other pair (source-KV pool dedup)
            src_kw = dict(source_len=(max(1, cfg.source_len // 4),
                                      cfg.source_len),
                          source_dim=cfg.d_model, source_share=2)
        trace = poisson_trace(
            n_requests=args.requests, vocab_size=cfg.vocab_size,
            rate=args.rate, prompt_len=(min(8, args.prompt_len),
                                        args.prompt_len),
            max_new=(min(4, args.gen), args.gen), seed=args.seed,
            shape=args.trace_shape, **src_kw)

    telemetry = (Telemetry(jsonl_path=args.events_out)
                 if (args.trace_out or args.events_out) else None)
    overload = (OverloadConfig(max_queue=args.max_queue,
                               policy=args.shed_policy)
                if args.max_queue else None)
    with mesh:
        eng = ContinuousBatchingEngine(
            model, params, n_slots=n_slots, max_len=max_len,
            chunk=args.chunk, eos_id=args.eos_id,
            temperature=args.temperature, seed=args.seed,
            decode_ticks=args.decode_ticks, telemetry=telemetry,
            overload=overload,
            auditor=EngineAuditor() if args.audit else None)
        eng.warmup()
        # a Ctrl-C lands inside run(), which drains gracefully: the
        # in-flight block finishes, queued requests shed with a typed
        # code, conservation still holds, and the report comes back with
        # interrupted: true — so the telemetry/trace sinks below always
        # flush instead of losing the JSONL tail
        report = eng.run(trace)
        if report["aggregate"].get("interrupted"):
            log.warning("run interrupted: %d shed, %d retired with partial "
                        "tokens", report["aggregate"]["n_shed"],
                        report["aggregate"]["n_retired"])
    if telemetry is not None:
        if args.trace_out:
            path = telemetry.write_chrome_trace(args.trace_out)
            log.info("wrote Perfetto trace (%d events) -> %s",
                     len(telemetry.events), path)
        telemetry.close()

    metrics = {"arch": args.arch, "mode": "continuous", "n_slots": n_slots,
               "max_len": max_len, "chunk": args.chunk,
               **report["aggregate"]}
    log.info("%s", metrics)
    print(json.dumps(metrics))
    if args.metrics_out:
        Path(args.metrics_out).write_text(json.dumps(
            {"metrics": metrics, "requests": report["requests"]}, indent=1))
    return report, metrics


if __name__ == "__main__":
    main()
