#!/usr/bin/env python3
"""Chip smoke: the continuous-batching server on one TPU at h2o-danube-1.8b's
published widths, and both Pallas kernels compiled (not interpreted) at that
model's widths, each checked against a reference.

Run it from the repository root on a machine with a TPU:

    python chip_smoke.py [--seed N]

Phases, in one process (a chip belongs to one process at a time), each
printing one JSON line:

1. ``device``: JAX must find a TPU; anything else exits non-zero, naming
   the platform it found.
2. ``served``: ``ContinuousBatchingEngine`` over the published 24-layer
   config (random weights from ``--seed``, bf16 compute, the default decode
   path) serves a seeded trace of 8 requests. Every request must retire at
   its full budget with finite logits, and one request's served prefill
   logits must agree with the plain f32 reference forward.
3. ``swiftkv_decode``: the decode-attention kernel (linear, ring, int8) at
   B=4, Hkv=8, G=4, D=80, S=2048 against the blockwise jnp path and an f32
   softmax reference; then one full-width ``decode_step`` with
   ``decode_impl="kernel"`` against ``"blockwise"`` on the same cache.
4. ``gemv_w4a8``: the W4A8 kernel at 2560x6912 and 6912x2560 against
   ``w4a8_matmul_ref``, once through ``layers.linear``.

Every kernel's compiled program must contain ``tpu_custom_call``. The last
line of stdout is ``{"ok": true, "device": {...}}``; any failed check exits
non-zero before it.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b"
# the pool that fits one v5e (16 GB): f32 parameter storage (7.3 GB) plus
# the bf16 cache and each program's temporaries
N_SLOTS, MAX_LEN, CHUNK, DECODE_TICKS = 4, 2048, 256, 8
N_REQUESTS, PROMPT_LEN, GEN_LEN = 8, (64, 1024), (32, 128)

# Served bf16 logits against the f32 reference, as ||got - ref|| / ||ref||.
# bf16 rounds to 2^-9 relative; the residual path rounds about 7 times per
# layer, so over 24 layers independent errors add to about sqrt(168) * 2^-9
# = 0.025. A 24-layer, 640-wide bf16 model of the same shape measured
# 0.018-0.022 on CPU (prompts of 64-700 tokens). A wrong mask, position or
# head gives an error of order 1. The same bound holds two bf16 decode paths
# against each other.
SERVED_REL_TOL = 0.06
DECODE_IMPL_REL_TOL = 0.06
# One attention call against its references, same norm: each output element
# is rounded once to bf16 (2^-9) and may carry a bf16-rounded probability
# from a default-precision f32 matmul (2^-9); a wrong head, block or
# position gives an error of order 1.
ATTN_REL_TOL = 2.0 ** -6
# W4A8 against its reference: the int32 group partials are exact in both,
# only the f32 order over at most 54 group terms differs (2^-24 each).
GEMV_REL_TOL = 1e-4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def require_kernel(compiled, what: str) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{what}: no tpu_custom_call in the compiled program")


def device_check() -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform {d.platform!r} "
            f"({len(devices)} x {d.device_kind!r}); this check runs only on "
            "a TPU")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def served_phase(cfg, model, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.reference import dense_reference_logits
    from repro.serving import ContinuousBatchingEngine, Request

    t0 = time.perf_counter()
    eng = ContinuousBatchingEngine(model, params, n_slots=N_SLOTS,
                                   max_len=MAX_LEN, chunk=CHUNK,
                                   decode_ticks=DECODE_TICKS, seed=seed)
    eng.warmup()
    jax.block_until_ready(eng.cache)
    compile_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(PROMPT_LEN[0],
                                                         PROMPT_LEN[1] + 1)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.integers(GEN_LEN[0],
                                                    GEN_LEN[1] + 1)),
                    rid=i)
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    report = eng.run(reqs)
    jax.block_until_ready(eng.cache)
    wall_s = time.perf_counter() - t0

    agg = report["aggregate"]
    for key in ("n_errored", "n_shed", "n_rejected"):
        if agg[key]:
            fail(f"served: {key} = {agg[key]}")
    budget = {r.rid: r.max_new_tokens for r in reqs}
    done = {r["rid"]: r for r in report["requests"]}
    if sorted(done) != sorted(budget):
        fail(f"served: requests {sorted(budget)} ended as {sorted(done)}")
    for rid, r in done.items():
        if r["status"] != "retired" or r["n_tokens"] != budget[rid]:
            fail(f"served: request {rid} {r['status']} with "
                 f"{r['n_tokens']}/{budget[rid]} tokens ({r['finish_reason']})")
    generated = sum(r["n_tokens"] for r in done.values())
    ttft = sorted(r["ttft_s"] for r in done.values())

    # the served prefill program's last-token logits: finite for every
    # prompt, and the longest prompt (4 chunks) held to the f32 reference
    served = [eng.prefill_logits(r.prompt) for r in reqs]
    for r, row in zip(reqs, served):
        if not np.all(np.isfinite(row)):
            fail(f"served: non-finite prefill logits for request {r.rid}")
    probe = max(range(N_REQUESTS), key=lambda i: len(reqs[i].prompt))
    ref = jax.jit(functools.partial(dense_reference_logits, cfg))(
        params, jnp.asarray(reqs[probe].prompt))[-1]
    ref = np.asarray(ref)
    if not np.all(np.isfinite(ref)):
        fail("served: non-finite reference logits")
    err = rel_err(served[probe], ref)
    emit("served", arch=cfg.name, n_layers=cfg.n_layers,
         compute_dtype=cfg.compute_dtype, decode_impl=cfg.decode_impl,
         n_slots=N_SLOTS, max_len=MAX_LEN, chunk=CHUNK,
         decode_ticks=DECODE_TICKS, n_requests=N_REQUESTS,
         compile_s=compile_s, wall_s=wall_s, generated_tokens=generated,
         tokens_per_s=generated / wall_s, ttft_median_s=ttft[len(ttft) // 2],
         ttft_max_s=ttft[-1], prefill_ref_prompt_len=len(reqs[probe].prompt),
         prefill_ref_rel_err=err, prefill_ref_tol=SERVED_REL_TOL)
    if not err <= SERVED_REL_TOL:
        fail(f"served: prefill logits off the f32 reference by {err}")


def swiftkv_phase(cfg, model, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import attention as attn
    from repro.core.quantization import quantize_kv
    from repro.models.api import build_model

    b, hkv, g, d, s = N_SLOTS, cfg.n_kv_heads, 4, cfg.resolved_head_dim, 2048
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(kq, (b, hkv * g, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
    k8, ks = quantize_kv(k.astype(jnp.float32))
    v8, vs = quantize_kv(v.astype(jnp.float32))
    # the cache's scale planes: position last, bf16
    ks = jnp.swapaxes(ks, 1, 2).astype(jnp.bfloat16)
    vs = jnp.swapaxes(vs, 1, 2).astype(jnp.bfloat16)
    window = 1024
    cases = {
        "linear": ((q, k, v, jnp.asarray([s, 1531, 700, 1], jnp.int32)), {}),
        # lengths past S: the ring has wrapped
        "ring": ((q, k, v, jnp.asarray([2 * s + 37, s + 500, 1800, 1],
                                       jnp.int32)),
                 {"ring": True, "window": window}),
        "int8": ((q, k8, v8, jnp.asarray([s, 1531, 700, 1], jnp.int32)),
                 {"k_scale": ks, "v_scale": vs}),
    }
    out = {}
    for name, (args, kw) in cases.items():
        def impl(name_):
            return jax.jit(functools.partial(attn.decode_attention,
                                             impl=name_, **kw))
        kern = impl("kernel").lower(*args).compile()
        require_kernel(kern, f"swiftkv_decode {name}")
        got = kern(*args)
        blockwise = impl("blockwise")(*args)
        with jax.default_matmul_precision("highest"):
            f32 = [a.astype(jnp.float32) for a in args[:3]]
            naive = impl("naive")(*f32, args[3])
        for other, ref in (("blockwise", blockwise), ("naive_f32", naive)):
            err = rel_err(got, ref)
            out[f"{name}_vs_{other}"] = err
            if not err <= ATTN_REL_TOL:
                fail(f"swiftkv_decode {name}: {err} off {other}")

    # one full-width decode step, kernel against blockwise, same cache
    cache = model.init_cache(N_SLOTS, MAX_LEN)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed + 2))
    cache["k"] = jax.random.normal(kk, cache["k"].shape, cache["k"].dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape, cache["v"].dtype)
    cache["len"] = jnp.asarray([2000, 1300, 517, 64], jnp.int32)
    tok = jnp.asarray([11, 222, 3333, 31999], jnp.int32)
    logits = {}
    for impl in ("blockwise", "kernel"):
        m = build_model(cfg.replace(decode_impl=impl))
        step = jax.jit(lambda p, t, c, m=m: m.decode_step(p, t, c)[0])
        compiled = step.lower(params, tok, cache).compile()
        if impl == "kernel":
            require_kernel(compiled, "decode_step(decode_impl='kernel')")
        logits[impl] = np.asarray(compiled(params, tok, cache))
        if not np.all(np.isfinite(logits[impl])):
            fail(f"decode_step {impl}: non-finite logits")
    err = rel_err(logits["kernel"], logits["blockwise"])
    emit("swiftkv_decode", batch=b, n_kv_heads=hkv, group=g, head_dim=d,
         cache_len=s, ring_window=window, rel_err=out, tol=ATTN_REL_TOL,
         decode_step_kernel_vs_blockwise_rel_err=err,
         decode_step_tol=DECODE_IMPL_REL_TOL)
    if not err <= DECODE_IMPL_REL_TOL:
        fail(f"decode_step: kernel logits {err} off blockwise")


def gemv_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.quantization import quantize_w4, w4a8_matmul_ref
    from repro.kernels.gemv_w4a8.ops import gemv_w4a8
    from repro.models.layers import linear

    out = {}
    key = jax.random.PRNGKey(seed + 3)
    for kdim, ndim in ((2560, 6912), (6912, 2560)):
        key, kw_, kx = jax.random.split(key, 3)
        w = jax.random.normal(kw_, (kdim, ndim), jnp.float32) * kdim ** -0.5
        qw = jax.jit(quantize_w4)(w)
        # activations already on the int8 grid (a row max of 127 times a
        # power of two), so both sides quantize them identically
        xq = jax.random.randint(kx, (8, kdim), -127, 128).at[:, 0].set(127)
        x = xq.astype(jnp.float32) * 2.0 ** -6
        kern = gemv_w4a8.lower(x, qw.packed, qw.scale).compile()
        require_kernel(kern, f"gemv_w4a8 {kdim}x{ndim}")
        want = jax.jit(w4a8_matmul_ref)(x, qw)
        err = rel_err(kern(x, qw.packed, qw.scale), want)
        out[f"{kdim}x{ndim}"] = err
        if not err <= GEMV_REL_TOL:
            fail(f"gemv_w4a8 {kdim}x{ndim}: {err} off w4a8_matmul_ref")
        if kdim == 2560:
            # the model's projection call site reaches the same kernel
            p = {"w__qp": qw.packed, "w__qs": qw.scale}
            lin = jax.jit(lambda p_, x_: linear(p_, "w", x_)).lower(
                p, x).compile()
            require_kernel(lin, "layers.linear on a W4A8 projection")
            err = rel_err(lin(p, x), want)
            out["linear_2560x6912"] = err
            if not err <= GEMV_REL_TOL:
                fail(f"layers.linear W4A8: {err} off w4a8_matmul_ref")
    emit("gemv_w4a8", rows=8, rel_err=out, tol=GEMV_REL_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the trace and the kernel inputs")
    args = ap.parse_args(argv)

    device = device_check()
    emit("device", **device)

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import build_model

    cache_dir = Path(enable_compile_cache())
    # compile_s below is cold unless this directory already held entries
    emit("compile_cache", dir=str(cache_dir),
         entries_before=len(list(cache_dir.glob("*")))
         if cache_dir.is_dir() else 0)
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)

    served_phase(cfg, model, params, args.seed)
    gc.collect()    # the engine's cache goes before the next phase's
    swiftkv_phase(cfg, model, params, args.seed)
    gemv_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
