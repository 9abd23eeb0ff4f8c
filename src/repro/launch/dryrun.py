"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

Proves the distribution config is coherent without hardware: for every
(architecture x input-shape) cell, ``jit(step).lower(...).compile()`` must
succeed on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh, with
``memory_analysis()`` showing it fits and ``cost_analysis()`` + the optimized
HLO feeding the roofline terms.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out reports/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --all --reduced   # machinery smoke
"""
# The dry-run needs 512 placeholder devices; jax locks the device count on
# first init, so this MUST precede every other import (including repro.*).
# It is pinned to the CPU backend: on a machine with a TPU it must neither
# take the chip nor, through the per-cell child processes (which inherit
# this environment), contend for it.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import functools     # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms", "cpu")   # also when jax came in first

from repro.configs import ARCH_IDS, ASSIGNED_ARCHS, SHAPES, get_config  # noqa: E402
from repro.distributed import roofline  # noqa: E402
from repro.distributed.context import set_context  # noqa: E402
from repro.distributed.sharding import (MeshRules, batch_specs, cache_specs,  # noqa: E402
                                        fixup_divisibility, fixup_tree, named,
                                        param_specs)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.api import build_model, input_specs, needs_source  # noqa: E402
from repro.models.config import shape_applicable  # noqa: E402
from repro.optim import AdamWState, adamw_init  # noqa: E402
from repro.train import make_train_step  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _cost_dict(cost) -> dict:
    """``cost_analysis()`` as a dict (None on backends without one)."""
    return cost if isinstance(cost, dict) else {}


# ---------------------------------------------------------------------------
# Step builders: one lowered unit per shape kind
# ---------------------------------------------------------------------------

def build_cell(cfg, shape, mesh, *, microbatches: int = 1,
               train_opts: dict | None = None):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    rules = MeshRules(mesh)
    set_context(mesh, batch_axes=rules.batch_axes, model_axis="model")
    model = build_model(cfg)
    specs = input_specs(cfg, shape)
    params_shapes = jax.eval_shape(
        functools.partial(model.init_params, jax.random.PRNGKey(0)))
    if shape.kind != "train":
        # serving stores weights in the compute dtype (bf16); training keeps
        # f32 masters (the optimizer state) and casts at use.
        cdt = jnp.dtype(cfg.compute_dtype)
        params_shapes = jax.tree.map(
            lambda s: (jax.ShapeDtypeStruct(s.shape, cdt)
                       if s.dtype == jnp.float32 and s.ndim >= 2 else s),
            params_shapes)
        if cfg.w4a8_serve:
            from repro.models.quantized import quantize_params
            params_shapes = jax.eval_shape(quantize_params, params_shapes)

    if shape.kind == "train":
        bspecs = fixup_tree(batch_specs(cfg, shape, rules), specs, mesh)
        pspec = param_specs(params_shapes, rules, train=True)
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        ospec = AdamWState(step=P(), mu=pspec, nu=pspec)
        step = make_train_step(model, microbatches=microbatches,
                               param_specs=pspec, **(train_opts or {}))
        args = (params_shapes, opt_shapes, specs)
        in_sh = (named(pspec, mesh), named(ospec, mesh), named(bspecs, mesh))
        out_sh = (named(pspec, mesh), named(ospec, mesh), None)
        return step, args, in_sh, out_sh

    pspec = param_specs(params_shapes, rules, train=False)
    src_len = cfg.source_len if needs_source(cfg) else None
    if shape.kind == "prefill":
        cache_shapes = jax.eval_shape(functools.partial(
            model.init_cache, shape.global_batch, shape.seq_len, src_len))
        cspec = fixup_tree(cache_specs(cfg, shape, rules), cache_shapes, mesh)
        bspecs = fixup_tree(batch_specs(cfg, shape, rules), specs, mesh)

        def prefill_step(params, batch):
            b, s = batch["tokens"].shape
            cache = model.init_cache(b, s, src_len)
            cache = jax.lax.with_sharding_constraint(cache, named(cspec, mesh))
            logits, cache = model.prefill(params, batch["tokens"], cache,
                                          batch.get("source"))
            return logits, cache

        args = (params_shapes, specs)
        in_sh = (named(pspec, mesh), named(bspecs, mesh))
        out_sh = (None, named(cspec, mesh))
        return prefill_step, args, in_sh, out_sh

    # decode: serve_step — one token for every sequence in the batch
    cspec = fixup_tree(cache_specs(cfg, shape, rules), specs["cache"], mesh)
    tok_spec = fixup_divisibility(
        batch_specs(cfg, shape, rules)["tokens"],
        specs["tokens"].shape, mesh)

    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    args = (params_shapes, specs["tokens"], specs["cache"])
    in_sh = (named(pspec, mesh), named(tok_spec, mesh), named(cspec, mesh))
    out_sh = (None, named(cspec, mesh))
    return serve_step, args, in_sh, out_sh


# ---------------------------------------------------------------------------
# One cell: lower + compile + analyze
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             reduced: bool = False, microbatches: int | None = None,
             save_hlo: str | None = None, unroll: bool = False,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch, reduced=reduced)
    shape = SHAPES[shape_name]
    # ``unroll`` is the roofline COST pass: python-loop the layer stack,
    # single KV block, no microbatch scan — every loop XLA would cost once
    # is flattened, so flops/bytes/collectives are trip-count-true. The
    # scanned pass is the production program (memory/fits comes from it).
    # ``overrides`` feed the perf hillclimb.
    ov = dict(overrides or {})
    if unroll:
        ov.setdefault("attn_block", shape.seq_len)
    cfg = cfg.replace(unroll_layers=unroll, **ov)
    if microbatches is None:
        microbatches = 1 if unroll else (8 if shape.kind == "train" else 1)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    report = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "kind": shape.kind, "mode": "unroll" if unroll else "scan",
              "microbatches": microbatches, "ok": False}

    runs, reason = shape_applicable(cfg, shape)
    if not runs:
        report.update(skipped=True, reason=reason, ok=True)
        return report

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = MeshRules(mesh)
    n_chips = mesh.devices.size
    try:
        fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh,
                                             microbatches=microbatches)
        t0 = time.perf_counter()
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.perf_counter() - t0
            # platform-independent pre-partition costs: true-dtype bytes
            # (the CPU backend's bf16->f32 converts inflate compiled bytes)
            lca = _cost_dict(lowered.cost_analysis())
            t0 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        cost = _cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        if save_hlo:
            Path(save_hlo).write_text(hlo)

        bytes_per_chip = (mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes
                          + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes)
        # memory term: lowered (global, dtype-true) bytes spread over chips;
        # compute term: compiled per-chip FLOPs (includes padding waste)
        terms = {"flops": float(cost.get("flops", 0.0)),
                 "bytes accessed":
                     float(lca.get("bytes accessed", 0.0)) / n_chips}
        rep = roofline.analyze(
            arch, shape_name, mesh_name, n_chips, terms, hlo,
            bytes_per_chip=bytes_per_chip,
            model_flops=roofline.model_flops_for_cell(cfg, shape),
            tp_size=rules.tp_size)
        rep_extra = {
            "compiled_bytes_per_chip_gb":
                float(cost.get("bytes accessed", 0.0)) / 1e9,
            "lowered_global_gflops": float(lca.get("flops", 0.0)) / 1e9,
        }

        report.update(
            ok=True,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_gb": mem.argument_size_in_bytes / 1e9,
                "output_gb": mem.output_size_in_bytes / 1e9,
                "temp_gb": mem.temp_size_in_bytes / 1e9,
                "alias_gb": mem.alias_size_in_bytes / 1e9,
                "per_chip_gb": bytes_per_chip / 1e9,
                "fits_16gb": bytes_per_chip < 16e9,
            },
            roofline={**rep.row(), **rep_extra},
        )
    except Exception as e:  # a failure here is a bug in our sharding config
        report.update(ok=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-3000:])
    return report


def print_report(rep: dict):
    if rep.get("skipped"):
        print(f"[SKIP] {rep['arch']} x {rep['shape']} ({rep['mesh']}): "
              f"{rep['reason']}")
        return
    if not rep["ok"]:
        print(f"[FAIL] {rep['arch']} x {rep['shape']} ({rep['mesh']}): "
              f"{rep['error']}")
        return
    m, r = rep["memory"], rep["roofline"]
    print(f"[ OK ] {rep['arch']} x {rep['shape']} ({rep['mesh']} "
          f"{rep.get('mode', 'scan')}) lower={rep.get('lower_s', '-')}s "
          f"compile={rep.get('compile_s', '-')}s")
    if "argument_gb" in m:
        print(f"       mem/chip={m['per_chip_gb']:.2f} GB "
              f"(args={m['argument_gb']:.2f} temp={m['temp_gb']:.2f}; "
              f"fits 16GB: {m['fits_16gb']})")
    print(f"       t_compute={r['t_compute_ms']:.3f}ms "
          f"t_memory={r['t_memory_ms']:.3f}ms "
          f"t_collective={r['t_collective_ms']:.3f}ms "
          f"-> {r['dominant']}-bound; useful={100 * r['useful_frac']:.1f}% "
          f"roofline={100 * r['roofline_frac']:.1f}%")
    print(f"       collectives: {r['op_counts']}")


# ---------------------------------------------------------------------------
# Cost pass via layer-pair extrapolation
# ---------------------------------------------------------------------------

def _layer_pair(cfg) -> tuple[int, int, int]:
    """(L_small, L_big, L_full) preserving the arch's layer-group structure."""
    if cfg.cross_attn_every > 1:                 # vlm: groups of N layers
        g = cfg.cross_attn_every
        return g, 2 * g, cfg.n_layers
    return 2, 4, cfg.n_layers


def _cfg_with_layers(cfg, n_layers: int):
    kw = {"n_layers": n_layers}
    if cfg.encoder_layers:
        kw["encoder_layers"] = n_layers
    return cfg.replace(**kw)


def _extract_costs(cfg, shape, mesh, rules, microbatches=1,
                   train_opts=None):
    fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh,
                                         microbatches=microbatches,
                                         train_opts=train_opts)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh,
                          out_shardings=out_sh).lower(*args)
        lca = _cost_dict(lowered.cost_analysis())
        compiled = lowered.compile()
    cost = _cost_dict(compiled.cost_analysis())
    stats = roofline.parse_collectives(compiled.as_text(),
                                       default_group=rules.tp_size)
    return {
        "chip_flops": float(cost.get("flops", 0.0)),
        "global_bytes": float(lca.get("bytes accessed", 0.0)),
        "ici_bytes": stats.ici_bytes,
        "op_counts": dict(stats.op_counts),
        "op_bytes": dict(stats.op_bytes),
    }


def run_cost_cell(arch: str, shape_name: str, *, reduced: bool = False,
                  overrides: dict | None = None,
                  train_opts: dict | None = None) -> dict:
    """Roofline COST extraction: unrolled layers, single KV block, no
    microbatch scan — lowered at a small/big layer pair and extrapolated
    linearly to the full depth (per-layer cost is L-independent for these
    homogeneous stacks, so the extrapolation is exact; validated against
    full unrolls in EXPERIMENTS.md §Dry-run)."""
    cfg0 = get_config(arch, reduced=reduced)
    shape = SHAPES[shape_name]
    report = {"arch": arch, "shape": shape_name, "mesh": "16x16",
              "kind": shape.kind, "mode": "unroll-extrap", "ok": False}
    runs, reason = shape_applicable(cfg0, shape)
    if not runs:
        report.update(skipped=True, reason=reason, ok=True)
        return report

    ov = dict(overrides or {})
    ov.setdefault("attn_block", shape.seq_len)
    ov.setdefault("unroll_layers", True)
    cfg = cfg0.replace(**ov)
    l_small, l_big, l_full = _layer_pair(cfg)
    # encoder-decoder: scale both stacks; count total scaled layers
    denom_small = l_small * (2 if cfg.encoder_layers else 1)
    denom_big = l_big * (2 if cfg.encoder_layers else 1)
    denom_full = l_full * (2 if cfg.encoder_layers else 1)

    mesh = make_production_mesh(multi_pod=False)
    rules = MeshRules(mesh)
    try:
        t0 = time.perf_counter()
        c_small = _extract_costs(_cfg_with_layers(cfg, l_small), shape, mesh,
                                 rules, train_opts=train_opts)
        c_big = _extract_costs(_cfg_with_layers(cfg, l_big), shape, mesh,
                               rules, train_opts=train_opts)
        wall = time.perf_counter() - t0

        def extrap(key):
            delta = ((c_big[key] - c_small[key])
                     / (denom_big - denom_small))
            return c_big[key] + delta * (denom_full - denom_big)

        flops = extrap("chip_flops")
        gbytes = extrap("global_bytes")
        ici = extrap("ici_bytes")
        scale_counts = (denom_full - denom_big) / (denom_big - denom_small)
        op_counts = {
            k: int(round(c_big[k2] if False else c_big["op_counts"].get(k, 0)
                         + (c_big["op_counts"].get(k, 0)
                            - c_small["op_counts"].get(k, 0)) * scale_counts))
            for k in set(c_big["op_counts"]) | set(c_small["op_counts"])}

        n_chips = mesh.devices.size
        rep = roofline.RooflineReport(
            arch=arch, shape=shape_name, mesh="16x16", n_chips=n_chips,
            hlo_flops=flops, hlo_bytes=gbytes / n_chips,
            collective_op_bytes=0, collective_ici_bytes=ici,
            bytes_per_chip=0.0,
            model_flops=roofline.model_flops_for_cell(cfg0, shape),
            op_counts=op_counts).finalize()
        op_bytes = {
            k: (c_big["op_bytes"].get(k, 0)
                + (c_big["op_bytes"].get(k, 0)
                   - c_small["op_bytes"].get(k, 0)) * scale_counts)
            for k in set(c_big["op_bytes"]) | set(c_small["op_bytes"])}
        report.update(ok=True, compile_s=round(wall, 2),
                      layer_pair=[l_small, l_big, l_full],
                      memory={"per_chip_gb": float("nan"),
                              "fits_16gb": None},
                      roofline=rep.row(),
                      op_gbytes={k: round(v / 1e9, 3)
                                 for k, v in op_bytes.items()})
    except Exception as e:
        report.update(ok=False, error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-3000:])
    return report


# ---------------------------------------------------------------------------
# --all driver: every cell in a fresh subprocess (memory isolation)
# ---------------------------------------------------------------------------

def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ASSIGNED_ARCHS for s in SHAPES]


def run_all(out_dir: Path, *, reduced: bool, timeout: int = 3600,
            archs=None, shapes=None):
    """Three passes per cell: (16x16, scan), (2x16x16, scan) — the multi-pod
    lowering proof — and (16x16, unroll) — the roofline-term extraction."""
    out_dir.mkdir(parents=True, exist_ok=True)
    results = []
    passes = [(False, False), (True, False), (False, True)]  # (mp, unroll)
    for arch, shape in all_cells():
        if archs and arch not in archs:
            continue
        if shapes and shape not in shapes:
            continue
        for mp, unroll in passes:
            tag = (f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                   f"{'__unroll' if unroll else ''}")
            fout = out_dir / f"{tag}.json"
            if fout.exists():
                rep = json.loads(fout.read_text())
                if rep.get("ok"):
                    results.append(rep)
                    print(f"[CACHED] {tag}")
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--json", str(fout)]
            if mp:
                cmd.append("--multi-pod")
            if unroll:
                cmd.append("--cost")   # layer-pair extrapolated cost pass
            if reduced:
                cmd.append("--reduced")
            t0 = time.perf_counter()
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=timeout)
                rep = (json.loads(fout.read_text()) if fout.exists() else
                       {"arch": arch, "shape": shape, "ok": False,
                        "mesh": "2x16x16" if mp else "16x16",
                        "mode": "unroll" if unroll else "scan",
                        "error": proc.stderr[-2000:]})
            except subprocess.TimeoutExpired:
                rep = {"arch": arch, "shape": shape, "ok": False,
                       "mesh": "2x16x16" if mp else "16x16",
                       "mode": "unroll" if unroll else "scan",
                       "error": f"timeout after {timeout}s"}
                fout.write_text(json.dumps(rep, indent=1))
            rep.setdefault("wall_s", round(time.perf_counter() - t0, 1))
            results.append(rep)
            print_report(rep)
    summarize(results, out_dir)
    return results


def summarize(results: list[dict], out_dir: Path):
    ok = sum(1 for r in results if r.get("ok") and not r.get("skipped"))
    skip = sum(1 for r in results if r.get("skipped"))
    fail = sum(1 for r in results if not r.get("ok"))
    print(f"\n=== dry-run summary: {ok} ok, {skip} skipped, {fail} failed "
          f"of {len(results)} ===")
    (out_dir / "summary.json").write_text(json.dumps(results, indent=1))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", help="architecture id (e.g. qwen3-8b)")
    ap.add_argument("--shape", choices=list(SHAPES), help="input-shape cell")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs (machinery smoke test)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer stack (full-depth cost pass)")
    ap.add_argument("--cost", action="store_true",
                    help="layer-pair extrapolated cost pass (fast)")
    ap.add_argument("--override", nargs="*", default=[],
                    help="cost pass: ModelConfig overrides, k=v (hillclimb)")
    ap.add_argument("--bf16-gather", action="store_true",
                    help="cost pass: bf16 FSDP all-gathers (hillclimb)")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--json", help="write the cell report to this path")
    ap.add_argument("--save-hlo", help="dump optimized HLO text to this path")
    ap.add_argument("--out", default="reports/dryrun",
                    help="--all: output directory")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--archs", nargs="*", help="--all: restrict archs")
    ap.add_argument("--shapes", nargs="*", help="--all: restrict shapes")
    args = ap.parse_args()

    if args.all:
        run_all(Path(args.out), reduced=args.reduced, timeout=args.timeout,
                archs=args.archs, shapes=args.shapes)
        return

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    overrides = {}
    for kv in (args.override or []):
        k, v = kv.split("=", 1)
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        elif v.lstrip("-").isdigit():
            v = int(v)
        overrides[k] = v
    topts = {"bf16_gather": True} if args.bf16_gather else None
    if args.cost:
        rep = run_cost_cell(args.arch, args.shape, reduced=args.reduced,
                            overrides=overrides, train_opts=topts)
    else:
        rep = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                       reduced=args.reduced, microbatches=args.microbatches,
                       save_hlo=args.save_hlo, unroll=args.unroll,
                       overrides=overrides)
    print_report(rep)
    if args.json:
        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json).write_text(json.dumps(rep, indent=1))
    sys.exit(0 if rep["ok"] else 1)


if __name__ == "__main__":
    main()
