"""The chip benchmark's harness: one cell, one seed, one measured window.

A cell of ``BENCHMARK.json`` pairs a configuration (``configs/<name>.json``)
with a traffic mix (``traffic/<name>.json``); its metrics are readers
(``metrics/<name>.py``) and its check's limit is ``checks/<cell>.json``.
Everything is found by name, so a new cell, mix, configuration or metric is
new files only.

A run:

1. set-up (``setup_s``, from process start): JAX on the chip, the persistent
   compilation cache inside the checkout, weights made on the device from
   the seed in one jitted call (``references/<ref>.make_params``, float32
   as the server stores them), the server's ``ContinuousBatchingEngine``
   built and warmed up on the cell's own programs;
2. the window, through the engine's public ``submit``/``step``: an open
   loop submits each request when it is due, times its first token from the
   due time, and drains the requests due in the window; a batch keeps the
   queue deeper than the pool and counts the tokens stamped in the window;
3. with ``--trace 1``, a profiler trace of the window's last seconds,
   reduced by ``trace_reduce``;
4. the metrics, by their readers;
5. the check (``check.py``): served tokens against the plain reference.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]
# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH / "out" / "trace"
TRACE_S = 3.0              # seconds of the window a traced run records
MODEL_KEYS_OF_CONFIG = ("family", "vocab_size", "d_model", "n_layers",
                        "n_heads", "n_kv_heads", "d_ff", "window", "act",
                        "gated_mlp", "qk_norm", "rope_base", "rotary_frac",
                        "norm_eps", "tie_embeddings", "compute_dtype")


class BenchError(Exception):
    """The run cannot be made: no chip, a missing file, a bad cell."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, from files
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<name>.json
    mix: dict               # traffic/<name>.json
    check: dict             # checks/<cell>.json
    end_to_end: list
    per_layer: list

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def pool(self) -> dict:
        return self.config["pool"]


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its data files
    from ``<root>/benchmarks/chip``."""
    import traffic
    data = root / BENCH.relative_to(ROOT)
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    mix = _read_json(data / "traffic" / f"{w['traffic']}.json")
    traffic.validate_mix(mix)
    if traffic.longest_request(mix) > config["pool"]["max_len"]:
        raise BenchError(f"{name}: the mix's longest request does not fit "
                         f"max_len {config['pool']['max_len']}")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                check=_read_json(data / "checks" / f"{name}.json"),
                end_to_end=e2e, per_layer=per)


def reader_path(metric: str) -> Path:
    """``metrics/<metric>.py``; a quantity split by a suffix for the cells
    that report different end-to-end metrics (``decode_tick_ms.open``,
    ``decode_tick_ms.batch``) falls back to its base name's reader."""
    name = metric
    while not (BENCH / "metrics" / f"{name}.py").is_file():
        if "." not in name:
            raise BenchError(f"no reader for {metric} in {BENCH / 'metrics'}")
        name = name.rsplit(".", 1)[0]
    return BENCH / "metrics" / f"{name}.py"


def load_reader(metric: str):
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(name: str):
    path = BENCH / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("chip_ref_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def start_jax(chips: int, *, require_tpu: bool = True) -> dict:
    """JAX with the compilation cache inside the checkout; the device, as
    JAX names it. Off a TPU, or with fewer chips than the cell asks for,
    raises ``BenchError``."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: the directory holds this benchmark's programs only
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU: JAX found {len(devices)} x "
                         f"{d.platform} ({d.device_kind!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()          # takes the directory set above
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def key_of(seed: int):
    """A PRNG key from any whole seed, 64 bits of it."""
    import jax
    import numpy as np
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def server_config(cell: Cell):
    """The server's config for the cell, checked against the sizes the
    configuration file states."""
    from repro.configs import get_config
    cfg = get_config(cell.config["arch"]).replace(**cell.config["overrides"])
    m = cell.model
    for k in MODEL_KEYS_OF_CONFIG:
        if getattr(cfg, k) != m[k]:
            raise BenchError(f"{cell.config['name']}: server {k}="
                             f"{getattr(cfg, k)!r}, file states {m[k]!r}")
    if cfg.resolved_head_dim != m["head_dim"]:
        raise BenchError("head_dim differs from the file")
    return cfg


def make_weights(cell: Cell, model, seed: int):
    """The cell's weights on the device, in one jitted call, in the
    server's parameter layout (checked against its own shapes)."""
    import jax
    import jax.numpy as jnp
    ref = load_reference(cell.config["reference"])
    m = cell.model
    made = jax.eval_shape(lambda k: ref.make_params(m, k), key_of(seed))
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    if jax.tree.structure(made) != jax.tree.structure(want):
        raise BenchError("weight layout differs from the server's")
    dtype = jnp.dtype(m["param_dtype"])
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype or a.dtype != dtype:
            raise BenchError(f"weight {a} differs from the server's {b}")
    params = jax.jit(lambda k: ref.make_params(m, k))(key_of(seed))
    jax.block_until_ready(params)
    return params


def build_engine(cell: Cell, seed: int):
    """Weights and a warmed-up engine for the cell."""
    import jax
    import numpy as np
    from repro.models.api import build_model
    from repro.serving import ContinuousBatchingEngine, Request
    t0 = time.perf_counter()
    cfg = server_config(cell)
    model = build_model(cfg)
    params = make_weights(cell, model, seed)
    t1 = time.perf_counter()
    p = cell.pool
    eng = ContinuousBatchingEngine(
        model, params, n_slots=p["n_slots"], max_len=p["max_len"],
        chunk=p["chunk"], decode_ticks=p["decode_ticks"], eos_id=None,
        temperature=0.0, seed=seed)
    eng.warmup()
    # the engine's warm-up prefills one row; the window finalizes up to
    # n_slots rows in one dispatch, so every row's first-token pick runs once
    eng.run([Request(prompt=np.zeros(p["chunk"] + 1, np.int32),
                     max_new_tokens=2 * p["decode_ticks"],
                     rid=f"__rows{i}__") for i in range(p["n_slots"])])
    jax.block_until_ready(eng.cache)
    log(f"set-up: weights {t1 - t0:.3f} s, engine and warm-up "
        f"{time.perf_counter() - t1:.3f} s")
    return params, eng


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Record:
    """One request of the window, on the host clock (perf_counter s)."""
    index: int
    prompt: object
    budget: int
    due: float
    state: object = None
    submit: float = math.nan

    def _abs(self, t, base):
        return math.nan if t is None else t + base

    def finish(self, base: float) -> None:
        st = self.state
        self.admit = self._abs(st.t_admit, base)
        self.times = [t + base for t in st.token_times]
        self.tokens = list(st.tokens)
        self.ok = (st.status == "retired" and len(self.tokens) == self.budget)
        self.errored = st.status == "errored"

    @property
    def n(self) -> int:
        return len(self.tokens)

    @property
    def ttft(self) -> float:
        return self.times[0] - self.due if self.ok else math.inf

    @property
    def tpot(self) -> float:
        if not self.ok:
            return math.inf
        return (self.times[-1] - self.times[0]) / max(1, self.n - 1)


class StepLog:
    """What each engine step did, read from the request states around it:
    prompt chunks advanced and decode tokens emitted per live row."""

    def __init__(self, eng):
        self.eng, self.steps = eng, []

    def before(self) -> None:
        s = self.eng.sched
        self._dec = [(st, len(st.tokens)) for st in s.decoding.values()]
        self._pre = [(st, st.prefilled)
                     for st in list(s.prefilling) + list(s.queue)]

    def after(self, index: int) -> None:
        decode, prefill = [], []       # (prompt len, token index, n), ...
        for st, n0 in self._dec:
            if len(st.tokens) > n0:
                decode.append((len(st.request.prompt), n0,
                               len(st.tokens) - n0))
        for st, p0 in self._pre:
            plen = len(st.request.prompt)
            if st.prefilled > p0:
                last = st.prefilled >= plen
                prefill.append((p0, st.prefilled - p0, last))
                if last and len(st.tokens) > 1:   # joined this step's block
                    decode.append((plen, 1, len(st.tokens) - 1))
        self.steps.append({"index": index, "decode": decode,
                           "prefill": prefill,
                           "ticks": max((n for *_, n in decode), default=0)})


@dataclass
class Window:
    records: list
    t0: float
    t1: float                       # end of the measured window
    t_end: float                    # end of the drain (open loop)
    counters: dict
    compiles: int
    steps: list | None = None
    trace: dict | None = None
    lags: list = field(default_factory=list)


COUNTERS = ("dispatches", "host_syncs", "decode_steps", "decode_dispatches",
            "prefill_dispatches", "active_row_steps", "issued_ticks",
            "parked_ticks")


def _counters(eng) -> dict:
    return {k: getattr(eng, k) for k in COUNTERS}


class CompileCount:
    """Traces, compiles and cache reads that JAX reports while counting."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n, self.on = 0, False
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in self.EVENTS:
            self.n += 1


def run_window(cell: Cell, eng, seed: int, seconds: float, *,
               trace: bool = False, trace_s: float = TRACE_S,
               compile_count=None, mix: dict | None = None) -> Window:
    """One measured window through ``eng``; ``mix`` replaces the cell's
    traffic (a sweep's rates). A traced window records its last
    ``trace_s`` seconds (and an open loop's drain)."""
    import jax
    import traffic
    from repro.serving import Request
    mix = mix or cell.mix
    vocab = cell.model["vocab_size"]
    base = eng._t0                  # the engine's clock: perf_counter - base
    steps = StepLog(eng) if trace else None
    ann = jax.profiler.TraceAnnotation if trace else (
        lambda *a, **k: nullcontext())
    trace_from = max(0.0, seconds - trace_s)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    tracing = False
    records: list[Record] = []
    c0 = _counters(eng)
    if compile_count is not None:
        compile_count.n, compile_count.on = 0, True

    def submit(rec: Record, now: float) -> None:
        rec.state = eng.submit(Request(prompt=rec.prompt,
                                       max_new_tokens=rec.budget),
                               now=now - base)
        rec.submit = now
        records.append(rec)

    def step(i: int, deadline) -> bool:
        if steps is not None:
            steps.before()
        with ann("engine.step", step=i):
            worked = eng.step(time.perf_counter() - base, deadline)
        if steps is not None:
            steps.after(i)
        return worked

    def start_trace(now: float) -> bool:
        if trace and not tracing and now >= t0 + trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            return True
        return tracing

    i = 0
    if mix["loop"] == "open":
        items = traffic.open_schedule(mix, seed, seconds, vocab)
        t0 = time.perf_counter()
        due = [Record(it.index, it.prompt, it.max_new_tokens, t0 + it.due)
               for it in items]
        n_due = 0
        while True:
            now = time.perf_counter()
            tracing = start_trace(now)
            if n_due < len(due) and due[n_due].due <= now:
                with ann("harness.submit"):
                    while n_due < len(due) and due[n_due].due <= now:
                        submit(due[n_due], now)
                        n_due += 1
            nxt = due[n_due].due if n_due < len(due) else None
            # as the engine's own run(): a due request with a free slot
            # waiting for it caps the decode block
            deadline = (nxt - base if nxt is not None and eng.pool.n_free
                        else None)
            worked = step(i, deadline)
            i += 1
            if not worked:
                if nxt is None:
                    break
                with ann("harness.wait"):
                    time.sleep(max(0.0, nxt - time.perf_counter()))
        t1 = t0 + seconds
        t_end = time.perf_counter()
    else:
        stream = traffic.BatchStream(mix, seed, vocab)
        depth = eng.pool.n_slots + int(mix["queue_depth"])
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            tracing = start_trace(now)
            with ann("harness.submit"):
                while (len(eng.sched.queue) + len(eng.sched.prefilling)
                       + len(eng.sched.decoding)) < depth:
                    it = stream.next()
                    submit(Record(it.index, it.prompt, it.max_new_tokens,
                                  time.perf_counter()), time.perf_counter())
            step(i, None)
            i += 1
        t1 = t_end = time.perf_counter()
    if compile_count is not None:
        compile_count.on = False
    counters = {k: v - c0[k] for k, v in _counters(eng).items()}
    reduced = None
    if tracing:
        import trace_reduce
        jax.profiler.stop_trace()
        reduced = trace_reduce.reduce(trace_reduce.read_planes(
            trace_reduce.find_trace(TRACE_DIR)))
    for r in records:
        r.finish(base)
    return Window(records=records, t0=t0, t1=t1, t_end=t_end,
                  counters=counters,
                  compiles=compile_count.n if compile_count else -1,
                  steps=steps.steps if steps else None, trace=reduced,
                  lags=[r.submit - r.due for r in records])


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def pct(values, q: float) -> float:
    """Exact nearest-rank percentile (the ceil(q n)-th smallest); an
    infinite value (a failed request) sorts last."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])


class RunContext:
    """What a metric reader reads. ``requests`` are the window's records;
    for an open loop all of them were due in the window and have drained.
    ``steps`` and ``trace`` exist in traced runs only."""

    def __init__(self, cell: Cell, win: Window, setup_s: float,
                 peak: dict):
        import roofline
        self.cell, self.win, self.setup_s, self.peak = cell, win, setup_s, peak
        self.model, self.roofline = cell.model, roofline
        self.n_slots = cell.pool["n_slots"]
        self.requests = win.records
        self.counters = win.counters
        self.trace = win.trace
        self.pct = pct

    @property
    def window_s(self) -> float:
        return self.win.t1 - self.win.t0

    def tokens_in_window(self) -> int:
        """Tokens stamped inside the measured window."""
        lo, hi = self.win.t0, self.win.t1
        return sum(1 for r in self.requests for t in r.times if lo <= t < hi)

    def tokens_emitted(self) -> int:
        """Tokens the engine emitted while the counters counted."""
        return sum(r.n for r in self.requests)

    def program_s(self, *prefixes: str) -> float | None:
        """Device seconds of programs whose name starts with a prefix, in
        the traced interval; None without a trace or such a program."""
        if not self.trace:
            return None
        hits = [s for p, s in self.trace["programs"].items()
                if p.startswith(prefixes)]
        return sum(hits) if hits else None

    def program_calls(self, *prefixes: str) -> int:
        if not self.trace:
            return 0
        return sum(n for p, n in self.trace["program_calls"].items()
                   if p.startswith(prefixes))

    def traced_steps(self) -> list:
        if not self.trace or self.win.steps is None:
            return []
        idx = {i for i, _, _ in self.trace["steps"]}
        return [s for s in self.win.steps if s["index"] in idx]

    def decode_work(self) -> tuple[int, float, float, int]:
        """(ticks, model flops, roofline seconds, ticks bound by memory) of
        the decode blocks of the traced steps; each tick's least time from
        its own live rows."""
        rl, m = self.roofline, self.model
        ticks, flops, bound, by_memory = 0, 0.0, 0.0, 0
        for s in self.traced_steps():
            ticks += s["ticks"]
            for t in range(s["ticks"]):
                pos = [p + j + t - 1 for p, j, n in s["decode"] if n > t]
                f, b = rl.decode_tick(m, pos)
                least, which = rl.bound_seconds(f, b, self.peak)
                flops, bound = flops + f, bound + least
                by_memory += which == "memory"
        return ticks, flops, bound, by_memory

    def prefill_flops(self) -> float:
        rl, m = self.roofline, self.model
        return sum(rl.prefill_chunk(m, off, n, last)[0]
                   for s in self.traced_steps()
                   for off, n, last in s["prefill"])


def read_metrics(ctx: RunContext, entries: list) -> dict:
    out = {}
    for m in entries:
        value = load_reader(m["name"]).read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])

