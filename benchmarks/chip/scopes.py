"""The decode program's time by named scope, and the device's idle time by
the engine's phase span, from a run's ``jax.profiler`` trace.

The server wraps its decode program's sublayers in ``jax.named_scope``
(``SCOPES``) and each phase of an engine step in a profiler span
(``serve.*``). A device op's scope is in its ``tf_op`` stat, JAX's
``op_name`` path (``jit(block)/while/body/.../attention/dot_general``),
which the trace keeps on the op's *event metadata*, not on its events;
``jax.profiler.ProfileData`` does not expose metadata stats, so
``op_paths`` reads them from the ``.xplane.pb`` protobuf wire format
directly. The rest reuses ``trace_reduce``: its events, its interval, its
self times and its idle gaps.

A program without scopes (every op ``unscoped``) or without ``serve.*``
spans gives readings the readers turn into ``None``.
"""
from __future__ import annotations

import bisect
import functools
import re
from pathlib import Path

import harness
import trace_reduce as tr

SCOPES = ("embed", "qkv", "kv_write", "attention", "attn_out", "mlp",
          "lm_head", "sample")
MATMUL_SCOPES = ("qkv", "attn_out", "mlp", "lm_head")
UNSCOPED = "unscoped"
PROGRAM = "jit_block"
SPAN_PREFIX = "serve."
DECODE_SPAN = "serve.decode"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


# ---------------------------------------------------------------------------
# protobuf wire format: XSpace -> XPlane.event_metadata -> XStat
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _stat(buf, names: dict):
    """(stat name, value) of one ``XStat``; a ``ref_value`` resolves to the
    name of the stat metadata it points at."""
    mid, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f in (3, 4):                   # uint64, int64
            value = v
        elif f in (5, 6):                   # str, bytes
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:                        # ref to a stat metadata's name
            value = names.get(v)
    return names.get(mid), value


def op_paths(path) -> dict:
    """``{device plane: {(program id, op name): tf_op path}}`` from the
    event metadata of every TPU device plane of an ``.xplane.pb``. The op
    name is the event's full name (its HLO text); the program id is the
    one a program's module event carries (``jit_block(<id>)``)."""
    data = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(data):
        if f != 1:                          # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:                   # map<int64, XEventMetadata>
                metas.append(v)
            elif pf == 5:                   # map<int64, XStatMetadata>
                for ef, ev in _fields(v):
                    if ef == 2:
                        m = dict(_fields(ev))
                        stat_names[m.get(1, 0)] = bytes(
                            m.get(2, b"")).decode()
        if not name.startswith("/device:TPU:") or "Core" in name:
            continue
        ops = out.setdefault(name, {})
        for entry in metas:
            for ef, ev in _fields(entry):
                if ef != 2:
                    continue
                op, stats = "", {}
                for mf, mv in _fields(ev):
                    if mf == 2:
                        op = bytes(mv).decode("utf-8", "replace")
                    elif mf == 5:
                        k, val = _stat(mv, stat_names)
                        stats[k] = val
                if stats.get("tf_op"):
                    ops[(stats.get("program_id"), op)] = stats["tf_op"]
    return out


def scope_of(tf_op: str | None) -> str:
    """The scope named by a component of a ``tf_op`` path (the innermost,
    were there several), else ``unscoped``. A component matches only when
    it equals a scope's name: ``decode_attention`` is not ``attention``."""
    if not tf_op:
        return UNSCOPED
    parts = [p.split(":", 1)[0] for p in tf_op.split("/")]
    for p in reversed(parts):
        if p in SCOPES:
            return p
    return UNSCOPED


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce(raw: dict, paths: dict, interval: tuple[float, float]) -> dict:
    """Over ``interval`` (ns, ``trace_reduce.reduce``'s): the self time of
    ``jit_block``'s ops by scope (``scopes``, seconds, mean over devices,
    ``unscoped`` included), ``scoped`` (whether any op had a scope), the
    device idle time whose midpoint falls in each ``serve.*`` span
    (``span_idle``, seconds; gaps shorter than ``trace_reduce.SEAM_NS``
    are seams, charged to none) and the ``serve.decode`` spans that start
    in the interval (``decode_spans``)."""
    lo, hi = interval
    n_dev = max(1, len(raw["devices"]))
    scopes: dict[str, float] = {}
    idle: dict[str, float] = {}
    spans = sorted((a, b, n) for n, a, b, _ in raw["host"]
                   if n.startswith(SPAN_PREFIX) and b > a)
    starts = [a for a, _, _ in spans]
    for dev, events in raw["devices"].items():
        known = paths.get(dev, {})
        mods = sorted(events["modules"], key=lambda e: e[1])
        mod_starts = [a for _, a, _ in mods]
        inside = [(n, a, b) for n, a, b in events["ops"] if lo <= a < hi]
        for name, a, dt in tr.self_times(inside):
            i = bisect.bisect_right(mod_starts, a) - 1
            if i < 0 or a >= mods[i][2] or \
                    tr.program_name(mods[i][0]) != PROGRAM:
                continue
            m = _PROGRAM_ID.search(mods[i][0])
            pid = int(m.group(1)) if m else None
            scope = scope_of(known.get((pid, name)))
            scopes[scope] = scopes.get(scope, 0.0) + dt * 1e-9 / n_dev
        busy = tr.union(tr.clip([(a, b) for _, a, b in
                                 events["ops"] or events["modules"]], lo, hi))
        for a, b in tr.gaps(busy, lo, hi):
            if b - a < tr.SEAM_NS:
                continue
            mid = (a + b) / 2
            j = bisect.bisect_right(starts, mid) - 1
            if j >= 0 and mid < spans[j][1]:
                n = spans[j][2]
                idle[n] = idle.get(n, 0.0) + (b - a) * 1e-9 / n_dev
    return {"scopes": scopes,
            "scoped": any(s != UNSCOPED for s in scopes),
            "span_idle": idle,
            "decode_spans": sum(1 for a, _, n in spans
                                if n == DECODE_SPAN and lo <= a < hi)}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int,
                 interval: tuple[float, float]) -> dict:
    return reduce(tr.read_planes(path), op_paths(path), interval)


def of(ctx) -> dict | None:
    """The reduction of this run's trace (read once a run), or ``None``
    without a trace."""
    if not ctx.trace:
        return None
    path = tr.find_trace(harness.TRACE_DIR)
    if path is None:
        return None
    return _reduce_file(str(path), path.stat().st_mtime_ns,
                        tuple(ctx.trace["interval"]))


def scope_s(ctx, *names: str) -> float | None:
    """Device seconds of ``jit_block``'s ops in the given scopes, in the
    traced interval; ``None`` without a trace, when the program has no
    scopes at all, or when none of these ran."""
    r = of(ctx)
    if not r or not r["scoped"]:
        return None
    t = sum(r["scopes"].get(n, 0.0) for n in names)
    return t or None


def tick_rows(ctx):
    """The 0-based positions the live rows feed, for each decode tick of
    the traced steps (``RunContext.decode_work``'s ticks, in its order)."""
    for s in ctx.traced_steps():
        for t in range(s["ticks"]):
            yield [p + j + t - 1 for p, j, n in s["decode"] if n > t]


def span_args(path, name: str, key: str,
              interval: tuple[float, float]) -> list:
    """The argument ``key`` of each ``name`` span that starts in
    ``interval``, in order (``trace_reduce.read_planes`` keeps no span
    arguments)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    lo, hi = interval
    found = sorted((e.start_ns, dict(e.stats).get(key))
                   for plane in pd.planes if plane.name.startswith("/host")
                   for line in plane.lines for e in line.events
                   if e.name == name and lo <= e.start_ns < hi)
    return [v for _, v in found]
