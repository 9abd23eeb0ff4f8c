"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, device time per compiled program and per
operation, the idle gaps charged to what the host was doing, and the
harness's own spans.

Device planes are named ``/device:TPU:<n>``; on each, the ``XLA Modules``
line holds one event per program execution (named ``<program>(<id>)``) and
the ``XLA Ops`` line one event per operation. Host events (the harness's
``TraceAnnotation`` spans among them) are on ``/host:CPU``. All times are on
the profiler's one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

HARNESS_SPANS = ("engine.step", "harness.submit", "harness.wait")
# idle gaps shorter than this are the seams between one operation and the
# next; they count as idle but are not charged to a host event one by one
SEAM_NS = 10_000.0
_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    """``jit_block(123)`` -> ``jit_block``."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """An XLA op event is named by its HLO text: ``%fusion.3 = bf16[...]
    fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(ops) -> list[tuple[str, float, float]]:
    """(name, start, self time) of each op: its duration less that of the
    ops nested in it (a loop's body ops lie inside the loop op)."""
    out, stack = [], []          # stack: [name, start, end, child time]

    def close(entry):
        name, a, b, child = entry
        out.append((name, a, (b - a) - child))
        if stack:
            stack[-1][3] += b - a

    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no busy interval covers."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns),
             e) for e in line.events]


def read_planes(path: str | Path) -> dict:
    """Raw events by plane: ``devices`` maps a device plane to its module
    and op events, ``host`` lists every host event with its stats."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                "modules": [(n, a, b) for n, a, b, _ in
                            _events(lines["XLA Modules"])]
                if "XLA Modules" in lines else [],
                "ops": [(n, a, b) for n, a, b, _ in _events(lines["XLA Ops"])]
                if "XLA Ops" in lines else [],
            }
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for n, a, b, e in _events(line):
                    # only the harness's step spans carry stats it reads
                    host.append((n, a, b, dict(e.stats)
                                 if n == "engine.step" else {}))
    return {"devices": devices, "host": host}


def reduce(raw: dict, top: int = 10) -> dict | None:
    """Reduce one trace over the interval its ``engine.step`` spans cover.

    Returns ``None`` when the trace has no device plane or no step. Keys:
    ``interval`` (ns), ``window_s``, ``busy_s`` (mean over devices),
    ``programs`` {program: device seconds inside the interval, mean over
    devices}, ``program_calls`` {program: executions starting inside it},
    ``steps`` [(step index, start, end)], ``device_ops`` (ops by self time
    inside the interval, ``program/op``) and ``idle_gaps`` (idle time by
    what the host was doing), the ``breakdown`` lists."""
    steps = sorted((int(st.get("step", -1)), a, b)
                   for n, a, b, st in raw["host"] if n == "engine.step")
    if not raw["devices"] or not steps:
        return None
    lo, hi = min(a for _, a, _ in steps), max(b for _, _, b in steps)
    n_dev = len(raw["devices"])
    busy_s, programs, calls, ops, idle = 0.0, {}, {}, {}, {}
    host = _HostIndex(raw["host"])
    for dev in raw["devices"].values():
        evs = dev["ops"] or dev["modules"]
        busy = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_s += sum(b - a for a, b in busy) * 1e-9
        mods = sorted(dev["modules"], key=lambda e: e[1])
        starts = [a for _, a, _ in mods]
        for name, a, b in mods:
            p = program_name(name)
            dt = sum(y - x for x, y in clip([(a, b)], lo, hi)) * 1e-9
            programs[p] = programs.get(p, 0.0) + dt / n_dev
            if lo <= a < hi:
                calls[p] = calls.get(p, 0) + 1
        inside = [(n, a, b) for n, a, b in dev["ops"] if lo <= a < hi]
        for name, a, dt in self_times(inside):
            i = bisect.bisect_right(starts, a) - 1
            prog = (program_name(mods[i][0])
                    if i >= 0 and a < mods[i][2] else "?")
            key = f"{prog}/{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + dt * 1e-9 / n_dev
        for a, b in gaps(busy, lo, hi):
            label = ("seams between ops" if b - a < SEAM_NS
                     else host.label((a + b) / 2))
            idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9 / n_dev
    return {
        "interval": (lo, hi),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s / n_dev,
        "programs": programs,
        "program_calls": calls,
        "steps": steps,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }


class _HostIndex:
    """What the host was doing at a time: the innermost harness span, and
    the innermost other host event inside it."""

    def __init__(self, host):
        self.spans = sorted((a, b, n) for n, a, b, _ in host
                            if n in HARNESS_SPANS)
        self.inner = sorted((a, b, n) for n, a, b, _ in host
                            if n not in HARNESS_SPANS and b > a)
        self._span_starts = [a for a, _, _ in self.spans]
        self._inner_starts = [a for a, _, _ in self.inner]

    def label(self, t: float) -> str:
        # the harness's spans follow one another: only the latest to start
        # before t can cover it
        i = bisect.bisect_right(self._span_starts, t) - 1
        if i < 0 or t >= self.spans[i][1]:
            return "outside harness spans"
        a0, _, name = self.spans[i]
        best = None
        j = bisect.bisect_right(self._inner_starts, t) - 1
        while j >= 0 and self.inner[j][0] >= a0:
            a, b, n = self.inner[j]
            if b > t and (best is None or b - a < best[0]):
                best = (b - a, n)
            j -= 1
        return f"{name}/{best[1]}" if best else name


def find_trace(log_dir: str | Path) -> Path | None:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None
