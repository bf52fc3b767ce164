"""Profiler traces: capture a window on the chip, and reduce it to events.

``capture`` runs a callable under ``jax.profiler.trace`` inside a host span
named ``bench.window``. ``load`` reads the ``.xplane.pb`` it wrote into an
:class:`Events` record: the device's operations and program executions
(first TPU plane; its "XLA Ops" and "XLA Modules" lines) and every host
span, all on the trace's one clock in nanoseconds. ``testdata/`` keeps a
recorded excerpt as the JSON of those three lists.

The reduction is plain interval arithmetic over those records; every
per-layer metric reads it through :func:`reduce`.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
_MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")

Interval = Tuple[str, int, int]  # name, start ns, end ns


@dataclasses.dataclass
class Events:
    ops: List[Interval]
    modules: List[Interval]
    host: List[Interval]

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(**{k: [tuple(e) for e in d[k]] for k in ("ops", "modules", "host")})


def capture(fn, log_dir: str):
    """Run ``fn()`` under the profiler; returns ``(fn's result, xplane path)``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer slows the host drive
    with jax.profiler.trace(log_dir, profiler_options=opts):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            result = fn()
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    return result, found[-1]


def load(path: str) -> Events:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Interval] = []
    modules: List[Interval] = []
    host: List[Interval] = []
    device_seen = False
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not device_seen:
            device_seen = True  # one chip: the first TPU plane
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return Events(ops=sorted(ops, key=lambda e: e[1]),
                  modules=sorted(modules, key=lambda e: e[1]),
                  host=sorted(host, key=lambda e: e[1]))


def module_base(name: str) -> str:
    """``jit__run_epoch_scan(1459...)`` -> ``_run_epoch_scan``."""
    return _MODULE_NAME.match(name).group(1)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Tuple[int, int]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    # per program base name: executions and device seconds in the window
    programs: Dict[str, Dict[str, float]]
    # device-idle gaps between consecutive executions, per program base name
    program_gaps_s: Dict[str, List[float]]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def executions(self) -> int:
        return int(sum(p["count"] for p in self.programs.values()))


def window_of(ev: Events) -> Tuple[int, int]:
    spans = [(s, e) for n, s, e in ev.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(ev: Events, top: int = 10) -> Reduced:
    """Busy time (union of device operations), per-program device time and
    counts, the gaps between consecutive executions of each program, the
    device operations that took most time, and the longest idle gaps named
    by the innermost benchmark span (``bench.*``) and the innermost other
    host span over their midpoint; all clipped to the ``bench.window``
    span."""
    lo, hi = window_of(ev)
    busy = _union([c for _, s, e in ev.ops if (c := _clip(s, e, lo, hi))])
    busy_ns = sum(e - s for s, e in busy)

    programs: Dict[str, Dict[str, float]] = {}
    last_end: Dict[str, int] = {}
    gaps: Dict[str, List[float]] = {}
    for name, s, e in ev.modules:
        if (c := _clip(s, e, lo, hi)) is None:
            continue
        base = module_base(name)
        p = programs.setdefault(base, {"count": 0, "device_s": 0.0})
        p["count"] += 1
        p["device_s"] += (c[1] - c[0]) * 1e-9
        if base in last_end:
            gaps.setdefault(base, []).append(max(0, s - last_end[base]) * 1e-9)
        last_end[base] = e

    per_op: Dict[str, int] = {}
    for name, s, e in ev.ops:
        if (c := _clip(s, e, lo, hi)) is not None:
            key = name.split(" = ")[0]
            per_op[key] = per_op.get(key, 0) + c[1] - c[0]
    device_ops = sorted(((k, v * 1e-9) for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:top]

    idle = []
    edges = [lo] + [x for se in busy for x in se] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            idle.append((s, e))
    idle.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in idle[:top]:
        mid = (s + e) // 2
        over = [(he - hs, n) for n, hs, he in ev.host
                if hs <= mid <= he and n != WINDOW_SPAN]
        ours = [o for o in over if o[1].startswith("bench.")]
        theirs = [o for o in over if not o[1].startswith("bench.")]
        parts = [min(x)[1] for x in (ours, theirs) if x]
        named.append((" > ".join(parts) or "host: no span", (e - s) * 1e-9))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   programs=programs, program_gaps_s=gaps,
                   device_ops=device_ops, idle_gaps=named)


def load_json(path: str) -> Events:
    with open(path) as f:
        return Events.from_json(json.load(f))
