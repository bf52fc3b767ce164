"""The program's own tracing, read from a profiler trace: the device self
time of each named scope and the program's host spans.

The program names its layers itself. Inside the compiled programs,
``jax.named_scope`` puts ``privacy_layer`` (with ``conv`` or ``conv_taps``
round each of its convolutions), ``guard``, ``trunk`` and ``optimizer``
into every op's ``op_name`` metadata; JAX names the trunk's backward
``transpose(jvp(trunk))``. On the host, ``fit`` and ``serve``
open ``repro.*`` spans: ``repro.fit.upload`` (once a call),
``repro.fit.dispatch`` and ``repro.fit.readout`` (once an epoch),
``repro.serve.release`` (once an offered request) and ``repro.serve.batch``
(once a batch). ``ServeReport.queue_wait_ms`` counts each answered
request's wait from its push to its pop.

A TPU op's event carries no op_name. The trace holds the optimized HLO of
each program it ran instead (the ``Hlo Proto`` stats of its metadata
plane, keyed by the program's name as the ``XLA Modules`` events give it),
so :func:`load` finds each op's scope path from the program running at the
op's time and the op's instruction name: never from an op's own name.
:func:`layer_times` reduces the events; :func:`readings` gives the
per-layer numbers.

Each reading is a per-layer metric of the benchmark: ``metrics/<name>.py``
returns its entry of :func:`readings` over ``run.Context``'s
``layer_times``, its traced calls' counts and the program's counters.

Run as a script on the chip, it measures one cell as ``run.py --trace 1``
does and prints the readings, the cell's per-layer metrics, the spans and
the device time under no scope of the program's as its last line:

  python3 bench/layer_trace.py --workload <cell> --seed <n> [--seconds <s>]
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

import tracing

SPAN_PREFIX = "repro."
HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"


@dataclasses.dataclass
class ScopedEvents(tracing.Events):
    # the op_name scope path of each op of ``ops``, in the same order;
    # "" where the trace does not say
    op_scopes: List[str] = dataclasses.field(default_factory=list)


# ------------------------------------------------- protobuf, read by hand
# The few fields read here, by number: XSpace.planes 1; XPlane.name 2,
# .event_metadata 4 and .stat_metadata 5 (map entries: key 1, value 2);
# XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .bytes_value 6;
# XStatMetadata.name 2; HloProto.hlo_module 1; HloModuleProto.computations
# 3; HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2.
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a serialized message: an int, or a
    memoryview for a length-delimited field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _text(buf) -> str:
    return bytes(buf).decode() if buf is not None else ""


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{program name: {instruction: op_name}}`` from the optimized HLO
    that the trace's metadata plane holds for each program it ran."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        stat_names = {}
        events = []
        for g, entry in _fields(plane):
            if g == 5:
                stat_names[_field(entry, 1, 0)] = _text(_field(_field(entry, 2, b""), 2))
            elif g == 4:
                events.append(_field(entry, 2, b""))
        for meta in events:
            for h, stat in _fields(meta):
                if h == 5 and stat_names.get(_field(stat, 1, 0)) == HLO_PROTO_STAT:
                    out[_text(_field(meta, 2))] = _instruction_op_names(_field(stat, 6, b""))
    return out


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    names = {}
    for f, comp in _fields(_field(hlo_proto, 1, b"")):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g == 2:
                op_name = _text(_field(_field(inst, 7, b""), 2))
                if op_name:
                    names[_text(_field(inst, 1))] = op_name
    return names


# ------------------------------------------------------------------ events
def load(path: str) -> ScopedEvents:
    """The trace's events, as :func:`tracing.load` reads them, with each
    device op's scope path."""
    ev = tracing.load(path)
    with open(path, "rb") as f:
        return with_scopes(ev, hlo_op_names(f.read()))


def with_scopes(ev: tracing.Events, programs: Dict[str, Dict[str, str]]) -> ScopedEvents:
    """Each op's scope path: the op_name of its instruction in the program
    whose execution holds the op's start."""
    starts = [s for _, s, _ in ev.modules]
    scopes = []
    for name, s, _ in ev.ops:
        k = bisect.bisect_right(starts, s) - 1
        module = ev.modules[k] if k >= 0 and s < ev.modules[k][2] else None
        ops = programs.get(module[0], {}) if module else {}
        scopes.append(ops.get(name.split(" = ")[0].lstrip("%"), ""))
    return ScopedEvents(ops=ev.ops, modules=ev.modules, host=ev.host, op_scopes=scopes)


def under(path: str, scope: str) -> bool:
    """Whether ``scope`` is one of the path's components, bare or inside
    transform wrappers (``vmap(guard)``, ``jvp(trunk)``)."""
    return any(re.fullmatch(r"(?:[\w.]+\()*" + re.escape(scope) + r"\)*", c)
               for c in path.split("/"))


def backward_of(path: str, scope: str) -> bool:
    """Whether a component of the path is ``scope`` under ``transpose(``:
    the ops of its backward."""
    return any(c.startswith("transpose(") and under(c, scope)
               for c in path.split("/"))


def self_times(ops: List[tracing.Interval], lo: int, hi: int) -> List[int]:
    """Each op's nanoseconds in the window less those of the ops nested
    directly inside it (a ``%while`` less its body's ops), so that no time is
    counted twice."""
    clipped = [tracing._clip(s, e, lo, hi) for _, s, e in ops]
    own = [c[1] - c[0] if c else 0 for c in clipped]
    order = sorted((i for i, c in enumerate(clipped) if c),
                   key=lambda i: (clipped[i][0], -clipped[i][1]))
    stack: List[int] = []
    for i in order:
        s, e = clipped[i]
        while stack and clipped[stack[-1]][1] < e:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@dataclasses.dataclass
class LayerTimes:
    window_s: float
    idle_s: float
    # device self seconds per op_name scope path ("" where the trace has none)
    scope_s: Dict[str, float]
    # per host span name (``repro.*``): count, seconds, device-idle seconds
    spans: Dict[str, Dict[str, float]]

    def device_s(self, include, exclude=lambda p: False) -> float:
        return sum(v for p, v in self.scope_s.items() if include(p) and not exclude(p))


def _busy_before(busy: List[Tuple[int, int]]):
    """``t -> `` the device's busy nanoseconds before ``t``, over ``busy``:
    sorted, disjoint intervals. Each query costs a bisection, so that a
    serving window's thousands of spans over its tens of thousands of
    device ops are read in well under a second."""
    starts = [s for s, _ in busy]
    done = [0]
    for s, e in busy:
        done.append(done[-1] + e - s)

    def before(t: int) -> int:
        k = bisect.bisect_right(starts, t) - 1
        return done[k] + min(busy[k][1], t) - busy[k][0] if k >= 0 else 0

    return before


def layer_times(ev: ScopedEvents) -> LayerTimes:
    """The device self time per scope path and the ``repro.*`` host spans'
    counts, durations and device-idle time, all clipped to ``bench.window``."""
    lo, hi = tracing.window_of(ev)
    scope_ns: Dict[str, int] = defaultdict(int)
    for path, ns in zip(ev.op_scopes, self_times(ev.ops, lo, hi)):
        if ns:
            scope_ns[path] += ns
    busy = tracing._union([c for _, s, e in ev.ops if (c := tracing._clip(s, e, lo, hi))])
    busy_before = _busy_before(busy)
    spans: Dict[str, Dict[str, float]] = {}
    for name, s, e in ev.host:
        if not name.startswith(SPAN_PREFIX) or (c := tracing._clip(s, e, lo, hi)) is None:
            continue
        covered = busy_before(c[1]) - busy_before(c[0])
        sp = spans.setdefault(name, {"count": 0, "seconds": 0.0, "idle_s": 0.0})
        sp["count"] += 1
        sp["seconds"] += (c[1] - c[0]) * 1e-9
        sp["idle_s"] += (c[1] - c[0] - covered) * 1e-9
    busy_ns = sum(e - s for s, e in busy)
    return LayerTimes(window_s=(hi - lo) * 1e-9, idle_s=(hi - lo - busy_ns) * 1e-9,
                      scope_s={k: v * 1e-9 for k, v in scope_ns.items()},
                      spans=spans)


def _per(total: float, n) -> Optional[float]:
    return total / n if n else None


def _span(lt: LayerTimes, name: str, key: str) -> float:
    return lt.spans.get(name, {}).get(key, 0.0)


def readings(lt: LayerTimes, totals: dict, counters: dict) -> Dict[str, Optional[float]]:
    """The per-layer numbers, by the names a benchmark entry would give
    them: ``None`` where the trace or the counters hold nothing to read."""
    import numpy as np

    steps = totals.get("steps")
    scoped = any(v for p, v in lt.scope_s.items() if p)

    def ms_per_step(include, exclude=lambda p: False):
        return _per(1e3 * lt.device_s(include, exclude), steps) if scoped else None

    fits = _span(lt, "repro.fit.upload", "count")
    releases = _span(lt, "repro.serve.release", "count")
    batches = _span(lt, "repro.serve.batch", "count")
    waits = counters.get("queue_wait_ms")
    return {
        "train.privacy_layer_device_ms_per_step": ms_per_step(
            lambda p: under(p, "privacy_layer"), lambda p: under(p, "guard")),
        "train.conv_device_ms_per_step": ms_per_step(lambda p: under(p, "conv")),
        "train.guard_device_ms_per_step": ms_per_step(lambda p: under(p, "guard")),
        "train.trunk_fwd_device_ms_per_step": ms_per_step(
            lambda p: under(p, "trunk"), lambda p: backward_of(p, "trunk")),
        "train.trunk_bwd_device_ms_per_step": ms_per_step(
            lambda p: backward_of(p, "trunk")),
        "train.optimizer_device_ms_per_step": ms_per_step(
            lambda p: under(p, "optimizer")),
        "train.upload_idle_ms_per_call": _per(
            1e3 * _span(lt, "repro.fit.upload", "idle_s"), fits),
        "serve.release_host_ms_per_request": _per(
            1e3 * _span(lt, "repro.serve.release", "seconds"), releases),
        "serve.batch_host_ms_per_batch": _per(
            1e3 * _span(lt, "repro.serve.batch", "seconds"), batches),
        "serve.release_idle_share": (
            100.0 * _span(lt, "repro.serve.release", "idle_s") / lt.idle_s
            if releases and lt.idle_s > 0 else None),
        "serve.queue_wait_p95_ms": float(np.percentile(waits, 95)) if waits else None,
    }


def _parse(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="untraced window whose counters are read")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    import json
    import sys

    import harness
    import run

    args = _parse(argv)
    bench = harness.load_benchmark()
    cell, cfg, traffic, _ = harness.cell_files(bench, args.workload)
    flops = harness.model_flops(cfg)
    layers = harness.layer_map(traffic["runner"])
    try:
        device = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    run.compile_cache(trace=1)
    runner = harness.load_module("runners", traffic["runner"]).Run(cfg, traffic, args.seed)
    runner.setup()
    harness.window(runner.unit, seconds=args.seconds)
    ctx = run.profile(runner, device, flops, layers, runner.counters())
    lt, totals = ctx.layer_times, ctx.totals
    metrics = {m["name"]: harness.load_module("metrics", m["name"]).read(ctx)
               for m in harness.metrics_for(bench, "per_layer", args.workload)}
    scopes = ("privacy_layer", "guard", "trunk", "optimizer")
    unscoped = sorted(((p, v) for p, v in lt.scope_s.items()
                       if not any(under(p, k) for k in scopes)), key=lambda kv: -kv[1])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "readings": readings(lt, totals, ctx.counters), "metrics": metrics,
        "totals": {k: v for k, v in totals.items() if k != "call_seconds"},
        "spans": lt.spans, "window_s": lt.window_s, "idle_s": lt.idle_s,
        "device_self_s": sum(lt.scope_s.values()),
        "unscoped_top_s": unscoped[:12],
    }), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    sys.path[:0] = [str(here), str(here.parent / "src")]
    sys.exit(main())
