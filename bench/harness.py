"""What every cell shares: finding a cell's files by name (its FLOP
counter and layer map among them), the chip check, the table of peaks, the
timed window and the result line."""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACED_CACHE = ".jax_cache_traced"  # under ROOT, git-ignored


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return read_json(ROOT / "BENCHMARK.json")


def find(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r}; known: "
                     f"{sorted(i['name'] for i in items)}")


def cell_files(bench: dict, workload: str):
    """``(cell, config, traffic, limits)`` of the named cell, each read from
    its own file: the configuration's ``file``, ``traffic/<traffic>.json``
    and ``limits/<cell>.json``."""
    cell = find(bench["workloads"], workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "config")
    cfg = read_json(ROOT / cfg_entry["file"])
    traffic = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = read_json(BENCH / "limits" / f"{workload}.json")
    return cell, cfg, traffic, limits


def model_flops(cfg: dict) -> dict:
    """The configuration's model FLOPs per sample (``train``, ``serve``,
    ...), from ``flops/<name>.py``'s ``model_flops(cfg["model"])``, where
    the configuration file gives ``"flops": "<name>"``. There is no default:
    a missing or unknown counter exits here, so that a run fails before its
    set-up and not after its window."""
    name = cfg.get("flops")
    if not name:
        raise SystemExit(f"bench: configuration {cfg.get('name')!r} names no FLOP "
                         f"counter (\"flops\": \"<name>\" for {BENCH / 'flops'}/<name>.py)")
    return load_module("flops", name).model_flops(cfg["model"])


def layer_map(runner: str) -> Dict[str, str]:
    """Program names to layers: ``layers.json``, and ``layers/<runner>.json``
    where that file exists. The runner's file may add names, not redefine
    them."""
    layers = read_json(BENCH / "layers.json")
    path = BENCH / "layers" / f"{runner}.json"
    if path.is_file():
        for name, layer in read_json(path).items():
            if layers.setdefault(name, layer) != layer:
                raise SystemExit(f"bench: {path} maps program {name!r} to {layer!r}; "
                                 f"layers.json maps it to {layers[name]!r}")
    return layers


def traced_cache_dir() -> Path:
    """The traced run's persistent compilation cache: a directory in the
    checkout named by a digest of every Python file of the program as
    imported (``repro``) and of the benchmark, so that two builds never
    share one."""
    import repro

    h = hashlib.sha256()
    for top in (*(Path(p).resolve() for p in repro.__path__), BENCH):
        for f in sorted(top.rglob("*.py")):
            h.update(str(f.relative_to(top.parent)).encode() + b"\0")
            h.update(f.read_bytes())
    return ROOT / TRACED_CACHE / h.hexdigest()[:16]


def metrics_for(bench: dict, kind: str, workload: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, imported by its path."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no file {path} ({kind} {name!r})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(chips: int) -> dict:
    """The device JAX found, or :class:`NoChip` when it is not a TPU with
    at least ``chips`` chips. There is no CPU fallback."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def peak(kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    table = read_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes on the fullest chip: the larger of what was in use and
    what the allocator held for programs' temporaries."""
    import jax

    best = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        best = max(best, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return best


def window(unit: Callable[[], dict], *, seconds: float = 0.0,
           calls: int = 1) -> Dict[str, float]:
    """Call ``unit()`` at least ``calls`` times and until ``seconds`` have
    passed; returns the summed counts of every call with ``"seconds"``, the
    summed host time of the calls themselves, and ``"call_seconds"``, the
    host time of each call."""
    totals: Dict[str, float] = {"seconds": 0.0}
    each: List[float] = []
    made = 0
    start = time.perf_counter()
    while made < calls or time.perf_counter() - start < seconds:
        t = time.perf_counter()
        counts = unit()
        each.append(time.perf_counter() - t)
        totals["seconds"] += each[-1]
        for k, v in counts.items():
            totals[k] = totals.get(k, 0) + v
        made += 1
    return {**totals, "call_seconds": each}


def emit(result: dict, checks: Dict[str, dict]) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, ``checks`` last in it."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps({**result, "checks": checks}), flush=True)


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """Each reading beside its limit; ``correct`` when every reading is
    finite and at most its limit, and every limit has a reading."""
    import math

    checks = {k: {"value": readings.get(k), "limit": lim} for k, lim in limits.items()}
    ok = all(c["value"] is not None and math.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
