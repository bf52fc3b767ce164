"""The chip benchmark: one cell, one process.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the TPU (there is no CPU fallback: no chip, or fewer than the cell
asks for, exits non-zero with no result), reads the cell's files by name
(``BENCHMARK.json``; the configuration's file; ``traffic/<traffic>.json``;
``limits/<cell>.json``; the traffic's ``runners/<runner>.py``), sets up and
warms up, then measures.

Either way the window runs its calls for ``--seconds``, untraced.
``--trace 0`` reports the cell's end-to-end metrics from it. ``--trace 1``
then profiles the traffic's ``traced_calls`` more calls and reports the
per-layer metrics, each read by ``metrics/<name>.py`` from the reduced trace
of those calls and the program's counters of the untraced window, with the
device's busy and window seconds and a breakdown of device operations and
idle gaps.

Either way the program's state is then freed, the runner compares what
the timed path produced with the plain reference, and the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown``), the host seconds
of each call of the untraced window, then ``checks``, each number compared
beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Context:
    """What a per-layer metric reads: the reduced trace, the window's
    counts, the model's FLOPs per sample, the chip's peaks, and the map
    from program names to layers."""

    def __init__(self, reduced, totals, counters, flops, peak, layers):
        self.reduced, self.totals, self.counters = reduced, totals, counters
        self.flops, self.peak, self.layers = flops, peak, layers

    def programs_of(self, layer: str) -> dict:
        return {n: p for n, p in self.reduced.programs.items()
                if self.layers.get(n) == layer}


def traced(run, bench, workload, cfg, device, layers, counters):
    """Profile the traced calls; the per-layer metrics and the breakdown."""
    import tracing
    from flops.cnn import model_flops

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        totals, path = tracing.capture(
            lambda: harness.window(run.unit, calls=run.traced_units()), log_dir)
        reduced = tracing.reduce(tracing.load(path))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    ctx = Context(reduced, totals, counters, model_flops(cfg["model"]),
                  harness.peak(device["kind"]), layers)
    metrics = {}
    for m in harness.metrics_for(bench, "per_layer", workload):
        value = harness.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {**device, "busy_s": reduced.busy_s, "window_s": reduced.window_s}
    breakdown = {"device_ops": [list(x) for x in reduced.device_ops],
                 "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    return totals, metrics, device, breakdown


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_benchmark()
    cell, cfg, traffic, limits = harness.cell_files(bench, args.workload)
    try:
        device = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run = harness.load_module("runners", traffic["runner"]).Run(cfg, traffic, args.seed)
    run.setup()
    setup_s = time.perf_counter() - T_START
    result = {}
    totals = harness.window(run.unit, seconds=args.seconds)
    attempted, failed = run.outcome(totals)
    if args.trace:
        layers = harness.read_json(harness.BENCH / "layers.json")
        traced_totals, metrics, device, breakdown = traced(
            run, bench, args.workload, cfg, device, layers, run.counters())
        result["breakdown"] = breakdown
        more = run.outcome(traced_totals)
        attempted, failed = attempted + more[0], failed + more[1]
    else:
        values = {**run.end_to_end(totals), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in harness.metrics_for(bench, "end_to_end", args.workload)}
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    run.release()
    correct, checks = harness.judge(run.check(), limits)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **result,
              "call_seconds": totals["call_seconds"]}
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
