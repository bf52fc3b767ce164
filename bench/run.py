"""The chip benchmark: one cell, one process.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell's files by name (``BENCHMARK.json``; the configuration's
file and the FLOP counter it names, ``flops/<name>.py``;
``traffic/<traffic>.json``; ``limits/<cell>.json``; ``layers.json`` and
``layers/<runner>.json``; the traffic's ``runners/<runner>.py``), finds the
TPU (there is no CPU fallback: no chip, or fewer than the cell asks for,
exits non-zero with no result), sets up and warms up, then measures.

Either way the window runs its calls for ``--seconds``, untraced.
``--trace 0`` reports the cell's end-to-end metrics from it. ``--trace 1``
keeps its compiled programs in a cache of its own (``compile_cache``), then
profiles the traffic's ``traced_calls`` more calls and reports the
per-layer metrics, each read by ``metrics/<name>.py`` from the reduced trace
of those calls, the program's named scopes and ``repro.*`` spans in it, and
the program's counters of the untraced window, with the device's busy and
window seconds and a breakdown of device operations and idle gaps.

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
    """What a per-layer metric reads: the reduced trace; the program's own
    layers in it (``layer_times.scope_s``, device self seconds per named
    scope path, and ``layer_times.spans``, the ``repro.*`` host spans); the
    traced calls' counts; the program's counters of the untraced window;
    the model's FLOPs per sample, by the configuration's counter; the
    chip's peaks; and the map from program names to layers."""

    def __init__(self, reduced, layer_times, totals, counters, flops, peak, layers):
        self.reduced, self.layer_times = reduced, layer_times
        self.totals, self.counters = totals, counters
        self.flops, self.peak, self.layers = flops, peak, layers

    def programs_of(self, layer: str) -> dict:
        return {n: p for n, p in self.reduced.programs.items()
                if self.layers.get(n) == layer}


def profile(run, device, flops, layers, counters) -> Context:
    """Profile the traffic's traced calls; what their metrics read."""
    import layer_trace
    import tracing

    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        totals, path = tracing.capture(
            lambda: harness.window(run.unit, calls=run.traced_units()), log_dir)
        ev = layer_trace.load(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return Context(tracing.reduce(ev), layer_trace.layer_times(ev), totals, counters,
                   flops, harness.peak(device["kind"]), layers)


def traced(ctx, bench, workload, device):
    """The cell's per-layer metrics, the device's busy and window seconds,
    and the breakdown, from a profiled window."""
    metrics = {}
    for m in harness.metrics_for(bench, "per_layer", workload):
        value = harness.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {**device, "busy_s": ctx.reduced.busy_s, "window_s": ctx.reduced.window_s}
    breakdown = {"device_ops": [list(x) for x in ctx.reduced.device_ops],
                 "idle_gaps": [list(x) for x in ctx.reduced.idle_gaps]}
    return metrics, device, breakdown


def compile_cache(trace: int) -> None:
    """Where JAX keeps its persistent compilation cache. The untraced run
    keeps it where the program puts it. The traced run keeps one of its
    own, named by the code it runs (``harness.traced_cache_dir``): the
    cache's key is taken after the debug information is stripped, so a
    program that differs from another build's by its named scopes alone
    would load that build's executable and HLO from a shared cache, and the
    scope metrics would read its names. The cache stays on: with it turned
    off, each MURA ``fit`` call runs 60 ms slower on a v5e (PERF.md)."""
    if trace:
        import jax

        jax.config.update("jax_compilation_cache_dir", str(harness.traced_cache_dir()))
    else:
        from repro.launch.compile_cache import configure_compile_cache

        configure_compile_cache()


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_benchmark()
    cell, cfg, traffic, limits = harness.cell_files(bench, args.workload)
    flops = harness.model_flops(cfg)
    layers = harness.layer_map(traffic["runner"])
    try:
        device = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    compile_cache(args.trace)
    run = harness.load_module("runners", traffic["runner"]).Run(cfg, traffic, args.seed)
    run.setup()
    setup_s = time.perf_counter() - T_START
    result = {}
    totals = harness.window(run.unit, seconds=args.seconds)
    attempted, failed = run.outcome(totals)
    if args.trace:
        ctx = profile(run, device, flops, layers, run.counters())
        metrics, device, result["breakdown"] = traced(ctx, bench, args.workload, device)
        more = run.outcome(ctx.totals)
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
