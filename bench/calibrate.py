"""Readings from which a cell's limits are set; not part of a benchmark run.

  python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
      [--controls 3] [--witness 0] [--units 2] [--out readings.jsonl]

For each seed, in one process: the cell's set-up and ``--units`` calls of
its window, the program's state freed, then the comparison of what the
timed path produced with the float32 reference (the lower readings). On
the first ``--controls`` seeds also the controls, the reference put in the
program's place computed in bfloat16, and for training the fault of half of
each hospital's batch left out, the mean taken over the rest, read from the
reference put in the program's place (the upper readings). For training,
on the first ``--witness`` seeds also the reference against itself fed each
batch's rows in reverse order: how far rounding alone carries two sound
computations apart over the checked steps. One JSON line per seed and kind.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def readings(run, kind: str) -> dict:
    import jax.numpy as jnp

    if kind == "program":
        return run.check()
    if hasattr(run, "sample"):  # serving: the control answers the same requests
        reqs = run.yardstick()[0]
        return run.judged(run.readings(reqs, jnp.bfloat16))
    if kind == "half_batch":
        per = run.cfg["server_batch"] // run.cfg["hospitals"]
        return run.judged(run.readings(keep_rows=per // 2))
    if kind == "reordered":
        return run.judged(run.readings(reverse_rows=True))
    return run.judged(run.readings(jnp.bfloat16))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--units", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell, cfg, traffic, _ = harness.cell_files(bench, args.workload)
    device = harness.require_chips(cell["chips"])
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    runner = harness.load_module("runners", traffic["runner"])
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        run = runner.Run(cfg, traffic, seed)
        run.setup()
        for _ in range(args.units):
            run.unit()
        run.release()
        gc.collect()
        kinds = ["program"]
        train = traffic["runner"] == "train"
        if i < args.controls:
            kinds += ["control"] + (["half_batch"] if train else [])
        if train and i < args.witness:
            kinds += ["reordered"]
        for kind in kinds:
            rec = {"workload": args.workload, "seed": seed, "kind": kind,
                   "device": device["kind"], **readings(run, kind)}
            rec["seconds"] = time.perf_counter() - t
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
