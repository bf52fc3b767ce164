"""Benchmark harness — one function per paper table. CSV: name,us_per_call,derived.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only table7 kernel

A suite that raises is reported as a ``<tag>/ERROR`` row, the others still
run, and the harness exits non-zero. Alongside the CSV, machine-readable
JSON is written for the perf trajectories later PRs must not regress:

  BENCH_kernels.json — the kernel suite rows (written here)
  BENCH_trainer.json — fused-engine vs seed-loop steps/sec (written by
                       benchmarks.trainer_perf when the trainer suite runs)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

JSON_SUITES = {"kernel": "BENCH_kernels.json"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="substring filters (e.g. table1 kernel trainer roofline)")
    args = ap.parse_args(argv)

    from benchmarks import kernel_perf, paper_tables, roofline_report, trainer_perf

    suites = [
        ("table1", paper_tables.table1_layers_at_client),
        ("table5", paper_tables.table5_fl_vs_split),
        ("table6", paper_tables.table6_mura_parts),
        ("table7", paper_tables.table7_cholesterol),
        ("privacy", paper_tables.fig7_privacy_inversion),
        ("kernel", kernel_perf.bench_privacy_conv),
        ("kernel", kernel_perf.bench_dp_release),
        ("kernel", kernel_perf.bench_flash_attention),
        ("kernel", kernel_perf.bench_selective_scan),
        ("trainer", trainer_perf.bench_fused_vs_looped),
        ("roofline", roofline_report.rows_from_artifacts),
    ]

    by_tag: dict = {}
    failed = []
    print("name,us_per_call,derived")
    for tag, fn in suites:
        if args.only and not any(o in tag for o in args.only):
            continue
        t0 = time.time()
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}")
                by_tag.setdefault(tag, []).append(
                    {"name": name, "us_per_call": us, "derived": derived}
                )
        except Exception as e:  # report, run the other suites, exit non-zero
            failed.append(tag)
            traceback.print_exc()
            print(f"{tag}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stdout)
            # mark the JSON too, so a truncated suite can't pose as complete
            by_tag.setdefault(tag, []).append(
                {"name": f"{tag}/ERROR", "us_per_call": 0.0,
                 "derived": f"{type(e).__name__}:{e}"}
            )
        print(f"# {tag} finished in {time.time()-t0:.1f}s", file=sys.stderr)

    for tag, fname in JSON_SUITES.items():
        if tag in by_tag:
            with open(fname, "w") as f:
                json.dump({"suite": tag, "rows": by_tag[tag]}, f, indent=2)
            print(f"# wrote {fname}", file=sys.stderr)
    if failed:
        print(f"# suites failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
