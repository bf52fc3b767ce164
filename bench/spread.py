"""Spread of a cell's runs, from the result lines they printed.

  python3 bench/spread.py results.jsonl [more.jsonl ...]

Each file holds result lines (one JSON object a line, as ``run.py`` prints
them last). Per metric it prints the median, and the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a share
of the median, with the number of runs; and which runs were not correct."""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(paths) -> int:
    for path in paths:
        rows = [json.loads(line) for line in open(path) if line.startswith("{")]
        print(f"{path}: {len(rows)} runs, "
              f"{sum(not r['correct'] for r in rows)} not correct")
        names = sorted({m for r in rows for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in rows if m in r["metrics"]]
            s = spread(vals)
            print(f"  {m}: median {statistics.median(vals)!r} spread "
                  f"{'n/a' if s is None else repr(s)} over {len(vals)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
