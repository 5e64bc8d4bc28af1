"""Spread of each metric over a set of result lines, as the bounds are set:
the distance between the first and third quartile of
``statistics.quantiles(values, n=4)``, as a share of the median.

    python3 chipbench/tools/spread.py set1.jsonl [set2.jsonl ...]

Each file holds the last stdout line of one run per line.  Prints, per
file and metric, the median, the spread and the values; then per metric
the widest spread over the files and five times it.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else float("nan")


def main() -> None:
    widest: dict = {}
    for path in sys.argv[1:]:
        runs = [json.loads(line) for line in open(path) if line.startswith("{")]
        print(f"{path}: {len(runs)} runs, correct "
              f"{sum(r['correct'] for r in runs)}")
        names = sorted({k for r in runs for k in r["metrics"]})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            if len(vals) < 2:
                continue
            s = spread(vals)
            widest[name] = max(widest.get(name, 0.0), s)
            print(f"  {name}: median {statistics.median(vals):.6g} "
                  f"spread {s:.4f} values {[round(v, 4) for v in vals]}")
    print("widest spread, x5:")
    for name, s in sorted(widest.items()):
        print(f"  {name}: {s:.4f} -> {5 * s:.4f}")


if __name__ == "__main__":
    main()
