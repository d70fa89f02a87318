#!/usr/bin/env python3
"""Spread of each metric over the result lines of one set of runs, as the
bound's rule reads it: interquartile range (statistics.quantiles, n=4) over
the median.

    python benchmark/tools/spread.py run1.out run2.out ...

Each file is one run's standard output; its last line is the result."""
import json
import statistics
import sys


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med, med


def main(paths):
    runs = []
    for p in paths:
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        runs.append(json.loads(lines[-1]))
    print(f"runs {len(runs)}, correct {sum(r['correct'] for r in runs)}, "
          f"failed {[r['failed'] for r in runs]}")
    for name in runs[0]["metrics"]:
        vals = [r["metrics"][name]["value"] for r in runs
                if name in r["metrics"]]
        if len(vals) >= 2:
            s, med = spread(vals)
            print(f"{name}: median {med:.6g} spread {100 * s:.3f}% "
                  f"min {min(vals):.6g} max {max(vals):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
