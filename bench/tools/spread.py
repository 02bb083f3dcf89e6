#!/usr/bin/env python3
"""Spread of a cell's end-to-end metrics over sets of runs.

    python3 bench/tools/spread.py set1.jsonl set2.jsonl ...

Each file holds the result lines (the last stdout line of
``bench/run.py``) of one set of runs. For every metric it prints each
set's median and its spread: the distance between the first and third
quartiles of ``statistics.quantiles(values, n=4)`` as a share of the
median, and the bound five times the widest spread would give.
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med


def main(paths) -> int:
    sets = []
    for path in paths:
        with open(path) as f:
            sets.append([json.loads(ln) for ln in f if ln.startswith("{")])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for name in names:
        rows = []
        for s in sets:
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            if len(vals) >= 2:
                rows.append((len(vals),) + spread(vals))
        widest = max((r[2] for r in rows), default=float("nan"))
        print(json.dumps({"metric": name,
                          "sets": [{"n": n, "median": m, "spread": sp}
                                   for n, m, sp in rows],
                          "widest_spread": widest,
                          "five_times": 5 * widest}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
