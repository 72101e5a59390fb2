#!/usr/bin/env python3
"""The table from which the bounds of BENCHMARK.json are set (README.md,
"How the bounds are set"): the readings of sets of runs of one cell, their
medians and their spreads.

    python3 benchmark/spreads.py <dir of set 1> <dir of set 2> [...]

A directory holds one file `<seed>.out` per run: the standard output of
`run.py --trace 0`, whose last line is the result. Three spreads a set and
metric, each as a share of the set's median:

    iqr        third less first quartile of all the runs, as Python's
               `statistics.quantiles(values, n=4)` gives them: what the bound
               is set from (about five times the widest), and what a check
               holds the bound to as too loose (over eight times the widest)
    iqr_less1  the same, leaving out the run farthest from the median: a check
               refuses a bound as too tight where the mean of the two sets'
               is over half of it
    range_less1  highest less lowest, leaving out that run: ISSUE 34's reading
               of the driver's wording

Reads nothing of the program; imports no JAX. Prints one JSON object.
"""

from __future__ import annotations

import json
import os
import statistics
import sys


def readings(set_dir: str) -> dict:
    """seed -> the result line of its run, in the order of the seeds."""
    out = {}
    for name in sorted(os.listdir(set_dir)):
        if not name.endswith(".out"):
            continue
        with open(os.path.join(set_dir, name)) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        out[name[:-4]] = json.loads(lines[-1]) if lines else None
    return out


def spreads(values: list) -> dict:
    med = statistics.median(values)
    q1, _, q3 = statistics.quantiles(values, n=4)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    r1, _, r3 = statistics.quantiles(rest, n=4)
    return {"median": med, "iqr": (q3 - q1) / med,
            "iqr_less1": (r3 - r1) / med,
            "range_less1": (max(rest) - min(rest)) / med,
            "left_out": far}


def main(dirs: list) -> int:
    table = {}
    for d in dirs:
        runs = readings(d)
        bad = [s for s, r in runs.items()
               if not r or not r.get("correct") or r.get("failed")]
        metrics = sorted({m for r in runs.values() if r for m in r["metrics"]})
        table[d] = {"seeds": list(runs), "not_correct_or_failed": bad, "metrics": {}}
        for m in metrics:
            vals = [r["metrics"][m]["value"] for r in runs.values() if r]
            table[d]["metrics"][m] = {"values": vals, **spreads(vals)}
    print(json.dumps(table, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
