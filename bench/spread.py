#!/usr/bin/env python3
"""Spread of a cell's runs, to set its bounds from.

    python3 bench/spread.py <file of one set of runs> ...

Each file holds the standard output of one set of runs of ``run_cell.py``;
every result line (the JSON objects that hold ``correct``) is one run.  For
each file and metric it prints the median and the spread: the distance
between the first and third quartiles (``statistics.quantiles(values,
n=4)``) as a share of the median, over all runs and with the run farthest
from the median left out.  Not part of a benchmark run.
"""

from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values):
    m = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - m))
    return values[:far] + values[far + 1:]


def main(paths) -> int:
    for path in paths:
        results = [json.loads(line) for line in open(path)
                   if line.startswith('{"correct"')]
        metrics = defaultdict(list)
        for res in results:
            for name, m in res["metrics"].items():
                metrics[name].append(m["value"])
        print(f"{path}: {len(results)} runs, correct "
              f"{sum(r['correct'] for r in results)}")
        for name, vals in sorted(metrics.items()):
            print(f"  {name}: n={len(vals)} median "
                  f"{statistics.median(vals)!r} spread {spread(vals):.5f} "
                  f"without the farthest "
                  f"{spread(without_farthest(vals)):.5f} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
