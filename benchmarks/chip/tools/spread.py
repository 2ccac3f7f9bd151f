"""Spread of each metric over runs, as the bounds are set from.

    python3 benchmarks/chip/tools/spread.py run1.out run2.out ...

Reads the last JSON line of each file (a ``run.py`` result) and prints,
per metric, the median and the spread: the distance between the first
and third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median.  A bound is set at about five times the widest spread of two
sets of runs, never under 1% and never over 25%.
"""

from __future__ import annotations

import json
import statistics
import sys


def last_json(path: str) -> dict:
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    if not lines:
        raise ValueError(f"{path} holds no result line")
    return json.loads(lines[-1])


def spreads(results) -> dict:
    by_metric = {}
    for r in results:
        for name, m in r["metrics"].items():
            by_metric.setdefault(name, []).append(m["value"])
    out = {}
    for name, values in sorted(by_metric.items()):
        med = statistics.median(values)
        q1, _, q3 = (statistics.quantiles(values, n=4)
                     if len(values) > 1 else (med, med, med))
        out[name] = {"runs": len(values), "median": med,
                     "spread": (q3 - q1) / med if med else None,
                     "values": values}
    return out


def main(argv=None) -> int:
    paths = (argv if argv is not None else sys.argv[1:])
    results = [last_json(p) for p in paths]
    for name, s in spreads(results).items():
        print(json.dumps({"metric": name, **s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
