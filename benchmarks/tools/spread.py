#!/usr/bin/env python3
"""Spreads of repeated runs, as the bounds are set from them.

    python3 benchmarks/tools/spread.py runs.jsonl

``runs.jsonl``: one line a run, {"workload", "set", "seed", "line": <the
run's last line>}. For every workload, set and metric: the median and the
spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median); then the
wider of the sets' spreads for each metric, and five times it."""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q[2] - q[0]) / median if median else 0.0


def main(path):
    runs = {}
    for text in open(path):
        r = json.loads(text)
        for name, m in r["line"]["metrics"].items():
            runs.setdefault((r["workload"], name), {}).setdefault(
                r["set"], []).append(m["value"])
        if not r["line"]["correct"]:
            print(f"NOT CORRECT: {r['workload']} set {r['set']} seed "
                  f"{r['seed']}: {r['line']['checks']}")
    for (workload, name), sets in sorted(runs.items()):
        widest = 0.0
        for tag, values in sorted(sets.items()):
            if len(values) < 2:
                print(f"{workload} {name} set {tag}: {values}")
                continue
            s = spread(values)
            widest = max(widest, s)
            print(f"{workload} {name} set {tag}: n {len(values)} median "
                  f"{statistics.median(values):.6g} spread {100 * s:.3f}% "
                  f"min {min(values):.6g} max {max(values):.6g}")
        print(f"{workload} {name}: widest spread {100 * widest:.3f}%, "
              f"five times it {100 * 5 * widest:.2f}%")


if __name__ == "__main__":
    main(sys.argv[1])
