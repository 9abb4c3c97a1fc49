#!/usr/bin/env python3
"""Spreads and bounds from the two sets that tools/sets.sh recorded:
for each metric the interquartile distance over the median
(statistics.quantiles, n=4), per set; the wider one; five times it.

  python3 benchmark/tools/spread.py chiprun_out/sets/<cell>.jsonl"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(path):
    runs = [json.loads(line) for line in open(path)]
    ok = [r for r in runs if r["result"]]
    print(f"{len(runs)} runs, {len(ok)} with a result, "
          f"{sum(r['result']['correct'] for r in ok)} correct")
    for name in ok[0]["result"]["metrics"]:
        sets = {}
        for r in ok:
            sets.setdefault(r["set"], []).append(
                r["result"]["metrics"][name]["value"])
        line = f"{name:20s}"
        widest = 0.0
        for k, v in sorted(sets.items()):
            s = spread(v) if len(v) >= 2 else float("nan")
            widest = max(widest, s)
            line += (f"  set {k}: median {statistics.median(v):.6g} "
                     f"spread {100 * s:.3f}% (n={len(v)})")
        print(line + f"  -> 5x widest = {500 * widest:.2f}%")


if __name__ == "__main__":
    main(sys.argv[1])
