#!/usr/bin/env python
"""Rank queries in a bench.py phase sidecar (BENCH_PHASES_PATH) by where
their best-run time goes: dispatches, fetch round trips, scalar syncs,
uploads, host execution.

Usage: python scripts/phases_report.py <phases.json>
"""
import json
import sys


def main(path):
    doc = json.load(open(path))
    rows = []
    for q, ph in sorted(doc.get("phases", {}).items()):
        b = ph.get("best", {})
        rows.append((
            q, b.get("total_ms", 0.0),
            b.get("dispatches", 0),
            b.get("fetches", 0), round(1000 * b.get("fetch_s", 0.0), 1),
            b.get("syncs", 0), round(1000 * b.get("sync_s", 0.0), 1),
            b.get("uploads", 0), b.get("upload_hits", 0),
            round(1000 * b.get("host_exec_s", 0.0), 1),
            round(1000 * b.get("dispatch_s", 0.0), 1),
        ))
    rows.sort(key=lambda r: -r[1])
    hdr = ("q", "total_ms", "disp", "fetch", "fetch_ms", "sync",
           "sync_ms", "upl", "upl_hit", "host_ms", "disp_ms")
    print(("%4s %9s %5s %6s %9s %5s %8s %4s %8s %8s %8s") % hdr)
    for r in rows:
        print(("%4s %9.1f %5d %6d %9.1f %5d %8.1f %4d %8d %8.1f %8.1f")
              % r)
    tracked = ["fetch_s", "sync_s", "host_exec_s", "dispatch_s"]
    for q, ph in sorted(doc.get("phases", {}).items()):
        b = ph.get("best", {})
        tot = b.get("total_ms", 0.0)
        acc = sum(1000 * b.get(k, 0.0) for k in tracked)
        if tot > 200 and acc < 0.5 * tot:
            print(f"# {q}: {tot - acc:.0f}ms of {tot:.0f}ms untracked "
                  "(host planning/merge or waits outside timers)")


if __name__ == "__main__":
    main(sys.argv[1])
