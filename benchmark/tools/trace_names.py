#!/usr/bin/env python3
"""What a raw device trace calls things, for PERF.md's breakdown by
program family (by hand, never by the harness):

  python3 benchmark/tools/trace_names.py <file.xplane.pb>

Prints the seconds of every device program inside the traced window by
the name on the `XLA Modules` line with the hash cut off
(`jit_tidb_fused_sort(123...)` -> `jit_tidb_fused_sort`), a device's
average, and where the trace carries the `jax.named_scope` stage names of
the fused pipeline: as an event's name, as one of its stats, or nowhere.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_reduce                                         # noqa: E402

SCOPES = ("scan_filter", "dim_probe", "compact", "group_agg", "topn")


def family(module_name):
    return module_name.split("(")[0]


def module_seconds(path):
    """-> [(family, seconds, runs)] by time, inside the traced window
    (the whole trace where it has no window span)."""
    trace = trace_reduce.load(path)
    offset = trace_reduce.clock_offset_ns(
        trace, "bench:clock_probe", "bench_clock_probe") or 0
    win = trace_reduce.window(trace, "bench:traced_window", offset)
    lo, hi = win if win else (float("-inf"), float("inf"))
    total, runs = {}, {}
    for mods in trace["modules"].values():
        for name, s, e in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                f = family(name)
                total[f] = total.get(f, 0) + d
                runs[f] = runs.get(f, 0) + 1
    n = max(len(trace["modules"]), 1)
    return sorted(((f, ns / n / 1e9, runs[f]) for f, ns in total.items()),
                  key=lambda x: -x[1])


def where_scopes_are(path, out=sys.stdout):
    """Prints which planes, lines and fields (an event's name, or one
    of its stats) hold a stage name, with one example each."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                fields = [("event name", ev.name)]
                fields += [(f"stat {k}", str(v)) for k, v in ev.stats]
                for field, text in fields:
                    if any(scope in text for scope in SCOPES):
                        found.setdefault((plane.name, line.name, field),
                                         text[:160])
    if not found:
        print("named scopes: nowhere in the trace", file=out)
    for (plane, line, field), text in sorted(found.items()):
        print(f"named scopes: plane {plane!r} line {line!r} {field}: "
              f"e.g. {text!r}", file=out)


def main():
    path = sys.argv[1]
    for fam, seconds, runs in module_seconds(path):
        print(f"module {fam}: {seconds:.6f} s in {runs} runs")
    where_scopes_are(path)


if __name__ == "__main__":
    main()
