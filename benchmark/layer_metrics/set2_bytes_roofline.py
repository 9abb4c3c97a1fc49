"""The six statements of `tpch_set2` against the bytes they cannot
avoid: each statement that ends inside the traced window needs its
tables' read columns once at their stored widths over the tables' rows
(`UNAVOIDABLE_BYTES(tables)` of the data set: a function of the data,
independent of lowering, fold or block size) at the HBM peak of
`peaks.json`; over the device's busy time of the window by
`kernel_stages.view`, whose statements are those that END inside the
window, so that an error of the clock offset moves the count by one
statement and no device time. Memory-bound by construction: a few
integer operations a row. Under 100: the gathers at fact width, the
sort-layout aggregation and the binary search are what the rest is.
The log has each statement's own share by `busy_inside`, which does
hang on the offset and decides nothing. A data set without
`UNAVOIDABLE_BYTES` or a program without the stage catalogue reports
nothing."""
import sys

import kernel_stages
import trace_reduce


def read(run):
    need_of = getattr(run["dataset"], "UNAVOIDABLE_BYTES", None)
    v = kernel_stages.view(run)
    if need_of is None or v is None:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        return None
    peak = run["peaks"][kind]["hbm_bytes_per_s"] * run["device"]["count"]
    need = need_of(run["tables"])
    t = run["trace"]
    lo, hi, off = t["lo"], t["hi"], t["offset_ns"]
    ended = [name[len(kernel_stages.STATEMENT):]
             for name, _s, e in t["trace"]["host"]
             if name.startswith(kernel_stages.STATEMENT)
             and lo <= e - off <= hi]
    busy_s = sum(v["ns"].values()) / 1e9
    if busy_s <= 0 or any(s not in need for s in ended):
        return None
    for stmt in sorted(set(ended)):
        n, busy = trace_reduce.busy_inside(
            t["trace"], kernel_stages.STATEMENT + stmt, lo, hi, off)
        if n and busy > 0:
            print(f"set2_bytes_roofline: {stmt} n={n} needs "
                  f"{need[stmt] / peak * 1e3:.3f} ms, busy "
                  f"{busy / n * 1e3:.3f} ms a statement: "
                  f"{100.0 * n * need[stmt] / peak / busy:.2f} %",
                  file=sys.stderr)
    return 100.0 * sum(need[s] for s in ended) / peak / busy_s
