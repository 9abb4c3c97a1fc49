"""Q6 and Q1, the two full scans of lineitem: the least time the chips
could take to read the bytes those scans cannot avoid (live rows x the
stored width of the columns each names, `SCAN_BYTES_PER_ROW` of the data
set) at the HBM peak of `peaks.json`, over the device-busy time inside
those statements' host spans. Memory-bound: the scans do a few integer
operations a row. Read only where one statement is in flight at a time
(one client), since kernels carry no statement's name."""


def read(run):
    import trace_reduce
    t = run["trace"]
    clients = run["traffic"]["clients"]
    if not t or len(clients) != 1:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise SystemExit(f"scan_roofline: no peaks for device kind {kind!r}")
    peak = run["peaks"][kind]["hbm_bytes_per_s"] * run["device"]["count"]
    rows = len(run["tables"]["lineitem"]["l_orderkey"])
    need_s, busy_s = 0.0, 0.0
    for stmt, width in run["dataset"].SCAN_BYTES_PER_ROW.items():
        n, busy = trace_reduce.busy_inside(
            t["trace"], f"stmt:{stmt}", t["lo"], t["hi"], t["offset_ns"])
        need_s += n * rows * width / peak
        busy_s += busy
    if busy_s <= 0:
        return None
    return 100.0 * need_s / busy_s
