"""What the program's own spans say about a traced window.

The program (`tidb_tpu/utils/tracing.py`) writes its spans into the
profiler's trace as flat self-time segments: every server thread keeps at
most one annotation open, `tidb:<span>`, named after its innermost open
span, so a thread's line is a non-overlapping sequence and the sum of a
name's segments is that span's self time. `trace_reduce.load` flattens
every host thread into one list, which is all these readers need: no
tree is rebuilt, and any number of connections may be in flight.

One view a run, shared by the readers in `layer_metrics/`:

  statements  the client's `stmt:` spans that lie whole inside the traced
              window (the convention of `trace_reduce.busy_inside`), `n`
              of them, on the device's clock
  segments    the `tidb:` segments clipped to the union of those
              statements: what the server did for the statements counted
  busy        the busy intervals of the busiest device inside the window
              (the device `device_idle_share` reads)

A program that writes no `tidb:` segment (the parent of PR 25) gives no
view, and every reader returns None. Times are nanoseconds on the
device's clock (host time minus `offset_ns`).
"""
import bisect
import sys

import trace_reduce

PREFIX = "tidb:"
STATEMENT = "stmt:"


def intersect(a, b):
    """Two sorted disjoint interval lists -> their intersection."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def view(run):
    """-> the view described above, or None. Computed once a run."""
    if "program_spans" in run:
        return run["program_spans"]
    run["program_spans"] = None
    t = run.get("trace")
    if not t:
        return None
    off, lo, hi = t["offset_ns"], t["lo"], t["hi"]
    host = [(n, s - off, e - off) for n, s, e in t["trace"]["host"]]
    server = [(n[len(PREFIX):], s, e) for n, s, e in host
              if n.startswith(PREFIX)]
    stmts = [(n[len(STATEMENT):], s, e) for n, s, e in host
             if n.startswith(STATEMENT) and s >= lo and e <= hi]
    if not server or not stmts:
        return None
    inside = [tuple(iv) for iv in
              trace_reduce.union((s, e) for _, s, e in stmts)]
    busy = trace_reduce.busy(t["trace"], lo, hi)
    busiest = max(busy.values(), key=trace_reduce.length) if busy else []
    run["program_spans"] = {
        "n": len(stmts), "statements": stmts, "server": server,
        "segments": [(n, cs, ce) for n, s, e in server
                     for cs, ce in intersect([(s, e)], inside)],
        "busy": [tuple(iv) for iv in busiest], "lo": lo, "hi": hi}
    return run["program_spans"]


def ms_by_name(segments, n):
    """-> {span name: self-time ms} over `n` statements."""
    total = {}
    for name, s, e in segments:
        total[name] = total.get(name, 0) + (e - s)
    return {k: ns / n / 1e6 for k, ns in total.items()}


def ms_per_query(run, names):
    """Self time of the spans `names`, summed, a counted statement; None
    without a view."""
    v = view(run)
    if v is None:
        return None
    by = ms_by_name(v["segments"], v["n"])
    return sum(by.get(n, 0.0) for n in names)


def idle_gaps(v):
    """The busiest device's idle intervals inside the window."""
    edges = [v["lo"]] + [t for iv in v["busy"] for t in iv] + [v["hi"]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def server_idle(run):
    """-> (nanoseconds in which the busiest device is idle and a `tidb:`
    segment is open on some server thread, the window's nanoseconds), or
    None without a view."""
    v = view(run)
    if v is None or v["hi"] <= v["lo"]:
        return None
    open_ = trace_reduce.union(trace_reduce.clip(
        [(s, e) for _, s, e in v["server"]], v["lo"], v["hi"]))
    held = intersect(idle_gaps(v), [tuple(iv) for iv in open_])
    return trace_reduce.length(held), v["hi"] - v["lo"]


def log_tables(run, out=sys.stderr):
    """What PERF.md section 5 is written from: the device's idle
    seconds by the server segment open at each gap's middle, and each
    statement's client latency split by segment (exact with one
    connection; with more, a statement's interval also holds the other
    connections' segments)."""
    v = view(run)
    if v is None:
        return
    by_label = {}
    server = sorted(v["server"], key=lambda seg: seg[1])
    starts = [seg[1] for seg in server]
    longest = max(e - s for _, s, e in server)
    for s, e in idle_gaps(v):
        mid = (s + e) // 2
        label, i = "no_server_segment", bisect.bisect_right(starts, mid)
        while i > 0 and starts[i - 1] >= mid - longest:
            i -= 1
            if server[i][2] > mid:
                label = server[i][0]
                break
        by_label[label] = by_label.get(label, 0) + (e - s)
    print("program_spans: idle seconds by segment: " + ", ".join(
        f"{k} {ns / 1e9:.6f}" for k, ns in
        sorted(by_label.items(), key=lambda kv: -kv[1])), file=out)
    groups = {"all": v["statements"]}
    for st in v["statements"]:
        groups.setdefault(st[0], []).append(st)
    for name, sts in groups.items():
        spans = [(n, cs, ce) for n, s, e in v["server"]
                 for _, ss, se in sts
                 for cs, ce in intersect([(s, e)], [(ss, se)])]
        by = ms_by_name(spans, len(sts))
        client = sum(e - s for _, s, e in sts) / len(sts) / 1e6
        print(f"program_spans: {name} n={len(sts)} client {client:.3f} ms, "
              f"segments {sum(by.values()):.3f} ms: " + ", ".join(
                  f"{k} {ms:.3f}" for k, ms in
                  sorted(by.items(), key=lambda kv: -kv[1])), file=out)
