"""From a profiler trace (`*.xplane.pb`) to numbers: device busy and idle
time, time per device operation, idle gaps by what the host was doing,
device time inside the host's statement spans. Checked on a small recorded trace by `tests/test_trace_reduce.py`.

What a TPU trace holds (looked at by hand, PR 24): one plane
`/device:TPU:<n>` a chip, whose line `XLA Ops` has one event for every
operation that ran there (`XLA Modules` one for every program); the plane
`/host:CPU` has a line a host thread, and a `jax.profiler.TraceAnnotation`
is an event on its thread's line. The device's clock is not the host's:
in the recorded trace every device operation starts 1.1 ms before the
host span that launched it. `clock_offset_ns` bounds the difference from
spans that each enclose one whole small program, and everything that
relates device time to host spans is shifted by it.

Times are nanoseconds as the profiler gives them.
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def load(path):
    """-> {"devices": {n: [(name, start, end)]}, "modules": the same for
    whole programs, "host": [(name, start, end)]}, each sorted by start;
    host events are annotations only (names with a `prefix:`)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for name, into in ((OPS_LINE, devices), (MODULES_LINE, modules)):
                into[int(m.group(1))] = sorted(
                    ((e.name, int(e.start_ns),
                      int(e.start_ns + e.duration_ns))
                     for line in plane.lines if line.name == name
                     for e in line.events), key=lambda e: e[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if re.match(r"^[a-z_]+:[A-Za-z0-9_.\-]+$", e.name)]
    return {"devices": devices, "modules": modules,
            "host": sorted(host, key=lambda e: e[1])}


def spans(trace, prefix):
    return [e for e in trace["host"] if e[0].startswith(prefix)]


def union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals):
    return sum(e - s for s, e in intervals)


def clock_offset_ns(trace, probe_prefix, probe_module):
    """host time = device time + offset. The k-th probe span encloses
    the whole of the k-th run of the probe program (the caller blocks on
    it inside the span), so that run cannot start before its span does
    nor end after it: the offset lies between the largest lower and the
    smallest upper bound; -> their midpoint, or None without probes or
    where the bounds contradict each other."""
    probes = spans(trace, probe_prefix)
    runs = sorted((s, e) for mods in trace["modules"].values()
                  for n, s, e in mods if probe_module in n)
    if not probes or len(probes) != len(runs):
        return None
    lo = max(ps - rs for (_, ps, _), (rs, _) in zip(probes, runs))
    hi = min(pe - re_ for (_, _, pe), (_, re_) in zip(probes, runs))
    return (lo + hi) // 2 if hi >= lo else None


def window(trace, name, offset):
    """The traced window's span on the device's clock."""
    found = [e for e in trace["host"] if e[0] == name]
    if not found:
        return None
    return found[0][1] - offset, found[0][2] - offset


def busy(trace, lo, hi):
    """-> {device: disjoint busy intervals inside [lo, hi]}."""
    return {n: clip(union((s, e) for _, s, e in ops), lo, hi)
            for n, ops in trace["devices"].items()}


def short(op_name):
    """`%fusion.3 = s32[...] fusion(...)` -> `fusion.3`."""
    return op_name.split(" = ")[0].lstrip("%")[:64]


def op_seconds(trace, lo, hi):
    """-> [(name, seconds)] by time, a device's average."""
    total = {}
    for ops in trace["devices"].values():
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[short(name)] = total.get(short(name), 0) + d
    n = max(len(trace["devices"]), 1)
    return sorted(((k, v / n / 1e9) for k, v in total.items()),
                  key=lambda kv: -kv[1])


def idle_gaps(trace, lo, hi, offset, prefixes=("stmt:",)):
    """Idle time of the busiest device inside [lo, hi], by the host span
    that covers each gap's middle -> [(label, seconds)] by time."""
    b = busy(trace, lo, hi)
    if not b:
        return []
    intervals = max(b.values(), key=length)
    edges = [lo] + [t for iv in intervals for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    labelled = [e for e in trace["host"] if e[0].startswith(prefixes)]
    total = {}
    for s, e in gaps:
        mid = (s + e) // 2 + offset
        label = next((n for n, hs, he in labelled if hs <= mid < he),
                     "between statements")
        total[label] = total.get(label, 0) + (e - s)
    return sorted(((k, v / 1e9) for k, v in total.items()),
                  key=lambda kv: -kv[1])


def busy_inside(trace, name, lo, hi, offset):
    """-> (executions, seconds): the host spans called `name` that lie
    whole inside the window, and the device time inside them, a device's
    average. Sound only where one statement is in flight at a time."""
    b = busy(trace, lo, hi)
    count, total = 0, 0
    for n, s, e in trace["host"]:
        if n != name or s - offset < lo or e - offset > hi:
            continue
        count += 1
        total += sum(length(clip(iv, s - offset, e - offset))
                     for iv in b.values())
    return count, total / max(len(b), 1) / 1e9


def reduce(path, window_name="bench:traced_window",
           probe_prefix="bench:clock_probe",
           probe_module="bench_clock_probe"):
    """-> the reduced trace the per-layer readers read, or None where
    the trace holds no device plane or no window span."""
    trace = load(path)
    if not trace["devices"]:
        return None
    offset = clock_offset_ns(trace, probe_prefix, probe_module) or 0
    win = window(trace, window_name, offset)
    if win is None:
        return None
    lo, hi = win
    b = busy(trace, lo, hi)
    per_device = {n: length(iv) / 1e9 for n, iv in b.items()}
    return {"trace": trace, "lo": lo, "hi": hi, "offset_ns": offset,
            "window_s": (hi - lo) / 1e9,
            "busy_s_by_device": per_device,
            "busy_s": sum(per_device.values()) / len(per_device),
            "device_ops": op_seconds(trace, lo, hi),
            "idle_gaps": idle_gaps(trace, lo, hi, offset)}
