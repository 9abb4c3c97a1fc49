"""The device's time of a traced window by pipeline stage.

A profile names a device operation by its compiled instruction
(`fusion.6`, `while.15`): a name local to one program, which half the
window's programs share. The program keeps, for every device program
that ran under the profiler session, which stage of the fused pipeline
each instruction belongs to (`tidb_tpu/utils/kernel_stages.py`) and
serves it as `tidb_tpu_kernel_stage_ops{program, entry, stage, ops}`,
which the harness's `after` snapshot holds. This joins the two:

  catalogue   {program family: [{instruction: stage} an entry]} from
              `run["growth"].after["metrics"]`
  module run  an event of the `XLA Modules` line; an operation belongs
              to the module run that holds its middle on its device
  entry       nothing in a module event equals anything the program can
              compute of its executable (PR 36, step 0: not the 32-byte
              fingerprint nor any eight bytes of it, not the module
              proto's id), so a module run is joined by fit: to the
              entries of its family whose instruction names cover the
              operations seen in it (`fit`). An operation whose stage
              those entries agree on has that stage; one they disagree
              on, one of a run no entry fits, of a family without
              entries (`jit_tidb_mask_copy`) or outside every module
              run is stage `none`.
  time        counted once an instant, for the innermost operation open
              at it (a `while`'s body operations are events of the same
              line, whole inside the `while`'s), clipped to the window,
              a device's average: the stages sum to `busy_s`.
  statements  the client's `stmt:` spans that END inside the window on
              the host's clock: an error of the device clock's offset
              moves no device time from one stage to another, and the
              count by at most one.

Without a catalogue (the parent of PR 36, a program never profiled) there
is no view and every reader returns None.
"""
import bisect
import re
import sys

import trace_reduce

FAMILY = "tidb_tpu_kernel_stage_ops"
OUTCOMES = "tidb_tpu_kernel_stage_catalogue_total"
STAGES = ("dim_probe", "group_agg", "compact", "scan_filter", "topn", "none")
NONE = "none"
STATEMENT = "stmt:"
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def labels_of(text):
    """`k="v",k2="v2"` as metrics_summary renders it -> {k: v}."""
    return {k: v.replace('\\"', '"').replace("\\\\", "\\")
            for k, v in _LABEL.findall(text)}


def catalogue(metrics):
    """A snapshot's {(name, labels): value} -> {family: {entry:
    {instruction: stage}}}, empty without a sample of the family."""
    out = {}
    for (name, labels), _value in metrics.items():
        if name != FAMILY:
            continue
        lb = labels_of(labels)
        ops = out.setdefault(lb["program"], {}).setdefault(lb["entry"], {})
        for op in lb["ops"].split():
            ops[op] = lb["stage"]
    return out


def family_of(module_name):
    return module_name.split("(")[0]


def own_time(ops, lo, hi):
    """[(name, start, end)] sorted by start -> [nanoseconds] of each
    inside [lo, hi] while it is the innermost operation open."""
    own, stack = [0] * len(ops), []
    cursor = 0

    def clipped(s, e):
        return max(0, min(e, hi) - max(s, lo))

    def advance(to):
        nonlocal cursor
        while stack:
            end = ops[stack[-1]][2]
            if end > to:
                own[stack[-1]] += clipped(cursor, to)
                break
            own[stack.pop()] += clipped(cursor, end)
            cursor = max(cursor, end)
        cursor = max(cursor, to)

    for i, (_name, start, _end) in enumerate(ops):
        advance(start)
        stack.append(i)
    advance(float("inf"))
    return own


def fit(entries, seen):
    """{entry: {instruction: stage}} of one family and the operations
    seen in one module run -> (label of the entries that fit, {operation:
    stage}). The entries that lack the fewest of the operations fit
    (`0`, `0+1`; `0~2` where even they lack two: an operation of a
    neighbouring program inside the module's interval costs itself, not
    the run); an entry that lacks half of them or more does not, and
    without one the label is `?` and every stage `none`."""
    lack = {e: sum(1 for op in seen if op not in ops)
            for e, ops in entries.items()}
    least = min(lack.values(), default=len(seen))
    if 2 * least >= len(seen):
        return "?", dict.fromkeys(seen, NONE)
    fitting = sorted((e for e in lack if lack[e] == least),
                     key=lambda e: (len(e), e))
    stages = {}
    for op in seen:
        said = {entries[e].get(op, NONE) for e in fitting}
        stages[op] = said.pop() if len(said) == 1 else NONE
    return "+".join(fitting) + (f"~{least}" if least else ""), stages


def view(run):
    """-> {"n": statements, "ns": {(family, entry, stage, operation):
    nanoseconds, a device's average}}, or None. Computed once a run."""
    if "kernel_stages" in run:
        return run["kernel_stages"]
    run["kernel_stages"] = None
    t = run.get("trace")
    cat = catalogue(run["growth"].after["metrics"]) if t else None
    if not cat:
        return None
    trace, lo, hi, off = t["trace"], t["lo"], t["hi"], t["offset_ns"]
    n = sum(1 for name, _s, e in trace["host"]
            if name.startswith(STATEMENT) and lo <= e - off <= hi)
    if not n or not trace["devices"]:
        return None
    total = {}
    for dev, ops in trace["devices"].items():
        # a `while` before the body operation that starts with it
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        mods = sorted(trace["modules"].get(dev, []), key=lambda m: m[1])
        # the module run that holds each operation's middle (an edge can
        # lie a nanosecond outside its own module's)
        starts = [m[1] for m in mods]
        where = []
        for _name, start, end in ops:
            mid = (start + end) // 2
            m = bisect.bisect_right(starts, mid) - 1
            where.append(mods[m] if m >= 0 and mods[m][2] > mid else None)
        # fitted a module run (the same operations fit the same way)
        seen, fitted = {}, {}
        for (name, _s, _e), mod in zip(ops, where):
            if mod is not None:
                seen.setdefault(mod, set()).add(trace_reduce.short(name))
        for mod, names in seen.items():
            key = (mod[0], frozenset(names))
            if key not in fitted:
                fitted[key] = fit(cat.get(family_of(mod[0]), {}), names)
            seen[mod] = fitted[key]
        for (name, _s, _e), mod, ns in zip(ops, where,
                                           own_time(ops, lo, hi)):
            if not ns:
                continue
            op = trace_reduce.short(name)
            if mod is None:
                key = ("(no module)", "?", NONE, op)
            else:
                entry, stages = seen[mod]
                key = (family_of(mod[0]), entry, stages[op], op)
            total[key] = total.get(key, 0) + ns
    ndev = len(trace["devices"])
    run["kernel_stages"] = {
        "n": n, "ns": {k: ns / ndev for k, ns in total.items()}}
    return run["kernel_stages"]


def ms_per_query(run, stage):
    """Device milliseconds of `stage` a counted statement; None without
    a view."""
    v = view(run)
    if v is None:
        return None
    return sum(ns for k, ns in v["ns"].items()
               if k[2] == stage) / v["n"] / 1e6


def log_tables(run, out=sys.stderr):
    """What PERF.md section 5 is written from: device seconds by program
    family and stage, the ten largest operations as
    family/entry/stage/name and after them the five largest that no
    stage names, and what the catalogue is made of."""
    v = view(run)
    if v is None:
        return
    metrics = run["growth"].after["metrics"]
    labels = [k[1] for k in metrics if k[0] == FAMILY]
    outcomes = {labels_of(k[1]).get("outcome"): int(val)
                for k, val in metrics.items() if k[0] == OUTCOMES}
    print(f"kernel_stages: catalogue of {len(labels)} samples, longest "
          f"label {max(map(len, labels))} bytes, outcomes {outcomes}; "
          f"{v['n']} statements end inside the window", file=out)
    by_family = {}
    for (family, _entry, stage, _op), ns in v["ns"].items():
        row = by_family.setdefault(family, {})
        row[stage] = row.get(stage, 0) + ns
    for family, row in sorted(by_family.items(),
                              key=lambda kv: -sum(kv[1].values())):
        print(f"kernel_stages: {family} {sum(row.values()) / 1e9:.6f} s "
              f"({sum(row.values()) / v['n'] / 1e6:.3f} ms a statement): "
              + ", ".join(f"{s} {row[s] / 1e9:.6f}" for s in STAGES
                          if s in row), file=out)
    by_stage = {s: sum(ns for k, ns in v["ns"].items() if k[2] == s)
                for s in STAGES}
    print("kernel_stages: all " + ", ".join(
        f"{s} {ns / v['n'] / 1e6:.3f} ms" for s, ns in by_stage.items())
        + f"; sum {sum(by_stage.values()) / 1e9:.6f} s", file=out)
    ranked = sorted(v["ns"].items(), key=lambda kv: -kv[1])
    unnamed = [kv for kv in ranked if kv[0][2] == NONE][:5]
    for key, ns in ranked[:10] + [kv for kv in unnamed
                                  if kv not in ranked[:10]]:
        print(f"kernel_stages: {'/'.join(key)} {ns / 1e9:.6f} s",
              file=out)
