"""Share of the busiest device's busy time that collective operations
hold, over the traced part of the window: the union of the intervals of
its `all-reduce`, `all-gather`, `all-to-all`, `collective-permute` and
`reduce-scatter` operations (and their `-start` / `-done` halves) over
the union of all its operations' intervals. The time between a `-start`
and its `-done` is not counted: other operations may fill it. A trace of
fewer than two device planes has no collective to read."""
import re

import trace_reduce

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter)(-start|-done)?(\.\d+)?$")


def collective_s(ops, lo, hi):
    """Seconds inside [lo, hi] in which a collective runs, of one
    device's [(name, start, end)]."""
    return trace_reduce.length(trace_reduce.clip(trace_reduce.union(
        (s, e) for name, s, e in ops
        if COLLECTIVE.match(trace_reduce.short(name))), lo, hi)) / 1e9


def read(run):
    t = run["trace"]
    if not t or len(t["busy_s_by_device"]) < 2:
        return None
    dev, busy_s = max(t["busy_s_by_device"].items(), key=lambda kv: kv[1])
    if busy_s <= 0:
        return None
    return 100.0 * collective_s(t["trace"]["devices"][dev],
                                t["lo"], t["hi"]) / busy_s
