"""Milliseconds of a query the host spends on materialised aggregate
dimensions: the self time of the program's `matdim` span (Q17's
decorrelated avg by part, Q13's orders counted by customer: finding the
dimension in the cache keyed on its base tables' versions, or, on a
`build`, shaping the subplan's output into a probe table; the subplan's
own execution is its children's time), from the `tidb:` segments of the
trace; see `program_spans.py`. In a read-only window every one but the
first is a `hit`, which the log line says. A program without
`tidb_tpu_matdim_total` reports nothing."""
import sys

import program_spans

COUNTER = "tidb_tpu_matdim_total"
SPANS = ("matdim",)


def read(run):
    if not any(k[0] == COUNTER for k in run["growth"].after["metrics"]):
        return None
    grown = {labels: n for labels, n in
             run["growth"].metric_by_label(COUNTER).items() if n}
    print(f"{SPANS[0]}: the window's {grown}", file=sys.stderr)
    return program_spans.ms_per_query(run, SPANS)
