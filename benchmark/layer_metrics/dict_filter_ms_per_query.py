"""Milliseconds of a query the host spends mapping a Python function
over a string dictionary's values: the self time of the program's
`dict_filter` span (`expression/vec.py:_dict_table`: a LIKE or REGEXP
over every distinct string of a column, Q13's over 1.5 M order
comments, Q9's over 200 k part names), from the `tidb:` segments of the
trace; see `program_spans.py`. 0 in a steady window: the predicates sit
under a cached fold or a cached aggregate dimension, and an unchanged
dictionary answers from the table kept with it (`hit`). A program
without `tidb_tpu_dict_filter_total` reports nothing."""
import sys

import program_spans

COUNTER = "tidb_tpu_dict_filter_total"
SPANS = ("dict_filter",)


def read(run):
    if not any(k[0] == COUNTER for k in run["growth"].after["metrics"]):
        return None
    grown = {labels: n for labels, n in
             run["growth"].metric_by_label(COUNTER).items() if n}
    print(f"{SPANS[0]}: the window's {grown}", file=sys.stderr)
    return program_spans.ms_per_query(run, SPANS)
