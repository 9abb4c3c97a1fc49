"""Device runs of an aggregation program that were thrown away and run
again, a query: growth of `tidb_tpu_agg_lowering_total` on every
`verdict` but `stands` (`retry_early_compact`, `retry_compact`,
`retry_pin_sorted`, `retry_grow_bucket`: what the run taught the
lowering; `retry_onehot_miss`, `retry_topn_unproven`: what only its
consumer saw; copr/agg_lowering.py) over the window's analytic statements.
0 is sound in a steady window: the sizes were learned in warm-up; the
run's log names whatever grew. A program without the counter reports
nothing."""
import sys

import counters

COUNTER = "tidb_tpu_agg_lowering_total"


def read(run):
    g = run["growth"]
    grown = g.metric_by_label(COUNTER)
    if not grown:
        return None
    again = {labels: n for labels, n in grown.items()
             if n and 'verdict="stands"' not in labels}
    for labels, n in sorted(again.items()):
        print(f"lowering_retries_per_query: {n:g} runs {{{labels}}}",
              file=sys.stderr)
    n = g.top_sql("exec_count", counters.is_query)
    return sum(again.values()) / n if n else None
