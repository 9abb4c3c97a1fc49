"""Of the window's dimension probes, the share that searches: growth of
`tidb_tpu_fused_dim_probe_total` (one count a dimension a fused
statement, at the `bind` that uploads the dimensions) under
`mode="search"`, a binary search over sorted keys at fact width, over
its growth under every mode (`folded`, `direct`, `exists`, `matdim`:
no probe of its own, or one gather). The probes neither a fold nor a
direct table resolves: Q9's partsupp, joined on two columns. A program
without the counter reports nothing."""
import sys

COUNTER = "tidb_tpu_fused_dim_probe_total"


def read(run):
    grown = {labels: n for labels, n in
             run["growth"].metric_by_label(COUNTER).items() if n}
    total = sum(grown.values())
    if total <= 0:
        return None
    for labels, n in sorted(grown.items()):
        print(f"searched_probe_share: {n:g} probes {{{labels}}}",
              file=sys.stderr)
    return 100.0 * sum(n for labels, n in grown.items()
                       if 'mode="search"' in labels) / total
