"""Of the window's judged device runs of the sort family of aggregation
lowerings (`kind` `posruns`, `sort_runs`, `sort_sorted` of
`tidb_tpu_agg_lowering_total`), the share that ran `sort_sorted`: the
argsort program a shape is pinned to when its keys do not cluster in
storage order (`runs_degraded`, copr/agg_lowering.py), the one that cost
the TPU compiler 29 GB of host at 4 M lanes (PERF.md, PR 27). 0 when
every shard or row block stays on runs. A program without the counter,
or a window with no run of the family, reports nothing."""
COUNTER = "tidb_tpu_agg_lowering_total"
FAMILY = ("posruns", "sort_runs", "sort_sorted")


def read(run):
    by_kind = dict.fromkeys(FAMILY, 0.0)
    for labels, n in run["growth"].metric_by_label(COUNTER).items():
        for kind in FAMILY:
            if f'kind="{kind}"' in labels:
                by_kind[kind] += n
    total = sum(by_kind.values())
    return 100.0 * by_kind["sort_sorted"] / total if total else None
