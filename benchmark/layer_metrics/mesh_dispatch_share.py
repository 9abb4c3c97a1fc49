"""Share of the window's device dispatches that ran on the mesh, of
those that could have: growth of `tidb_tpu_mesh_route_total` where
`route="mesh"` over the growth of all its samples but `reason="min_rows"`
(a table under `tidb_mpp_min_rows`, a nation or supplier scan, stays on
one chip by design). 100 is sound; the run's log names whatever else
grew by its `reason`. A program without the counter, or a process with
one device (where it does not move), reports nothing."""
import sys

COUNTER = "tidb_tpu_mesh_route_total"


def read(run):
    grown = {labels: n for labels, n in
             run["growth"].metric_by_label(COUNTER).items() if n}
    counted = {labels: n for labels, n in grown.items()
               if 'reason="min_rows"' not in labels}
    for labels, n in sorted(grown.items()):
        if 'route="mesh"' not in labels:
            print(f"mesh_dispatch_share: {n:g} dispatches {{{labels}}}",
                  file=sys.stderr)
    if not counted:
        return None
    on_mesh = sum(n for labels, n in counted.items()
                  if 'route="mesh"' in labels)
    return 100.0 * on_mesh / sum(counted.values())
