"""Milliseconds of a query the host spends in the mesh route's own code:
the self time of the program's `mpp_dispatch` span (choosing the
lowering, looking the mesh program up, unstacking the per-shard
partials: what is not `bind`, `dispatch`, `fetch` or `consume` under
it), from the `tidb:` segments of the trace; see `program_spans.py`.
`executor_host_ms_per_query` sums a fixed list of spans that does not
hold this one. A program that opens no such span on the route the
statements take (the parent of the PR that adds this reader) reports
nothing."""
import program_spans

SPAN = "mpp_dispatch"


def read(run):
    v = program_spans.view(run)
    if v is None or not any(name == SPAN for name, _, _ in v["segments"]):
        return None
    return program_spans.ms_per_query(run, (SPAN,))
