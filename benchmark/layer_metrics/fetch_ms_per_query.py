"""Host-clock milliseconds a query spends fetching results from the
device (`fetch_s` + `sync_s`) and on the host path (`host_exec_s`)."""
import counters


def read(run):
    g = run["growth"]
    n = g.top_sql("exec_count", counters.is_query)
    if not n:
        return None
    return (g.top_sql("sum_fetch_ms", counters.is_query) +
            g.top_sql("sum_host_ms", counters.is_query)) / n
