"""Lookups of the device buffer pool that found no resident buffer, a
query: growth of `tidb_tpu_device_buffer_pool_total{result="miss"}` in
the window over the window's analytic statements. 0 when every column a
statement binds is resident. A program without the counter reports
nothing."""
import counters

COUNTER = "tidb_tpu_device_buffer_pool_total"


def read(run):
    g = run["growth"]
    if not any(name == COUNTER for name, _ in g.after["metrics"]):
        return None
    n = g.top_sql("exec_count", counters.is_query)
    return g.metric(COUNTER, 'result="miss"') / n if n else None
