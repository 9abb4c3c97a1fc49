"""Bytes a query uploads to the device: 0 when the columns are resident
and every delta was folded on the device."""
import counters


def read(run):
    g = run["growth"]
    n = g.top_sql("exec_count", counters.is_query)
    return g.top_sql("upload_bytes", counters.is_query) / n if n else None
