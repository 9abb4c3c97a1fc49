"""The program's counters, read over the wire before and after the
window from the tables it serves them in: `information_schema`'s
`tidb_top_sql` (each statement's phase counters folded by digest) and
`metrics_summary` (the flat per-store counters and the typed registry). Per-layer readers see the
growth between the two snapshots."""

TOP_SQL = ("exec_count", "sum_ms", "sum_device_ms", "sum_compile_ms",
           "sum_host_ms", "sum_fetch_ms", "sum_upload_ms", "kernel_builds",
           "dispatches", "upload_bytes", "fetch_bytes", "fallback_count",
           "sum_errors", "delta_applies", "delta_bytes")
# a degrade is a different result, not a slower one: growth of any of
# these inside the window counts as that many failed operations. Warning
# 9013 is covered by `device_fallback` (see traffic.py)
DEGRADE = ("device_fallback", "device_dispatch_error", "device_retry",
           "device_breaker_open", "fused_pipeline_error")


def snapshot(wire):
    top = {r[0]: dict(zip(("text",) + TOP_SQL,
                          (r[1],) + tuple(float(x) for x in r[2:])))
           for r in wire.rows(
               "select sql_digest, sql_text, " + ", ".join(TOP_SQL) +
               " from information_schema.tidb_top_sql")}
    metrics = {(r[0], r[1]): float(r[2]) for r in wire.rows(
        "select metrics_name, labels, sum_value "
        "from information_schema.metrics_summary")}
    return {"top_sql": top, "metrics": metrics}


def builds(wire):
    """Programs built so far: every statement's `kernel_builds` plus
    the persistent compile cache's misses."""
    top = wire.rows("select sum(kernel_builds) from "
                    "information_schema.tidb_top_sql")[0][0]
    miss = wire.rows(
        "select sum(sum_value) from information_schema.metrics_summary "
        "where metrics_name = 'tidb_tpu_xla_cache_total' "
        "and labels like '%miss%'")[0][0]
    return float(top or 0) + float(miss or 0)


def xla_cache(wire):
    """-> the persistent compile cache's {labels: lookups} so far."""
    return {r[0]: float(r[1]) for r in wire.rows(
        "select labels, sum_value from information_schema.metrics_summary "
        "where metrics_name = 'tidb_tpu_xla_cache_total'")}


class Growth:
    """after - before, with the few questions the readers ask."""

    def __init__(self, before, after):
        self.before, self.after = before, after

    def metric(self, name, labels=""):
        key = (name, labels)
        return self.after["metrics"].get(key, 0.0) - \
            self.before["metrics"].get(key, 0.0)

    def metric_by_label(self, name):
        """-> {labels: growth} over every sample of one metric."""
        return {k[1]: self.metric(*k) for k in self.after["metrics"]
                if k[0] == name}

    def top_sql(self, column, match):
        total = 0.0
        for digest, row in self.after["top_sql"].items():
            if match(row["text"].lstrip().lower()):
                total += row[column] - \
                    self.before["top_sql"].get(digest, {}).get(column, 0.0)
        return total

    def degrades(self):
        return {k: self.metric(k) for k in DEGRADE}


def is_query(text):
    """The window's analytic statements, and not the harness's own reads
    of the counters."""
    return text.startswith("select") and "information_schema" not in text


def is_any(text):
    return "information_schema" not in text
