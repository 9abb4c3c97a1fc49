"""Programs built inside the window: `kernel_builds` of every statement
plus the persistent cache's misses. Nothing compiles in a warm window:
this should read 0."""
import counters


def read(run):
    g = run["growth"]
    return g.top_sql("kernel_builds", counters.is_any) + \
        g.metric("tidb_tpu_xla_cache_total", 'result="miss"')
