"""Device dispatches a query: `dispatches` of the window's analytic
statements (the phase counters the program folds by digest into
information_schema.tidb_top_sql) over their executions."""
import counters


def read(run):
    g = run["growth"]
    n = g.top_sql("exec_count", counters.is_query)
    return g.top_sql("dispatches", counters.is_query) / n if n else None
