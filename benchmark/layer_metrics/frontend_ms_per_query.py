"""Milliseconds of a query in the SQL front end: the self time of the
program's `parse` (AST-cache misses only), `statement` (admission, cache
lookups, building the executor, the statement's own bookkeeping) and
`plan` (plan-cache misses only) spans, from the `tidb:` segments of the
trace; see `program_spans.py`."""
import program_spans


def read(run):
    return program_spans.ms_per_query(run, ("parse", "statement", "plan"))
