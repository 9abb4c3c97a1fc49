"""Milliseconds of a query spent readying kernel operands: the self
time of the program's `bind` span (delta fold, snapshot, column binding,
padding, upload or resident-pool lookup), from the `tidb:` segments of
the trace; see `program_spans.py`."""
import program_spans


def read(run):
    return program_spans.ms_per_query(run, ("bind",))
