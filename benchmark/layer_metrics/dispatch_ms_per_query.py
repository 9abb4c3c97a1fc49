"""Milliseconds of a query the host spends enqueuing kernels: the self
time of the program's `dispatch` span (the call of a compiled kernel,
which returns before the device finishes), from the `tidb:` segments of
the trace; see `program_spans.py`. The wait for the device is `fetch`."""
import program_spans


def read(run):
    return program_spans.ms_per_query(run, ("dispatch",))
