"""Milliseconds of a query the connection thread spends in the wire
layer itself: the self time of the program's `command` span (reading the
packet's text, error replies, what follows the statement) and of
`wire_write` (encoding and sending the result set), from the `tidb:`
segments of the trace; see `program_spans.py`."""
import program_spans


def read(run):
    return program_spans.ms_per_query(run, ("command", "wire_write"))
