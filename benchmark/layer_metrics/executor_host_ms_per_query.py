"""Milliseconds of a query the executors keep the host busy: the self
time of the program's `execute` span (the Volcano operators' host work,
final merge, sort and projection), of `consume` (validating and merging
a partition's partials), and of `copr` and `device_attempt` (the host
code between binding, dispatch and consumption), from the `tidb:`
segments of the trace; see `program_spans.py`. Binding, the enqueue and
the wait for the device are not in it."""
import program_spans


def read(run):
    return program_spans.ms_per_query(
        run, ("execute", "consume", "copr", "device_attempt"))
