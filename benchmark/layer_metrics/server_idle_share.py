"""The part of `device_idle_share` the server answers for: the share of
the traced window in which the busiest device is idle while some server
thread has a `tidb:` segment open, i.e. the chip waits for the program's
host path. `device_idle_share` minus this is the chip waiting for the
client and the wire. Also logs the tables `PERF.md` section 5 is written
from; see `program_spans.py`."""
import program_spans


def read(run):
    held = program_spans.server_idle(run)
    if held is None:
        return None
    program_spans.log_tables(run)
    return 100.0 * held[0] / held[1]
