"""The `tpch` data set for a deployment that the host's memory bounds,
not the chip's: generator, loader, statements and references are
`tpch.py`'s, every public name of it, unchanged. What this file adds is
a watch on the resident memory of the one process a run is.

Past `HOST_SHARE` of the host's memory the run ends itself: a line on
stderr with the reading, exit code 1. The host is the one the
configuration states, 40 GiB, or the machine's own memory where that is
less: the chip's machine reports 45 GiB and ends a process at 40.
Without the watch a process that outgrows its host is ended later by
the machine, with no word and no exit code of its own; so ended the
commit before PR 27 at scale 3, in one 29 GB compile (PERF.md, section
6). The line, 38.65 GB, stands between two readings (my chip runs, PR
27): 32.81 GB, the most PR 27's tree held, compiling all its programs
on an empty cache (25.6 GB with programs cached), and the 42.95 GB at
which the machine ended its parent. A run under the line is not
touched and leaves its highest reading on stderr when it exits.

The watch starts with `generate`, so a module that is only loaded (the
tests, the tools) starts no thread.
"""
import atexit
import importlib.util
import os
import sys
import threading
import time

HOST_BYTES = 40 << 30
HOST_SHARE = 0.90
PERIOD_S = 0.5


def _tpch():
    """tpch.py beside this file, under the name run.py loads it by."""
    full = "benchmark_datasets_tpch"
    if full not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tpch.py")
        spec = importlib.util.spec_from_file_location(full, path)
        sys.modules[full] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[full])
    return sys.modules[full]


_base = _tpch()
globals().update({k: v for k, v in vars(_base).items()
                  if not k.startswith("_")})


def _kib_field(path, field):
    """`field:   123 kB` of a /proc status file, in bytes; None where
    the file or the field is missing (no Linux, no /proc)."""
    try:
        with open(path) as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def host_bytes():
    return min(HOST_BYTES, _kib_field("/proc/meminfo", "MemTotal") or
               HOST_BYTES)


def resident_bytes():
    return _kib_field("/proc/self/status", "VmRSS")


def _end(held, host):
    print(f"benchmark: the process holds {held / 1e9:.2f} GB, past "
          f"{HOST_SHARE:.0%} of the host's {host / 1e9:.2f} GB: this "
          "deployment does not fit its host; the run ends itself "
          "(benchmark/datasets/tpch_host_bound.py)",
          file=sys.stderr, flush=True)
    os._exit(1)


def over(held, host):
    return held is not None and held > HOST_SHARE * host


_highest = [0]       # sampled: the chip's machine gave no VmHWM to read


def _watch(host, read=resident_bytes, end=_end, period=PERIOD_S):
    while True:
        held = read()
        if over(held, host):
            return end(held, host)
        _highest[0] = max(_highest[0], held or 0)
        time.sleep(period)


def _report(host):
    print(f"benchmark: host memory at most {_highest[0] / 1e9:.2f} GB of "
          f"{host / 1e9:.2f} GB (the run ends itself past "
          f"{HOST_SHARE * host / 1e9:.2f})", file=sys.stderr, flush=True)


_started = threading.Lock()


def start_watch():
    """Once a process."""
    if not _started.acquire(blocking=False):
        return
    host = host_bytes()
    threading.Thread(target=_watch, args=(host,), daemon=True,
                     name="bench-host-memory").start()
    atexit.register(_report, host)


def generate(sf, seed, **shape):
    start_watch()
    return _base.generate(sf, seed, **shape)
