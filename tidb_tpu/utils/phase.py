"""Per-query phase accounting: where does a device query's wall time go?

The reference surfaces per-operator runtime stats through
pkg/util/execdetails (EXPLAIN ANALYZE's execution info column); this is
the TPU-engine analog at the *backend* altitude: counters accumulated by
the copr layer while a statement runs — kernel dispatch count and time,
kernel builds (trace+compile), host<->device upload time/bytes, device
buffer-pool hits, host-path execution time.

Collection points are central (one wrapper around every cached kernel,
one inside the device buffer pool), so new operators are covered for
free. Reset/snapshot is explicit: bench.py and EXPLAIN ANALYZE bracket
each statement with reset()/snap().

State is THREAD-LOCAL: each connection/background thread accumulates
into its own dict, so concurrent statements attribute their device time
to their own digest (Top SQL) instead of blurring into whichever
statement folds first. Nested internal SQL runs on its outer
statement's thread and accumulates into it by design (see
stmt_enter/depth). A worker thread doing a statement's dispatch on its
behalf (device_guard's watchdog) adopt()s a private dict that the owner
folds into its own when the dispatch ends inside its budget.

Timing a dispatch measures the *call* (async on TPU: the host returns
before the kernel finishes): `dispatch_s` is enqueue time, the wait
shows up in `fetch_s`/`sync_s`, and device time is the profiler
trace's. The two central wrappers also open the `dispatch` and `fetch`
spans (utils/tracing), so every kernel and every wait for the device
lands in the statement's trace, and in the profiler's as
`tidb:dispatch` / `tidb:fetch` segments, with no per-operator code.
"""
import threading
import time

from . import kernel_stages as _stages
from . import tracing as _tracing

_TLS = threading.local()


def _cur() -> dict:
    d = getattr(_TLS, "stats", None)
    if d is None:
        d = _TLS.stats = {}
    return d


def current() -> dict:
    """The calling thread's live stats dict — hand it to a worker
    thread via adopt() so dispatch done on this statement's behalf
    still lands on this statement."""
    return _cur()


def adopt(stats: dict):
    """Record this thread's phase counters into another thread's dict
    (device_guard watchdog workers)."""
    _TLS.stats = stats


def reset():
    _cur().clear()


def stmt_enter():
    """Called at statement start: reset ONLY for the outermost
    statement; nested (internal-SQL) statements accumulate into it.
    Nesting is per-thread — a statement on another connection's thread
    neither clears nor inherits this one's counters."""
    dep = getattr(_TLS, "depth", 0)
    if dep == 0:
        _cur().clear()
    _TLS.depth = dep + 1


def stmt_leave():
    _TLS.depth = max(getattr(_TLS, "depth", 0) - 1, 0)


def depth() -> int:
    """Statement nesting depth on this thread (1 = inside the outermost
    statement). Top SQL folds phase snapshots only at depth 1 so
    internal SQL never double-attributes the outer statement's
    accumulated counters."""
    return getattr(_TLS, "depth", 0)


def add(key, val):
    d = _cur()
    d[key] = d.get(key, 0) + val


def inc(key):
    d = _cur()
    d[key] = d.get(key, 0) + 1


def snap():
    """-> {phase: value} with times in ms (rounded), counters as-is."""
    out = {}
    for k, v in sorted(_cur().items()):
        out[k] = round(v * 1000, 2) if k.endswith("_s") else v
    return out


def _awaits_device(arr) -> bool:
    """True while the array's program has not finished: materialising
    it now blocks the host on the device, and that wait is what the
    `fetch` span is for. A ready array costs only its copy to the host,
    mostly prefetched: it gets no span (the time stays in the enclosing
    span; `fetch_s`/`sync_s` count it all the same), which keeps a
    statement's span count near its dispatch count instead of its
    output-array count."""
    return not arr.is_ready()


def _fetch_span(arr):
    return _tracing.span("fetch") if _awaits_device(arr) \
        else _tracing.NO_SPAN


def _install_fetch_timer():
    """Time every device->host materialization centrally by wrapping
    jax.Array's host-conversion dunders: __array__ (bulk fetches via
    np.asarray) as fetch_s/fetch_bytes, and scalar conversions
    (__bool__/__int__/__float__/__index__) as sync_s — each of those is
    a blocking device round-trip. Dispatch alone under-accounts a small
    query: on an accelerator its wall time lives in the result fetch."""
    try:
        from jax._src.array import ArrayImpl
    except Exception as e:                          # noqa: BLE001
        # never silent: without this the fetch_s/sync_s columns the
        # bench sidecar documents just vanish (e.g. a jax upgrade
        # moving jax._src.array)
        import sys
        print(f"# phase: fetch timer NOT installed ({e}); "
              "fetch_s/sync_s will be absent", file=sys.stderr)
        return
    if getattr(ArrayImpl, "_tidb_fetch_timed", False):
        return

    orig_array = ArrayImpl.__array__

    def timed_array(self, *a, **kw):
        with _fetch_span(self) as sp:
            t0 = time.perf_counter()
            out = orig_array(self, *a, **kw)
            add("fetch_s", time.perf_counter() - t0)
            nbytes = getattr(out, "nbytes", 0)
            if sp is not None:
                sp.attrs["bytes"] = nbytes
        add("fetch_bytes", nbytes)
        inc("fetches")
        return out

    ArrayImpl.__array__ = timed_array

    for name in ("__bool__", "__int__", "__float__", "__index__"):
        orig = getattr(ArrayImpl, name, None)
        if orig is None:
            continue

        def timed_scalar(self, _orig=orig):
            with _fetch_span(self) as sp:
                t0 = time.perf_counter()
                out = _orig(self)
                add("sync_s", time.perf_counter() - t0)
                if sp is not None:
                    sp.attrs["bytes"] = self.dtype.itemsize
            inc("syncs")
            return out

        setattr(ArrayImpl, name, timed_scalar)
    ArrayImpl._tidb_fetch_timed = True


try:
    _install_fetch_timer()
except Exception as _e:                             # noqa: BLE001
    import sys as _sys
    print(f"# phase: fetch timer NOT installed ({_e}); "
          "fetch_s/sync_s will be absent", file=_sys.stderr)


# what answered a snapshot of a columnar table: the outcomes of
# tidb_tpu_snapshot_facts_total, counted here a statement
_FACTS = ("hit", "build", "bypass_read_ts", "bypass_overlay")


def note_facts(outcome):
    """storage/columnar.py _facts_at: one snapshot of a table answered
    as `outcome`; the open `bind` span reads what grew."""
    inc("facts_" + outcome)


class bind_span:
    """The `bind` span: the host readying a kernel's operands (delta
    fold, snapshot, column binding, padding and upload). At close it
    carries what the statement's upload counters grew by inside it
    and, where a table was snapshot inside it, what answered
    (`facts`: `hit`, or every outcome that occurred, `+`-joined)."""

    __slots__ = ("_cm", "_sp", "_stats", "_bytes", "_hits", "_facts")

    def __enter__(self):
        self._cm = _tracing.span("bind")
        sp = self._sp = self._cm.__enter__()
        if sp is not None:
            d = self._stats = _cur()
            self._bytes = d.get("upload_bytes", 0)
            self._hits = d.get("upload_hits", 0)
            self._facts = [d.get("facts_" + o, 0) for o in _FACTS]
        return sp

    def __exit__(self, *exc):
        sp = self._sp
        if sp is not None:
            d = self._stats
            sp.attrs["upload_bytes"] = d.get("upload_bytes", 0) - \
                self._bytes
            sp.attrs["pool_hits"] = d.get("upload_hits", 0) - self._hits
            facts = [o for o, was in zip(_FACTS, self._facts)
                     if d.get("facts_" + o, 0) != was]
            if facts:
                sp.attrs["facts"] = "+".join(facts)
        return self._cm.__exit__(*exc)


class row_block:
    """The row block the thread works on while the context is open:
    `part` (0-based) of `parts`. The copr partition loops open it round
    one block's dispatch and round its consume; the `dispatch` and
    `consume` spans inside carry both numbers (`part_attrs`), so a
    trace shows which block of a many-block scan a kernel or a merge
    belonged to. Nests; a kernel dispatched outside any loop (vector
    search, a mesh program over the whole table) carries neither."""

    __slots__ = ("_part", "_prev")

    def __init__(self, part, parts):
        self._part = (part, parts)

    def __enter__(self):
        self._prev = getattr(_TLS, "part", None)
        _TLS.part = self._part
        return self

    def __exit__(self, *exc):
        _TLS.part = self._prev
        return False


def part_attrs() -> dict:
    """{"part", "parts"} of the open row block, else {}."""
    p = getattr(_TLS, "part", None)
    return {} if p is None else {"part": p[0], "parts": p[1]}


def timed_kernel(kind, fn):
    """Wrap a compiled kernel callable with dispatch accounting and the
    `dispatch` span (the enqueue; `kind` is the cache key's; `part` /
    `parts` inside a row-block loop). The first call is recorded
    separately (it pays the XLA trace+compile). Under a profiler
    session the program is noted for the stage catalogue
    (utils/kernel_stages); without one that costs the flag's read."""
    state = {"first": True}

    def wrapped(*args, **kw):
        if _stages.session_active():
            _stages.note(kind, fn, args, kw)
        with _tracing.span("dispatch", kind=kind, **part_attrs()) as sp:
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            dt = time.perf_counter() - t0
            first, state["first"] = state["first"], False
            if first and sp is not None:
                sp.attrs["first"] = 1
        inc("dispatches")
        if first:
            inc("kernel_builds")
            add("compile_s", dt)
        else:
            add("dispatch_s", dt)
        return out

    wrapped.__wrapped__ = fn
    return wrapped
