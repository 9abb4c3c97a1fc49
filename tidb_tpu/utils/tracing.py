"""Distributed span tracing + flight recorder (reference pkg/util/tracing
— span regions around statement stages, rendered by TRACE — and
pkg/util/traceevent — an in-memory ring of recent events that survives
until something goes wrong and is then inspectable).

Redesign notes: the reference pushes spans to OpenTracing and dumps the
flight-recorder ring to a file on triggers (session.go:2417-2423).
Here the ring IS the queryable surface — spans land in a bounded deque
exposed as `information_schema.tidb_trace_events`.

Trace context (docs/OBSERVABILITY.md "Distributed tracing"): every
root span mints a trace_id; child spans carry (trace_id, span_id,
parent_id), so the ring holds renderable trees instead of a flat
event list. A trace's events are BUFFERED in memory while it is open
and flushed to the ring only when the trace is sampled — a sampling
decision made at the root (the statement mints it; TRACE forces it;
mark_sampled() upgrades it retroactively, which is how slow statements
stay always-on without pre-paying ring writes for every fast OLTP
statement). Context crosses the RPC seam via install_remote /
uninstall_remote: the worker adopts the coordinator's (trace_id,
parent_id, sampled), records its spans under it, and hands the
finished events back to piggyback on the reply.

Module-level `span()` / `tag()` / `current_context()` ride a
thread-local "active tracer" installed by the innermost open root
span, so deep subsystems with no Domain reference (the WAL writer,
device_guard's retry loop, admission queues) can record spans without
plumbing a tracer through every constructor. With no active tracer on
the thread they are exact no-ops.

One span feeds three sinks (docs/OBSERVABILITY.md "Distributed
tracing"): the flight-recorder buffer above; the registry histogram
`tidb_tpu_span_seconds{span}`, observed with the span's inclusive
duration at close; and, while a `jax.profiler` session is active, the
profiler's trace, as FLAT SELF-TIME SEGMENTS: each thread keeps at most
one `TraceAnnotation` open, named `tidb:<span>` after its innermost
open span. Opening a child closes the parent's annotation, closing the
child reopens it, so a thread's line in the trace is a non-overlapping
sequence that says what the thread was doing at each instant, and the
sum of a name's segments is that span's self time with no tree to
rebuild. The annotations sit on the host plane beside whatever else
the process annotates, on the profiler's clock."""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from . import metrics as _metrics

# the profiler sink, resolved at the first span (importing this module
# must not import jax): jax.profiler.TraceAnnotation, or False where
# jax cannot be imported
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:                           # noqa: BLE001
            _ANNOTATION = False
    return _ANNOTATION


def _segment(tls, name):
    """Make `tidb:<name>` this thread's one open profiler annotation
    (none for name None). A flag check when no session is active."""
    open_ = getattr(tls, "ann", None)
    if open_ is not None:
        open_.__exit__(None, None, None)
        tls.ann = None
    ann = _ANNOTATION or _annotation()
    if name and ann and ann.is_enabled():
        tls.ann = ann("tidb:" + name)
        tls.ann.__enter__()


class SpanEvent(NamedTuple):
    """One finished span. The first six fields keep the legacy ring
    tuple layout (time, conn_id, depth, span, dur_ms, attrs) — the
    positional `ev[5]` surgery tag_recent used to do is now a named
    `_replace(attrs=...)` on an immutable record."""

    ts: float            # wall-clock close time
    conn_id: int
    depth: int
    name: str
    dur_ms: float
    attrs: str           # "k=v;k=v" rendered attributes
    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""
    worker: str = ""     # "" = coordinator/local domain

    @property
    def start_ts(self) -> float:
        return self.ts - self.dur_ms / 1000.0


class FlightRecorder:
    """Bounded ring of finished spans (reference traceevent ring)."""

    # retroactive tagging never walks more than this many ring slots:
    # the trigger fires right after the statement, so its spans sit at
    # the tail — an O(ring) full scan per slow statement was pure waste
    TAG_REACH_BACK = 512

    def __init__(self, cap: int = 4096):
        self.ring: collections.deque = collections.deque(maxlen=cap)
        self._mu = threading.Lock()

    def record(self, ev: SpanEvent):
        with self._mu:
            self.ring.append(ev)

    def record_many(self, evs):
        with self._mu:
            self.ring.extend(evs)

    def events(self) -> list:
        with self._mu:
            return list(self.ring)

    def tag_recent(self, conn_id: int, since: float, tag: str = "slow=1"):
        """Retroactively mark a connection's ring events recorded since
        `since`. Newest-first with an early stop at the first event
        older than `since` (plus the TAG_REACH_BACK hard bound), so the
        cost is proportional to the statement's own span count, not the
        ring size. Note: an OPEN trace's events are still buffered —
        mark_sampled()/tag() handle those; this reaches already-flushed
        flights only."""
        with self._mu:
            n = len(self.ring)
            for k in range(1, min(n, self.TAG_REACH_BACK) + 1):
                ev = self.ring[-k]
                if ev.ts < since:
                    break
                if ev.conn_id == conn_id and tag not in ev.attrs:
                    self.ring[-k] = ev._replace(
                        attrs=(ev.attrs + ";" + tag) if ev.attrs else tag)

    def clear(self):
        with self._mu:
            self.ring.clear()


def _render_attrs(attrs: dict) -> str:
    return ";".join(f"{k}={v}" for k, v in attrs.items())


class _Span:
    """An open span, and its own context manager: `with tracer.span(..)
    as sp` binds it, leaving the block closes it. (A class, not a
    generator: a statement opens dozens of spans, and two generator
    frames a span cost more than the span.)"""

    __slots__ = ("name", "depth", "start", "attrs", "conn_id",
                 "span_id", "parent_id", "tracer", "parent", "state",
                 "prev_active")

    def __init__(self, name, depth, attrs, conn_id, span_id, parent_id,
                 tracer=None, parent=None, state=None, prev_active=None):
        self.name = name
        self.depth = depth
        self.start = time.perf_counter()
        self.attrs = attrs
        self.conn_id = conn_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.tracer = tracer
        self.parent = parent            # None: this span is the root
        self.state = state
        self.prev_active = prev_active  # roots: the slot to restore

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.tracer._close(self)
        return False


class _NoSpan:
    """What span() hands back when nothing records: binds None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _TraceState:
    """Per-thread open-trace bookkeeping: the minted trace_id, the
    sampled decision, and the buffer finished child events accumulate
    in until the root closes (flush or drop)."""

    __slots__ = ("trace_id", "sampled", "buf", "remote")

    def __init__(self, trace_id, sampled, remote=None):
        self.trace_id = trace_id
        self.sampled = sampled
        self.buf: list = []
        self.remote = remote     # install_remote sink, or None


# thread-local active-tracer slot for the module-level helpers
_ACTIVE = threading.local()


class Tracer:
    """Per-domain tracer; span nesting + trace state tracked per
    thread. `worker` names this node in cross-worker trees ("" = the
    coordinator / a local single-domain engine)."""

    def __init__(self, recorder: FlightRecorder, worker: str = ""):
        self.recorder = recorder
        self.worker = worker
        self._tls = threading.local()
        self.enabled = True
        # CPython guarantees atomic __next__; ids stay unique across
        # threads without a lock, and the worker prefix keeps them
        # unique across processes in one trace tree
        self._seq = itertools.count(1)

    def _new_id(self, kind: str) -> str:
        w = self.worker or "c"
        return f"{kind}-{w}-{next(self._seq)}"

    # ---- remote context (the RPC piggyback seam) ---------------------

    def install_remote(self, trace_id: str, parent_id: str,
                       sampled: bool) -> None:
        """Adopt a caller's trace context on this thread: subsequent
        root spans join `trace_id` under `parent_id` and collect their
        finished events for uninstall_remote() to hand back."""
        self._tls.remote = {"trace_id": trace_id, "parent_id": parent_id,
                            "sampled": bool(sampled), "events": []}

    def uninstall_remote(self) -> list:
        """-> the SpanEvents recorded under the installed context (for
        the reply piggyback); clears the context."""
        r = getattr(self._tls, "remote", None)
        self._tls.remote = None
        return r["events"] if r is not None else []

    def absorb(self, events) -> None:
        """Fold remote (piggybacked) events into the current open
        trace's buffer so they flush with it; with no open trace they
        go straight to the ring (background jobs harvesting replies
        after their span closed)."""
        state = getattr(self._tls, "state", None)
        if state is not None:
            state.buf.extend(events)
        else:
            self.recorder.record_many(events)

    # ---- a worker thread on the statement's behalf ---------------------

    def handoff(self):
        """-> what a worker thread needs to record spans under this
        thread's innermost open span (device_guard's watchdog runs a
        statement's dispatch on a thread of its own), or None with no
        span open."""
        sp = getattr(self._tls, "cur", None)
        state = getattr(self._tls, "state", None)
        if sp is None or state is None:
            return None
        return (self, state.trace_id, sp.span_id, sp.depth, sp.conn_id)

    def adopt(self, handoff) -> list:
        """On the worker thread: spans opened here become children of
        the handed-off span, in its trace, and finish into the returned
        private list. The owner absorb()s the list when the work ends
        inside its budget; an abandoned worker's spans go nowhere."""
        _self, trace_id, span_id, depth, conn_id = handoff
        state = _TraceState(trace_id, False)
        self._tls.state = state
        # a parent that is not None, so spans here are never roots
        self._tls.cur = _Span(None, depth, {}, conn_id, span_id, "")
        _ACTIVE.tracer = self
        return state.buf

    # ---- trace state introspection -----------------------------------

    def current_context(self):
        """-> (trace_id, span_id, sampled, state) of the innermost open
        span on this thread, or None. The state reference lets fan-out
        threads append absorbed remote events to the owning trace."""
        sp = getattr(self._tls, "cur", None)
        state = getattr(self._tls, "state", None)
        if sp is None or state is None:
            return None
        return (state.trace_id, sp.span_id, state.sampled, state)

    def current_events(self) -> list:
        """Finished events of the open trace (TRACE renders from here
        while its statement span is still open)."""
        state = getattr(self._tls, "state", None)
        return list(state.buf) if state is not None else []

    def current_root(self):
        """(trace_id, innermost span) of the open trace, or None."""
        state = getattr(self._tls, "state", None)
        sp = getattr(self._tls, "cur", None)
        if state is None or sp is None:
            return None
        return state.trace_id, sp

    def mark_sampled(self):
        """Upgrade the open trace to sampled (flush at root close) —
        the slow-statement trigger and drained-something background
        polls call this after the fact."""
        state = getattr(self._tls, "state", None)
        if state is not None:
            state.sampled = True

    # ---- spans -------------------------------------------------------

    def span(self, name: str, conn_id: int | None = None,
             sampled: bool | None = None, trace_id: str | None = None,
             **attrs):
        """Open a span; a context manager that binds it (or None when
        the tracer is off). Nesting is per-thread; the outermost span on
        a thread is the trace ROOT: it mints (or adopts, under
        install_remote) the trace_id and owns the sampled decision —
        `sampled` / `trace_id` are honored only there. Child spans
        inherit conn_id and parent linkage automatically."""
        if not self.enabled:
            return NO_SPAN
        tls = self._tls
        parent = getattr(tls, "cur", None)
        prev_active = None
        if parent is None:
            remote = getattr(tls, "remote", None)
            if remote is not None:
                state = _TraceState(remote["trace_id"],
                                    remote["sampled"], remote)
                parent_id = remote["parent_id"]
            else:
                state = _TraceState(trace_id or self._new_id("t"),
                                    bool(sampled))
                parent_id = ""
            tls.state = state
            prev_active = getattr(_ACTIVE, "tracer", None)
            _ACTIVE.tracer = self
            if conn_id is None:
                conn_id = 0
            depth = 0
        else:
            state = tls.state
            parent_id = parent.span_id
            if conn_id is None:      # inherit: child spans (copr kernels)
                conn_id = parent.conn_id
            depth = parent.depth + 1
        sp = _Span(name, depth, attrs, conn_id, self._new_id("s"),
                   parent_id, self, parent, state, prev_active)
        tls.cur = sp
        _segment(tls, name)
        return sp

    def _close(self, sp):
        tls, parent, state = self._tls, sp.parent, sp.state
        tls.cur = parent
        # an adopted parent (name None) is open on its own thread
        _segment(tls, None if parent is None else parent.name)
        dur_s = time.perf_counter() - sp.start
        _metrics.SPAN_SECONDS.labels(sp.name).observe(dur_s)
        state.buf.append(SpanEvent(
            time.time(), sp.conn_id, sp.depth, sp.name, dur_s * 1000.0,
            _render_attrs(sp.attrs), state.trace_id, sp.span_id,
            sp.parent_id, self.worker))
        if parent is None:
            tls.state = None
            _ACTIVE.tracer = sp.prev_active
            remote = state.remote
            if remote is not None:
                # hand the whole subtree to the RPC reply; a
                # sampled remote trace ALSO lands in this worker's
                # own ring (locally inspectable mid-flight)
                remote["events"].extend(state.buf)
                if state.sampled:
                    self.recorder.record_many(state.buf)
            elif state.sampled:
                self.recorder.record_many(state.buf)
            # unsampled local trace: buffer dropped, ring untouched

    def tag(self, **attrs):
        """Attach attributes to the innermost open span (e.g. the slow
        trigger marking a statement's spans as interesting)."""
        sp = getattr(self._tls, "cur", None)
        if sp is not None:
            sp.attrs.update(attrs)

    def tag_buffered(self, tag: str = "slow=1"):
        """Tag the open trace's already-finished spans (plan/execute/
        copr closed into the buffer before the statement knew it was
        slow). In-place so concurrent absorb() extends stay safe."""
        state = getattr(self._tls, "state", None)
        if state is None:
            return
        buf = state.buf
        for i, ev in enumerate(buf):
            if tag not in ev.attrs:
                buf[i] = ev._replace(
                    attrs=(ev.attrs + ";" + tag) if ev.attrs else tag)


# ---- module-level helpers (for subsystems without a Domain) -----------

def active_tracer() -> Tracer | None:
    return getattr(_ACTIVE, "tracer", None)


def current_context():
    """Trace context of this thread's active tracer (None when no span
    is open). Fan-out threads receive it via set_thread_context."""
    ctx = getattr(_ACTIVE, "ctx", None)
    if ctx is not None:
        return ctx
    t = getattr(_ACTIVE, "tracer", None)
    return t.current_context() if t is not None else None


def set_thread_context(ctx) -> None:
    """Install an explicit trace context on this thread (cluster
    fan-out workers carry the coordinator statement's context across
    the thread boundary). Pass None to clear."""
    _ACTIVE.ctx = ctx


def span(name: str, **attrs):
    """Open a child span on this thread's active tracer; binds None and
    records nothing when none is active (background threads, untraced
    fast path)."""
    t = getattr(_ACTIVE, "tracer", None)
    if t is None:
        return NO_SPAN
    return t.span(name, **attrs)


def tag(**attrs) -> None:
    t = getattr(_ACTIVE, "tracer", None)
    if t is not None:
        t.tag(**attrs)


def handoff():
    """Tracer.handoff() of this thread's active tracer, or None."""
    t = getattr(_ACTIVE, "tracer", None)
    return t.handoff() if t is not None else None


def adopt(handed) -> list:
    """Tracer.adopt() on a worker thread; [] for a None handoff."""
    return handed[0].adopt(handed) if handed is not None else []


def absorb(events) -> None:
    """Fold a worker's finished spans into this thread's open trace."""
    t = getattr(_ACTIVE, "tracer", None)
    if t is not None and events:
        t.absorb(events)
