"""Hierarchical memory tracker with an action chain on quota breach
(reference pkg/util/memory/tracker.go:78 + the oom-action chain of
pkg/executor/internal/exec + sessionctx OOMAction).

The tree is session -> statement -> operator, rooted at
``domain.mem_root``. Every `consume` walks to the root under ONE lock
per tree (concurrent statements share the session/global ancestors:
an unlocked walk loses updates), updating `consumed`/`max_consumed`;
`release` floors at the releasing tracker's own remaining consumption
so a double-release can never drive the tree negative; `detach` (end
of statement/operator) releases whatever is still tracked and
disconnects the node, which is what makes the global accounting
balance to zero at quiesce no matter how the statement exited.

Quota breach runs the ACTION CHAIN, strictly in this order:

  1. LOG    — first breach of a tracker logs a warning (always).
  2. SPILL  — every registered-but-unarmed spill trigger arms; the
              owning operator (sort/agg/join, executor/executors.py)
              polls `trigger.armed`, spools its buffered input to disk
              and releases the bytes. While a spill is armed and not
              yet done the chain never cancels — disk is cheaper than
              a dead statement.
  3. CANCEL — no spill can help: per ``tidb_tpu_oom_action``,
              'cancel' raises MemoryQuotaExceededError (ER 8175,
              the statement dies cleanly), 'log' records and lets the
              statement proceed (operator-has-no-choice mode, like
              the reference's LogOnExceed).

Consumption from a buffer the CALLER can spill passes
``can_spill=True``: such a breach arms triggers but never cancels —
the operator itself guarantees a spill decision on its next poll.

HBM is NOT on this tree. Buffers the copr upload seams place in the
device-resident store (dag_exec._upload_padded and every _dev_put*
above it) outlive the statement that faulted them in, so they are
charged to the store's own byte budget (copr/residency.py) and shed by
its LRU and by device_guard's pressure protocol — never to a
statement's quota: a first touch of a large table is not a reason for
ER 8175. The tree governs what a statement itself holds on the host.

The ROOT tracker supports a soft limit (``soft_limit_fn`` +
``on_soft_breach``): the Domain wires the tidb_tpu_server_memory_limit
global controller there — on server-level breach the controller
cancels the single largest-consumer statement through the KILL seam
with ER 8175 (shed one query, never wedge or die); a victim's
statement tracker is flagged so its very next consume raises even if
it never reaches a check_killed poll.
"""
from __future__ import annotations

from . import metrics as _metrics
from .logutil import log
from ..errors import MemoryQuotaExceededError
from . import lockrank


class SpillTrigger:
    """Spill handle an operator registers on its statement tracker.
    The action chain ARMS it on quota breach; the operator polls
    `armed`, spools, and sets `done=True` once its buffered bytes are
    on disk (after which further breaches fall through to cancel)."""

    __slots__ = ("label", "armed", "done")

    def __init__(self, label: str):
        self.label = label
        self.armed = False
        self.done = False


class Tracker:
    def __init__(self, label: str, quota: int = -1,
                 parent: "Tracker" = None):
        self.label = label
        self.quota = quota
        self.parent = parent
        self.consumed = 0
        self.max_consumed = 0
        self.closed = False
        # 'cancel' | 'log' | None (inherit nearest ancestor, default
        # cancel); set from the tidb_tpu_oom_action sysvar on statement
        # trackers (executor/exec_base.ExecContext)
        self.oom_action = None
        self._spills: list = []
        self._logged = False
        self._kill_msg = None
        # consumption ceiling an armed-but-unfinished spill may grow
        # to before a NON-spillable breach stops deferring to it (the
        # arming point + one more quota of headroom): a blocked
        # operator whose pending spill cannot relieve the pressure —
        # a cross join draining under a sort's armed trigger — must
        # not ride that trigger past the quota forever
        self._spill_barrier = None
        # root-only soft limit (the server memory controller): checked
        # on every consume that reaches the root; the hook runs OUTSIDE
        # the tree lock
        self.soft_limit_fn = None
        self.on_soft_breach = None
        # ONE lock per tree: concurrent consume/release on shared
        # ancestors must serialize or updates are lost
        self._lock = parent._lock if parent is not None \
            else lockrank.ranked_rlock("memory.tracker")

    def child(self, label: str, quota: int = -1) -> "Tracker":
        return Tracker(label, quota, self)

    # ---- spill triggers (the chain's step 2) --------------------------
    def add_spill_trigger(self, label: str) -> SpillTrigger:
        t = SpillTrigger(label)
        with self._lock:
            self._spills.append(t)
        return t

    def remove_spill_trigger(self, t: SpillTrigger):
        with self._lock:
            if t in self._spills:
                self._spills.remove(t)

    # ---- server kill (global memory controller) -----------------------
    def mark_server_kill(self, msg: str):
        """Flag this (statement) tracker as the server-level victim:
        its very next consume raises ER 8175 even if the statement
        never reaches a check_killed poll."""
        with self._lock:
            self._kill_msg = msg

    # ---- accounting ---------------------------------------------------
    def consume(self, n: int, can_spill: bool = False):
        """Track n more bytes here and in every ancestor. Quota breach
        runs the action chain (log -> spill trigger -> cancel); a
        breach from spillable consumption arms triggers but never
        cancels. Negative n releases."""
        if n < 0:
            self.release(-n)
            return
        breached = []
        root_hook = None
        kill_msg = None
        with self._lock:
            t = self
            while t is not None:
                if t._kill_msg is not None and kill_msg is None:
                    kill_msg = t._kill_msg
                t.consumed += n
                if t.consumed > t.max_consumed:
                    t.max_consumed = t.consumed
                if t.quota and t.quota > 0 and t.consumed > t.quota:
                    breached.append(t)
                if t.parent is None and t.soft_limit_fn is not None \
                        and t.on_soft_breach is not None:
                    lim = t.soft_limit_fn()
                    if lim and t.consumed > lim:
                        root_hook = t
                t = t.parent
        if kill_msg is not None:
            raise MemoryQuotaExceededError(kill_msg)
        for t in breached:
            t._run_action_chain(can_spill)
        if root_hook is not None:
            root_hook.on_soft_breach(root_hook)

    def _run_action_chain(self, can_spill: bool):
        """log -> spill trigger -> cancel, outside the tree lock (a
        spill callback or the raise must not deadlock the tree)."""
        with self._lock:
            first = not self._logged
            self._logged = True
            armed_new = False
            live_spill = False
            for trig in self._spills:
                if not trig.armed:
                    trig.armed = True
                    armed_new = True
                elif not trig.done:
                    live_spill = True
            if armed_new:
                self._spill_barrier = self.consumed + max(self.quota, 0)
            if live_spill and not can_spill and \
                    self._spill_barrier is not None and \
                    self.consumed > self._spill_barrier:
                # the armed spill has not relieved anything within a
                # whole extra quota of growth — its owner is blocked
                # under the consumer (cross join under a sort): stop
                # deferring, fall through to the action
                live_spill = False
            action = None
            t = self
            while t is not None and action is None:
                action = t.oom_action
                t = t.parent
        if first:
            log("warn", "mem_quota_breach", tracker=self.label,
                consumed=self.consumed, quota=self.quota)
        if armed_new:
            _metrics.MEM_PRESSURE.labels("spill_trigger").inc()
        if armed_new or live_spill or can_spill:
            # a spill is armed (or the consumer itself spills): give it
            # the chance to shed to disk before anything cancels
            return
        if (action or "cancel") == "log":
            _metrics.MEM_PRESSURE.labels("oom_log").inc()
            return
        _metrics.MEM_PRESSURE.labels("oom_cancel").inc()
        raise MemoryQuotaExceededError(
            "Out Of Memory Quota! [%s] consumed %d > quota %d "
            "(tidb_mem_quota_query / MEMORY_QUOTA hint; action chain "
            "found nothing left to spill)",
            self.label, self.consumed, self.quota)

    def release(self, n: int):
        """Release up to n bytes: floored at this tracker's own
        remaining consumption, and the SAME amount is subtracted from
        every ancestor — a double-release (or a release racing a
        detach) can never drive the tree negative or desync it."""
        if n <= 0:
            return
        with self._lock:
            actual = min(n, self.consumed)
            if actual <= 0:
                return
            t = self
            while t is not None:
                t.consumed = max(t.consumed - actual, 0)
                t = t.parent

    def detach(self):
        """End of scope (statement done, operator closed): release
        whatever is still tracked from every ancestor and disconnect.
        Idempotent; late consumes/releases on a detached tracker stay
        local to it and can no longer touch the tree."""
        with self._lock:
            if self.closed:
                return
            rem = self.consumed
            t = self.parent
            while t is not None:
                t.consumed = max(t.consumed - rem, 0)
                t = t.parent
            self.consumed = 0
            self.parent = None
            self.closed = True

    def track_array(self, arr):
        self.consume(getattr(arr, "nbytes", 0))
        return arr
