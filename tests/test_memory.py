"""Memory safety under pressure (ISSUE 10, docs/ROBUSTNESS.md "Memory
safety"): the action-chain tracker (utils/memory.py), operator spill
wiring, HBM upload accounting + pressure protocol, the global memory
controller, and the information_schema surfaces. The full chaos gate is
scripts/mem_smoke.py; the fast storm slice at the bottom is its tier-1
stand-in."""
import threading

import pytest

from tidb_tpu.errors import MemoryQuotaExceededError
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import metrics as metrics_util
from tidb_tpu.utils.memory import Tracker


@pytest.fixture()
def ftk():
    return TestKit()


def _pressure(action):
    return metrics_util.MEM_PRESSURE.labels(action).value


# ---- tracker unit tests ------------------------------------------------

class TestTracker:
    def test_hierarchy_consume_release_detach(self):
        root = Tracker("root")
        sess = root.child("sess")
        stmt = sess.child("stmt", quota=1 << 30)
        op = stmt.child("op")
        op.consume(100)
        assert (op.consumed, stmt.consumed, sess.consumed,
                root.consumed) == (100, 100, 100, 100)
        op.release(40)
        assert (op.consumed, root.consumed) == (60, 60)
        assert op.max_consumed == 100 and root.max_consumed == 100
        op.detach()
        assert op.closed and op.consumed == 0
        assert stmt.consumed == 0 and root.consumed == 0
        op.detach()                      # idempotent
        # a late consume on a detached tracker stays local to it
        op.consume(5)
        assert root.consumed == 0

    def test_concurrent_consume_release_regression(self):
        """The round-1 Tracker raced: concurrent consume/release on a
        shared parent lost updates (unlocked += walk). 8 threads x 2k
        balanced consume/release pairs must net to EXACTLY zero."""
        root = Tracker("root")
        sess = root.child("sess")

        def work():
            t = sess.child("stmt")
            for _ in range(2000):
                t.consume(64)
                t.release(64)
            t.detach()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert root.consumed == 0, root.consumed
        assert sess.consumed == 0, sess.consumed
        assert root.max_consumed >= 64

    def test_double_release_floors(self):
        """A double-release must not drive the tree negative (the
        round-1 bug): the release floors at the tracker's own remaining
        consumption and subtracts the SAME amount from ancestors."""
        root = Tracker("root")
        a = root.child("a")
        b = root.child("b")
        a.consume(100)
        b.consume(50)
        a.release(100)
        a.release(100)                  # double release: no-op
        assert a.consumed == 0
        assert root.consumed == 50      # b's bytes survive intact
        b.detach()
        b.detach()
        assert root.consumed == 0

    def test_quota_breach_cancels_with_8175(self):
        stmt = Tracker("stmt", quota=1000)
        stmt.consume(900)
        with pytest.raises(MemoryQuotaExceededError) as ei:
            stmt.consume(200)
        assert ei.value.code == 8175
        assert "Out Of Memory Quota!" in ei.value.msg

    def test_oom_action_log_continues(self):
        stmt = Tracker("stmt", quota=1000)
        stmt.oom_action = "log"
        n0 = _pressure("oom_log")
        stmt.consume(2000)              # no raise
        assert stmt.consumed == 2000
        assert _pressure("oom_log") == n0 + 1

    def test_oom_action_inherited_from_ancestor(self):
        sess = Tracker("sess")
        sess.oom_action = "log"
        stmt = sess.child("stmt", quota=100)
        stmt.consume(500)               # nearest set action wins: log

    def test_spill_trigger_arms_before_cancel(self):
        stmt = Tracker("stmt", quota=1000)
        trig = stmt.add_spill_trigger("sort")
        n0 = _pressure("spill_trigger")
        stmt.consume(1500)              # chain arms the spill, no raise
        assert trig.armed and not trig.done
        assert _pressure("spill_trigger") == n0 + 1
        # spill still pending: further breaches keep waiting for it
        stmt.consume(100)
        # operator spilled and released; the next breach has nothing
        # left to shed -> cancel
        trig.done = True
        stmt.release(1600)
        with pytest.raises(MemoryQuotaExceededError):
            stmt.consume(5000)

    def test_blocked_spill_barrier(self):
        """Review-round regression: non-spillable breaches defer to an
        armed-but-unfinished spill only until consumption grows one
        whole quota past the arming point — a blocked owner's trigger
        cannot shield a foreign drain forever."""
        stmt = Tracker("stmt", quota=1000)
        stmt.add_spill_trigger("sort")
        stmt.consume(1500)      # breach arms; barrier = 1500 + 1000
        stmt.consume(500)       # 2000 <= 2500: still deferring
        with pytest.raises(MemoryQuotaExceededError):
            stmt.consume(1000)  # 3000 > 2500: the spill never helped

    def test_can_spill_never_cancels(self):
        stmt = Tracker("stmt", quota=1000)
        stmt.consume(5000, can_spill=True)
        assert stmt.consumed == 5000

    def test_server_kill_flag_raises_on_next_consume(self):
        stmt = Tracker("stmt")
        op = stmt.child("op")
        stmt.mark_server_kill("server memory limit reached")
        with pytest.raises(MemoryQuotaExceededError) as ei:
            op.consume(1)               # flag observed through the walk
        assert "server memory limit" in ei.value.msg


# ---- SQL-level wiring --------------------------------------------------

class TestStatementMemory:
    def _load(self, ftk, n=30000):
        ftk.must_exec("create table tm (a bigint, b bigint, s varchar(24))")
        rows = ",".join(f"({(i * 7919) % 10007}, {i}, 'v{i % 97}')"
                        for i in range(n))
        ftk.must_exec(f"insert into tm values {rows}")

    def test_sort_spill_fires_from_chain(self, ftk):
        self._load(ftk)
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        n0 = metrics_util.SPILLS.labels("sort").value
        rs = ftk.must_query("select a, b from tm order by a, b")
        vals = [r[0] for r in rs.rows]
        assert vals == sorted(vals) and len(vals) == 30000
        assert metrics_util.SPILLS.labels("sort").value > n0
        assert ftk.domain.metrics.get("sort_spill_count", 0) >= 1
        # the statement ends balanced: every tracked byte released
        assert ftk.domain.mem_root.consumed == 0

    def test_memory_quota_hint_reaches_operators(self, ftk):
        """MEMORY_QUOTA hint end-to-end (satellite): the session quota
        is the 1GB default, only the hint is tight — the spill must
        still fire, via plan.exec_hints -> ExecContext.mem_quota ->
        spill_quota."""
        self._load(ftk, n=60000)
        n0 = metrics_util.SPILLS.labels("sort").value
        rs = ftk.must_query(
            "select /*+ MEMORY_QUOTA(1 MB) */ a, b from tm "
            "order by a, b")
        assert len(rs.rows) == 60000
        assert metrics_util.SPILLS.labels("sort").value > n0
        # control: without the hint (1GB quota) the same statement
        # must NOT spill
        n1 = metrics_util.SPILLS.labels("sort").value
        ftk.must_query("select a, b from tm order by a, b")
        assert metrics_util.SPILLS.labels("sort").value == n1

    def test_join_spill_labeled_metric(self, ftk):
        self._load(ftk, n=20000)
        ftk.must_exec("create table tj (a bigint, c bigint)")
        rows = ",".join(f"({i % 10007}, {i})" for i in range(20000))
        ftk.must_exec(f"insert into tj values {rows}")
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        n0 = metrics_util.SPILLS.labels("join").value
        rs = ftk.must_query(
            "select /*+ HASH_JOIN(tm) */ count(*) from tm "
            "join tj on tm.a = tj.a")
        assert rs.rows[0][0] > 0
        assert metrics_util.SPILLS.labels("join").value > n0
        assert ftk.domain.metrics.get("join_spill_count", 0) >= 1
        assert ftk.domain.mem_root.consumed == 0

    def test_nonspillable_breach_cancels_8175(self, ftk):
        """An ungrouped DISTINCT agg has no spill path: the chain runs
        to its cancel step and the statement dies cleanly with ER
        8175, leaving the session usable and the accounting at zero."""
        self._load(ftk)
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        n0 = _pressure("oom_cancel")
        e = ftk.exec_err("select count(distinct a), count(distinct b), "
                         "count(distinct s) from tm")
        assert e.code == 8175
        assert _pressure("oom_cancel") == n0 + 1
        assert ftk.domain.mem_root.consumed == 0
        # session survives and works
        ftk.must_exec("set @@tidb_mem_quota_query = 1073741824")
        assert ftk.must_query("select count(*) from tm").rows[0][0] == 30000

    def test_oom_action_log_lets_statement_complete(self, ftk):
        self._load(ftk)
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        ftk.must_exec("set @@tidb_tpu_oom_action = 'log'")
        n0 = _pressure("oom_log")
        rs = ftk.must_query("select count(distinct a), count(distinct b),"
                            " count(distinct s) from tm")
        assert rs.rows[0][0] > 0
        assert _pressure("oom_log") > n0

    def test_blocked_spill_cannot_shield_nonspillable_drain(self, ftk):
        """Review-round regression: a cross join (no spill path)
        draining under a sort whose trigger is armed-but-blocked must
        still cancel once it grows a whole extra quota past the arming
        point — the pending spill cannot relieve the join's input."""
        ftk.must_exec("create table big (a bigint, b bigint)")
        for s in range(0, 50000, 10000):
            rows = ",".join(f"({(i * 13) % 9973}, {i})"
                            for i in range(s, s + 10000))
            ftk.must_exec(f"insert into big values {rows}")
        ftk.must_exec("create table small (c bigint)")
        ftk.must_exec("insert into small values (1), (2)")
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        # UNION ALL probe: the join drains MULTIPLE chunks, so growth
        # continues past the arming point — the spill barrier (arm
        # point + one quota) must stop the armed-but-blocked sort
        # trigger from shielding the join forever
        e = ftk.exec_err(
            "select u.a from (select a, b from big union all "
            "select a, b from big) u, small order by u.a")
        assert e.code == 8175, e
        assert ftk.domain.mem_root.consumed == 0

    def test_dml_statement_atomicity_on_quota_breach(self, ftk):
        """A mid-operator MemoryQuotaExceededError rolls the DML
        statement back WHOLLY: the buffered INSERT..SELECT applies
        nothing, and the next statement sees a clean table + balanced
        accounting (satellite)."""
        self._load(ftk)
        ftk.must_exec("create table tgt (a bigint)")
        ftk.must_exec("set @@tidb_mem_quota_query = 131072")
        e = ftk.exec_err(
            "insert into tgt select count(distinct a) + "
            "count(distinct b) + count(distinct s) from tm")
        assert e.code == 8175
        ftk.must_exec("set @@tidb_mem_quota_query = 1073741824")
        assert ftk.must_query("select count(*) from tgt").rows[0][0] == 0
        assert ftk.domain.mem_root.consumed == 0
        st = ftk.domain.copr._dev_store.stats()
        assert st["bytes"] == sum(st["bytes_by_spec"].values())
        # table stays writable after the rollback
        ftk.must_exec("insert into tgt values (1)")
        assert ftk.must_query("select count(*) from tgt").rows[0][0] == 1

    def test_upload_bytes_charge_statement_tracker(self, ftk):
        """HBM coordination (PR 27): uploads into the resident store
        outlive the statement, so they are charged to the store's
        budget and NOT to the tracker of the statement that faulted
        them in (its mem_max stays under the bytes it uploaded); the
        tree still balances to zero at statement end."""
        self._load(ftk)
        ftk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
        store = ftk.domain.copr._dev_store
        held = store.bytes
        ftk.must_query("select sum(b) from tm where a < 5000")
        uploaded = store.bytes - held
        assert uploaded > 0
        assert ftk.sess._stmt_mem_max < uploaded
        assert ftk.domain.mem_root.consumed == 0
        assert ftk.domain.mem_root.max_consumed < uploaded
        assert store.max_bytes >= store.bytes > 0

    def test_mem_max_in_slow_query_and_summary(self, ftk):
        self._load(ftk, n=20000)
        ftk.must_exec("set @@tidb_slow_log_threshold = 0")
        ftk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
        # a statement that holds memory of its own (the sort's rows):
        # resident uploads are the pool's since PR 27, so a bare device
        # scan has nothing to report here
        ftk.must_query("select a, b from tm where a < 9000 order by b")
        rows = ftk.must_query(
            "select query, mem_max from information_schema.slow_query "
            "where query like 'select a, b%'").rows
        assert rows and rows[-1][1] > 0, rows
        rows = ftk.must_query(
            "select digest_text, mem_max from "
            "information_schema.statements_summary "
            "where digest_text like 'select a , b%'").rows
        assert rows and max(r[1] for r in rows) > 0, rows

    def test_memory_usage_vtable(self, ftk):
        self._load(ftk, n=5000)
        ftk.must_query("select count(*) from tm")
        rows = ftk.must_query(
            "select scope, label, consumed, max_consumed, quota "
            "from information_schema.memory_usage").rows
        scopes = {r[0] for r in rows}
        assert "global" in scopes and "session" in scopes
        g = next(r for r in rows if r[0] == "global")
        assert g[1] == "global" and g[2] >= 0 and g[3] >= 0
        sess_rows = [r for r in rows if r[0] == "session"]
        assert any(f"conn {ftk.sess.conn_id}" == r[1] for r in sess_rows)


class TestGlobalController:
    def test_server_limit_sheds_largest_statement(self, ftk):
        ftk.must_exec("create table gm (a bigint, b bigint, "
                      "s varchar(24))")
        rows = ",".join(f"({i}, {i * 3}, 'v{i % 89}')"
                        for i in range(40000))
        ftk.must_exec(f"insert into gm values {rows}")
        # per-statement quota generous; only the SERVER limit is tight
        ftk.domain.global_vars["tidb_tpu_server_memory_limit"] = 1 << 18
        n0 = _pressure("server_cancel")
        try:
            e = ftk.exec_err("select count(distinct a), "
                             "count(distinct b), count(distinct s) "
                             "from gm")
        finally:
            ftk.domain.global_vars["tidb_tpu_server_memory_limit"] = 0
        assert e.code == 8175
        assert "server memory limit" in e.msg
        assert _pressure("server_cancel") == n0 + 1
        assert ftk.domain.metrics.get("server_memory_cancel", 0) == 1
        assert ftk.domain.mem_root.consumed == 0
        # shed ONE query, never wedge or die: the session works on
        assert ftk.must_query("select count(*) from gm").rows[0][0] \
            == 40000

    def test_server_limit_sheds_dml(self, ftk):
        """Review-round regression: DML statements register in
        _live_execs now, so the controller can shed a giant
        INSERT..SELECT — and the statement savepoint keeps it
        atomic."""
        ftk.must_exec("create table dsrc (a bigint, b bigint)")
        rows = ",".join(f"({i}, {i * 3})" for i in range(40000))
        ftk.must_exec(f"insert into dsrc values {rows}")
        ftk.must_exec("create table dtgt (a bigint)")
        ftk.domain.global_vars["tidb_tpu_server_memory_limit"] = 1 << 18
        try:
            e = ftk.exec_err("insert into dtgt select a from dsrc "
                             "order by a, b")
        finally:
            ftk.domain.global_vars["tidb_tpu_server_memory_limit"] = 0
        assert e.code == 8175 and "server memory limit" in e.msg, e
        assert ftk.must_query("select count(*) from dtgt").rows[0][0] == 0
        assert ftk.domain.mem_root.consumed == 0

    def test_victim_is_largest_of_two(self, ftk):
        """Two live statements: the controller must pick the larger
        consumer, not the first registered."""
        from tidb_tpu.executor.exec_base import ExecContext
        dom = ftk.domain
        s2 = ftk.new_session()
        e1 = ExecContext(ftk.sess)
        e2 = ExecContext(s2.sess)
        dom.register_exec(ftk.sess.conn_id, e1)
        dom.register_exec(s2.sess.conn_id, e2)
        try:
            e1.mem_tracker.consume(100)
            e2.mem_tracker.consume(50)
            dom.global_vars["tidb_tpu_server_memory_limit"] = 1
            dom.mem_controller.on_breach(dom.mem_root)
            assert e1.mem_killed and e1.killed
            assert not e2.killed
            with pytest.raises(MemoryQuotaExceededError):
                e1.check_killed()
        finally:
            dom.global_vars["tidb_tpu_server_memory_limit"] = 0
            dom.unregister_exec(ftk.sess.conn_id, e1)
            dom.unregister_exec(s2.sess.conn_id, e2)
            e1.finish()
            e2.finish()


class TestHBMPressure:
    def test_resource_exhausted_evicts_then_retries(self, ftk):
        """The pressure protocol: an HBM OOM dispatch sheds cold
        resident entries, the retry runs against the freed headroom,
        and the rows come back correct."""
        from tidb_tpu.utils import failpoint
        ftk.must_exec("create table hp (a bigint, b bigint)")
        rows = ",".join(f"({i % 997}, {i})" for i in range(20000))
        ftk.must_exec(f"insert into hp values {rows}")
        ftk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
        # warm the resident pool so there is something to shed
        expect = ftk.must_query("select sum(b) from hp where a < 500").rows
        store = ftk.domain.copr._dev_store
        assert store.bytes > 0
        ev0 = _pressure("evict") + _pressure("evict_noop")
        ok0 = _pressure("retry_ok")
        # the statement may route fused or conventional copr: inject
        # HBM exhaustion at both agg dispatch seams, first hit only
        failpoint.enable("device_guard/copr/agg",
                         "nth:1->error:resource_exhausted")
        failpoint.enable("device_guard/fused",
                         "nth:1->error:resource_exhausted")
        try:
            got = ftk.must_query(
                "select sum(b) from hp where a < 500").rows
        finally:
            failpoint.disable("device_guard/copr/agg")
            failpoint.disable("device_guard/fused")
        assert got == expect
        assert _pressure("evict") + _pressure("evict_noop") > ev0
        assert _pressure("retry_ok") > ok0
        # the shed was real: entries were dropped with cause=pressure
        assert metrics_util.DEV_BUFFER_EVICTIONS.labels(
            "pressure").value > 0

    def test_evict_bytes_accounting_exact(self, ftk):
        ftk.must_exec("create table he (a bigint)")
        ftk.must_exec("insert into he values " +
                      ",".join(f"({i})" for i in range(5000)))
        ftk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
        ftk.must_query("select sum(a) from he")
        store = ftk.domain.copr._dev_store
        before = store.bytes
        assert before > 0
        freed = store.evict_bytes(before)
        assert freed == before
        st = store.stats()
        assert st["bytes"] == 0 and st["entries"] == 0
        assert all(v == 0 for v in st["bytes_by_spec"].values())


class TestMemStormFastSlice:
    """Tier-1 stand-in for scripts/mem_smoke.py: a small concurrent
    quota storm with injected HBM exhaustion — every statement
    completes host-identical or dies with ER 8175, nothing wedges, and
    the accounting balances to zero at quiesce."""

    def test_fast_storm(self, ftk):
        from tidb_tpu.utils import failpoint
        ftk.must_exec("create table ms (a bigint, b bigint, "
                      "s varchar(24))")
        rows = ",".join(f"({(i * 31) % 1009}, {i}, 'v{i % 53}')"
                        for i in range(30000))
        ftk.must_exec(f"insert into ms values {rows}")
        queries = [
            "select sum(b), count(*) from ms where a < 600",
            "select a, sum(b) from ms group by a order by a limit 10",
            "select a, b from ms order by a, b limit 20",
            "select count(distinct a) from ms",
        ]
        expect = {}
        for q in queries:
            expect[q] = ftk.must_query(q).rows
        for s in ("copr/agg", "copr/filter", "copr/topn", "fused",
                  "sort"):
            failpoint.enable("device_guard/" + s,
                             "prob:0.5->error:resource_exhausted")
        errors = []
        wedged = []

        def worker():
            s = ftk.new_session()
            s.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
            s.must_exec("set @@tidb_mem_quota_query = 4194304")
            for _ in range(3):
                for q in queries:
                    try:
                        got = s.must_query(q).rows
                        if got != expect[q]:
                            errors.append(f"rows mismatch for {q}")
                    except Exception as e:       # noqa: BLE001
                        if getattr(e, "code", None) != 8175:
                            errors.append(
                                f"{q}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=worker) for _ in range(4)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                if t.is_alive():
                    wedged.append(t)
        finally:
            for s in ("copr/agg", "copr/filter", "copr/topn", "fused",
                      "sort"):
                failpoint.disable("device_guard/" + s)
        assert not wedged, f"{len(wedged)} wedged sessions"
        assert not errors, errors[:5]
        # quiesce: tracker and resident-store accounting balance
        assert ftk.domain.mem_root.consumed == 0
        store = ftk.domain.copr._dev_store
        st = store.stats()
        assert st["bytes"] == sum(st["bytes_by_spec"].values())
        assert st["bytes"] == store.evict_bytes(max(st["bytes"], 1)) \
            if st["bytes"] else True
