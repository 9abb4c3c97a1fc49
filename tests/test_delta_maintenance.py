"""Incremental HTAP delta maintenance (copr/delta.py + the residency
append seam) and resolved-ts analytic reads: a steady OLTP write stream
against a resident table must cost O(delta) upload bytes — scatter/
append-patched buffers, version-advanced in place — and resolved-mode
analytic statements must read a consistent committed-data snapshot at
the resolved floor, never the dirty session view."""
import numpy as np
import pytest

import jax

from tidb_tpu.chunk.device import shape_bucket
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import metrics as mu
from tidb_tpu.utils import phase


def _mk(n=2100, name="t"):
    tk = TestKit()
    tk.must_exec("set @@tidb_slow_log_threshold = 100000")
    tk.must_exec(f"create table {name} (id int primary key, k int, "
                 "v int, s varchar(16))")
    tk.must_exec(f"insert into {name} values " + ",".join(
        f"({i},{i % 7},{i * 3},'s{i % 11}')" for i in range(n)))
    return tk


Q = "select k, count(*), sum(v), min(v) from t group by k order by k"


def _expected(rows_kv):
    exp = {}
    for k, v in rows_kv:
        c, s, m = exp.get(k, (0, 0, None))
        exp[k] = (c + 1, s + v, v if m is None else min(m, v))
    return {k: (c, s, m) for k, (c, s, m) in exp.items()}


def _got(rows):
    return {r[0]: (r[1], int(r[2]), int(r[3])) for r in rows}


def _outcome(name):
    return mu.DELTA_APPLY.labels(name).value


class TestAppendFold:
    def test_append_patches_not_reuploads(self):
        """In-bucket appends tail-patch resident buffers: rows stay
        host-identical to a full re-upload and the buffer pool serves
        hits, not misses."""
        tk = _mk()
        rows_kv = [(i % 7, i * 3) for i in range(2100)]
        assert _got(tk.must_query(Q).rows) == _expected(rows_kv)
        miss0 = mu.DEV_BUFFER_POOL.labels("miss").value
        applied0 = _outcome("applied")
        total = 2100
        for step in range(4):
            base = 2100 + step * 8
            tk.must_exec("insert into t values " + ",".join(
                f"({i},{i % 7},{i * 3},'s{i % 11}')"
                for i in range(base, base + 8)))
            rows_kv += [(i % 7, i * 3) for i in range(base, base + 8)]
            total += 8
            phase.reset()
            assert _got(tk.must_query(Q).rows) == _expected(rows_kv)
            ph = phase.snap()
            assert ph.get("delta_applies", 0) > 0
            # delta bytes are the REAL appended rows, tiny vs table
            assert ph.get("delta_bytes", 0) <= 8 * 8 * 4
        assert _outcome("applied") > applied0
        # zero full re-uploads after warmup: every column's bind was a
        # pool hit; a new version's visibility mask is the one entry
        # each of the four writes makes anew (one byte a lane, a pool
        # miss since the mask is a store entry)
        assert mu.DEV_BUFFER_POOL.labels("miss").value == miss0 + 4
        assert mu.DELTA_APPLY_BYTES.labels().value > 0
        assert mu.DELTA_REUPLOAD_AVOIDED_BYTES.labels().value > 0

    def test_delta_bytes_small_vs_table(self):
        """Acceptance: delta_apply_bytes after a write burst is far
        below the table's column bytes (O(delta), not O(table))."""
        tk = _mk(4000)
        tk.must_query(Q)
        b0 = mu.DELTA_APPLY_BYTES.labels().value
        tk.must_exec("insert into t values " + ",".join(
            f"({i},{i % 7},{i * 3},'s{i % 11}')"
            for i in range(4000, 4020)))
        tk.must_query(Q)
        dbytes = mu.DELTA_APPLY_BYTES.labels().value - b0
        table_bytes = 4020 * 8 * 3
        assert 0 < dbytes < table_bytes / 20

    def test_tombstone_folding_advances_without_upload(self):
        """DELETE/UPDATE bump the version but touch no column data:
        the fold advances entries in place (outcome=advanced) and the
        next bind re-uploads nothing."""
        tk = _mk()
        rows_kv = [(i % 7, i * 3) for i in range(2100)]
        assert _got(tk.must_query(Q).rows) == _expected(rows_kv)
        adv0 = _outcome("advanced")
        miss0 = mu.DEV_BUFFER_POOL.labels("miss").value
        tk.must_exec("delete from t where id < 14")
        phase.reset()
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv[14:])
        assert _outcome("advanced") > adv0
        ph = phase.snap()
        # the one upload is the new version's visibility mask (the
        # delete's marks): a bucket's lanes of one byte, no column
        assert ph.get("uploads", 0) == 1
        assert ph.get("upload_bytes", 0) == shape_bucket(2100)
        assert mu.DEV_BUFFER_POOL.labels("miss").value == miss0 + 1
        # an UPDATE appends a new version row: patch, not re-upload
        tk.must_exec("update t set v = v + 1000000 where id = 20")
        phase.reset()
        got = _got(tk.must_query(Q).rows)
        exp = _expected(rows_kv[14:20] + [(20 % 7, 20 * 3 + 1000000)] +
                        rows_kv[21:])
        assert got == exp
        assert mu.DEV_BUFFER_POOL.labels("miss").value == miss0 + 2

    def test_bucket_crossing_falls_back_to_full_upload(self):
        """Growth past the padding bucket cannot patch: the entry is
        superseded (compacted/fell_back) and re-uploaded whole at the
        new capacity — correctness first."""
        tk = _mk(2040)                      # bucket 2048
        rows_kv = [(i % 7, i * 3) for i in range(2040)]
        tk.must_query(Q)
        tk.must_exec("insert into t values " + ",".join(
            f"({i},{i % 7},{i * 3},'s{i % 11}')"
            for i in range(2040, 2080)))     # crosses 2048
        rows_kv += [(i % 7, i * 3) for i in range(2040, 2080)]
        c0 = _outcome("compacted") + _outcome("fell_back_full_upload")
        assert _got(tk.must_query(Q).rows) == _expected(rows_kv)
        assert _outcome("compacted") + \
            _outcome("fell_back_full_upload") > c0

    def test_delta_overflow_sysvar_falls_back(self):
        """A delta larger than tidb_tpu_delta_max_rows drops the
        entry for a full re-upload (outcome=fell_back_full_upload)."""
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_delta_max_rows = 4")
        f0 = _outcome("fell_back_full_upload")
        tk.must_exec("insert into t values " + ",".join(
            f"({i},{i % 7},{i * 3},'s{i % 11}')"
            for i in range(2100, 2140)))
        rows_kv = [(i % 7, i * 3) for i in range(2140)]
        assert _got(tk.must_query(Q).rows) == _expected(rows_kv)
        assert _outcome("fell_back_full_upload") > f0

    def test_gc_compaction_drops_entries(self):
        """gc() rewrites positions in place: stale-epoch entries must
        be dropped (never patched or advanced), and rows stay right."""
        tk = _mk()
        tk.must_exec("delete from t where id < 50")
        tk.must_query(Q)
        ctab = tk.domain.columnar.tables[
            tk.domain.infoschema().table_by_name("test", "t").id]
        ctab.gc(safepoint=1 << 60)
        tk.must_exec("insert into t values (9001, 1, 7, 'x')")
        rows_kv = [(i % 7, i * 3) for i in range(50, 2100)] + [(1, 7)]
        assert _got(tk.must_query(Q).rows) == _expected(rows_kv)


class TestInvalidationRace:
    def test_patched_entry_survives_version_sweep(self):
        """The satellite regression: a delta-advanced entry records
        its new version through to the _by_uid index, so the bind-time
        ``invalidate(uid, keep_version)`` sweep KEEPS it. Without the
        write-through the sweep would drop the very buffer the
        maintainer just patched."""
        from tidb_tpu.copr.residency import DeviceResidentStore
        import jax.numpy as jnp
        store = DeviceResidentStore(1 << 20)
        dev = jnp.zeros(64, dtype=jnp.int64)
        store.put_appendable(("tcol", 1, "frag", 2, "d", 0, 0, 64),
                             dev, 64 * 8, uid=1, version=1, rows=10,
                             start=0, span=None, cap=64, epoch=0)
        # a version-keyed DERIVED entry of the same uid (a valid mask)
        store.put(("mask", 1, 1), dev, 64, uid=1, version=1)
        # maintainer patches: version advances in place
        dev2 = jnp.ones(64, dtype=jnp.int64)
        assert store.apply_delta(("tcol", 1, "frag", 2, "d", 0, 0, 64),
                                 dev2, 20, 2, expect_rows=10)
        dropped = store.invalidate(1, keep_version=2)
        # the derived entry (version 1) dies, the patched one lives
        assert dropped == 1
        ent = store.get_appendable(("tcol", 1, "frag", 2, "d", 0, 0,
                                    64))
        assert ent is not None and ent[1] == 20 and ent[2] == 2
        # and a LATER version sweep reclaims it
        assert store.invalidate(1, keep_version=3) == 1
        assert store.get_appendable(("tcol", 1, "frag", 2, "d", 0, 0,
                                     64)) is None

    def test_apply_delta_cas_on_rows(self):
        """Two concurrent folds race: the second apply_delta with a
        stale expect_rows must lose without clobbering the winner."""
        from tidb_tpu.copr.residency import DeviceResidentStore
        import jax.numpy as jnp
        store = DeviceResidentStore(1 << 20)
        key = ("tcol", 9, "frag", 1, "d", 0, 0, 64)
        store.put_appendable(key, jnp.zeros(64, dtype=jnp.int64),
                             64 * 8, uid=9, version=1, rows=10,
                             start=0, span=None, cap=64, epoch=0)
        a = jnp.full(64, 7, dtype=jnp.int64)
        b = jnp.full(64, 9, dtype=jnp.int64)
        assert store.apply_delta(key, a, 20, 2, expect_rows=10)
        assert not store.apply_delta(key, b, 15, 2, expect_rows=10)
        dev, rows, ver = store.get_appendable(key)
        assert rows == 20 and int(np.asarray(dev)[0]) == 7

    def test_put_appendable_loser_records_no_meta(self):
        """When two binds race the insert, the loser must not record
        its rows against the winner's buffer (overclaimed coverage
        would serve short reads)."""
        from tidb_tpu.copr.residency import DeviceResidentStore
        import jax.numpy as jnp
        store = DeviceResidentStore(1 << 20)
        key = ("tcol", 3, "frag", 1, "d", 0, 0, 64)
        store.put_appendable(key, jnp.zeros(64, dtype=jnp.int64),
                             64 * 8, uid=3, version=1, rows=10,
                             start=0, span=None, cap=64, epoch=0)
        store.put_appendable(key, jnp.ones(64, dtype=jnp.int64),
                             64 * 8, uid=3, version=1, rows=50,
                             start=0, span=None, cap=64, epoch=0)
        dev, rows, _ver = store.get_appendable(key)
        assert rows == 10 and int(np.asarray(dev)[0]) == 0


needs_mesh = pytest.mark.skipif(len(jax.devices()) < 2,
                                reason="needs multi-device mesh")


class TestMeshPlacements:
    @needs_mesh
    def test_sharded_entries_patch_on_mesh(self):
        """The MPP dense path's sharded fact buffers tail-patch under
        appends: rows identical, placement preserved, delta applied."""
        tk = _mk(3000)
        tk.must_exec("set @@tidb_mpp_min_rows = 0")
        tk.must_exec("set @@tidb_enable_mpp = on")
        q = "select k, count(*), sum(v) from t group by k order by k"
        r0 = tk.must_query(q).rows
        assert tk.domain.metrics.get("copr_mpp_exec", 0) > 0
        applied0 = _outcome("applied")
        tk.must_exec("insert into t values " + ",".join(
            f"({i},{i % 7},{i * 3},'s{i % 11}')"
            for i in range(3000, 3012)))
        r1 = tk.must_query(q).rows
        # host-identical vs the single-chip (freshly uploaded) path
        tk.must_exec("set @@tidb_enable_mpp = off")
        tk.domain.plan_cache.clear()
        assert _got3(r1) == _got3(tk.must_query(q).rows)
        assert _outcome("applied") > applied0
        stats = tk.domain.copr._dev_store.stats()
        assert stats["bytes_by_spec"]["sharded"] > 0

    @needs_mesh
    def test_replicated_entry_patches(self):
        """A replicated (broadcast dim) appendable entry patches on
        every device and keeps its replicated placement."""
        from tidb_tpu.copr.delta import append_key
        tk = _mk(1200)
        copr = tk.domain.copr
        mesh = copr._get_mesh()
        assert mesh is not None
        info = tk.domain.infoschema().table_by_name("test", "t")
        ctab = tk.domain.columnar.tables[info.id]
        cid = info.find_column("v").id
        cap = 2048
        key = append_key(ctab.uid, ("dim",), cid, "d", ctab.gc_epoch,
                         (), cap)
        dev = copr._dev_put_append(
            key, ctab.data[cid][:ctab.n], ctab.n, cap, ctab.uid,
            ctab.version, ctab.gc_epoch, 0, None, mesh=mesh,
            spec="replicated")
        assert copr._dev_store.spec_of(key) == "replicated"
        tk.must_exec("insert into t values (8000, 3, 424242, 'z')")
        copr.delta.refresh(ctab)
        ent = copr._dev_store.get_appendable(key)
        assert ent is not None
        dev2, rows, ver = ent
        assert rows == ctab.n and ver == ctab.version
        host = np.asarray(dev2)
        assert host[ctab.n - 1] == 424242
        assert copr._dev_store.spec_of(key) == "replicated"
        assert len(dev2.sharding.device_set) == mesh.devices.size


def _got3(rows):
    return {r[0]: (r[1], int(r[2])) for r in rows}


class TestResolvedReads:
    def test_never_observes_uncommitted_or_above_watermark(self):
        """A resolved-mode analytic read sees neither an uncommitted
        row (another session's open txn) nor a row committed ABOVE the
        resolved floor held down by an older open transaction."""
        tk = _mk()
        rows_kv = [(i % 7, i * 3) for i in range(2100)]
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        # an open txn holds the floor at its start_ts via FOR UPDATE
        holder = tk.new_session()
        holder.must_exec("begin")
        holder.must_exec("select * from t where id = 1 for update")
        # another session COMMITS a row — its commit_ts > floor
        writer = tk.new_session()
        writer.must_exec("insert into t values (7001, 1, 999, 'w')")
        # and yet another has an UNCOMMITTED buffered row
        dirty = tk.new_session()
        dirty.must_exec("begin")
        dirty.must_exec("insert into t values (7002, 1, 888, 'u')")
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv)      # neither row visible
        holder.must_exec("rollback")
        dirty.must_exec("rollback")
        # floor released: the committed row appears
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv + [(1, 999)])

    def test_resolved_skips_dirty_overlay_leader_keeps_it(self):
        """mode=resolved retires the dirty-overlay rescan for the
        session's own analytic reads; mode=leader (default) keeps
        read-your-own-writes."""
        tk = _mk()
        tk.must_query(Q)
        rows_kv = [(i % 7, i * 3) for i in range(2100)]
        # leader: in-txn analytic sees the buffered write
        tk.must_exec("begin")
        tk.must_exec("insert into t values (7010, 2, 123, 'x')")
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv + [(2, 123)])
        tk.must_exec("rollback")
        # resolved: the same shape reads committed data only
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        r0 = mu.ANALYTIC_READS.labels("resolved").value
        tk.must_exec("begin")
        tk.must_exec("insert into t values (7011, 2, 123, 'x')")
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv)
        tk.must_exec("rollback")
        assert mu.ANALYTIC_READS.labels("resolved").value > r0

    def test_resolved_contract_covers_point_and_index_plans(self):
        """The committed-data contract must hold on EVERY plan shape:
        an olap-classified statement planned through batch-point-get
        or an index range must exclude the session's uncommitted
        writes exactly like the full-scan path."""
        tk = _mk()
        tk.must_exec("create index ik on t (k)")
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        tk.must_exec("begin")
        tk.must_exec("insert into t values (9999, 600, 111, 'pp')")
        # batch-point-get under an aggregate (IN over the PK)
        s = tk.must_query(
            "select sum(v) from t where id in (1, 2, 9999)").rows
        assert int(s[0][0]) == 1 * 3 + 2 * 3
        # index-range scan under an aggregate (k = 600 only exists in
        # the dirty buffer)
        s = tk.must_query(
            "select count(*), sum(v) from t where k > 99").rows
        assert (s[0][0], s[0][1]) == (0, None)
        tk.must_exec("rollback")

    def test_explicit_txn_stays_repeatable_read(self):
        """Inside an explicit transaction the resolved floor is
        clamped to the txn's start_ts: a commit from another session
        mid-txn must NOT appear between two analytic statements of the
        same transaction (the view may be stale, never fresher than
        the txn snapshot)."""
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        rows_kv = [(i % 7, i * 3) for i in range(2100)]
        tk.must_exec("begin")
        first = _got(tk.must_query(Q).rows)
        writer = tk.new_session()
        writer.must_exec("insert into t values (7100, 3, 77, 'rr')")
        second = _got(tk.must_query(Q).rows)
        assert second == first == _expected(rows_kv)
        tk.must_exec("commit")
        got = _got(tk.must_query(Q).rows)
        assert got == _expected(rows_kv + [(3, 77)])

    def test_resolved_does_not_block_on_locks(self):
        """An analytic read at the resolved floor never waits on OLTP
        write locks (the decoupling contract)."""
        import time
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        holder = tk.new_session()
        holder.must_exec("begin")
        holder.must_exec("select * from t where id = 3 for update")
        t0 = time.time()
        tk.must_query(Q)
        assert time.time() - t0 < 1.0
        holder.must_exec("rollback")

    def test_staleness_bound_falls_back_to_leader(self):
        """A floor older than the staleness bound keeps the statement
        on the strict leader path (and counts the fallback)."""
        import time
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        tk.must_exec("set @@tidb_tpu_analytic_max_staleness_ms = 50")
        holder = tk.new_session()
        holder.must_exec("begin")
        holder.must_exec("select * from t where id = 3 for update")
        time.sleep(0.12)
        f0 = mu.ANALYTIC_READS.labels("staleness_fallback").value
        tk.must_query(Q)
        assert mu.ANALYTIC_READS.labels("staleness_fallback").value > f0
        holder.must_exec("rollback")

    def test_for_update_stays_strict(self):
        """FOR UPDATE analytics never route to the resolved view."""
        tk = _mk()
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        s0 = mu.ANALYTIC_READS.labels("strict").value
        tk.must_exec("begin")
        tk.must_query("select k, v from t where k > 100 for update")
        tk.must_exec("rollback")
        assert mu.ANALYTIC_READS.labels("strict").value >= s0

    def test_resolved_matches_leader_at_quiesce(self):
        """With no open transactions the resolved floor is current:
        both modes return identical rows (the htap_smoke equivalence
        gate, tier-1 sized)."""
        tk = _mk()
        tk.must_exec("insert into t values (7020, 5, 55, 'q')")
        leader = tk.must_query(Q).rows
        tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
        assert tk.must_query(Q).rows == leader


class TestFreshnessSurface:
    def test_replica_freshness_rows_and_gauge(self):
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("insert into t values (7030, 0, 1, 'f')")
        rows = tk.must_query(
            "select table_schema, table_name, resolved_ts, lag_ms, "
            "pending_delta_rows, mode from information_schema"
            ".tidb_replica_freshness where table_name = 't'").rows
        assert len(rows) == 1
        sch, name, resolved, lag, pend, mode = rows[0]
        assert (sch, name) == ("test", "t")
        assert resolved > 0 and pend >= 1
        assert mode in ("leader", "resolved")
        # vtable read refreshes the lag gauge
        assert mu.REPLICA_LAG_SECONDS.labels().value >= 0

    def test_top_sql_attributes_delta_cost(self):
        tk = _mk()
        tk.must_query(Q)
        tk.must_exec("insert into t values (7040, 0, 1, 'g')")
        tk.must_query(Q)
        rows = tk.must_query(
            "select delta_applies, delta_bytes from information_schema"
            ".tidb_top_sql where delta_applies > 0").rows
        assert rows and all(r[1] > 0 for r in rows)
