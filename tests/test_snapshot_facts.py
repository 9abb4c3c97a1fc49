"""A table version's snapshot facts (storage/columnar.py `_facts_at`:
the read-latest visibility mask, the newest timestamp it holds, each
column's has-a-NULL) and the mask's resident copy a row block
(copr/dag_exec.py `_mask_operand`): computed once a version by its first
reader, served to every snapshot at or past the version's newest
timestamp, and never across a version bump, a GC compaction, or to an
older snapshot. The benchmark's cells only read, so these tests hold the
write path: snapshot isolation, and an acknowledged write visible to the
next read, on the fused, per-DAG and host routes. One parametrised test
a guarantee."""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from tidb_tpu.chunk.device import shape_bucket
from tidb_tpu.parallel import make_mesh
from tidb_tpu.storage.columnar import ColumnarTable
from tidb_tpu.testkit import TestKit
from tidb_tpu.types.time_types import micros_to_str
from tidb_tpu.utils import metrics as mu
from tidb_tpu.utils import phase

facts = mu.snapshot_facts           # a reading; what grew since one

NDIM, NFACT = 10, 300
# the fused route (fact f, dimension d), the per-DAG route, and the
# same two with the host twin in the device's place
Q_FUSED = ("select d.grp, count(*), sum(f.v) from f, d "
           "where f.d_id = d.id group by d.grp order by d.grp")
Q_DAG = "select d_id, count(*), sum(v) from f group by d_id order by d_id"
ROUTES = {"fused": (Q_FUSED, True), "dag": (Q_DAG, True),
          "host": (Q_DAG, False), "host_join": (Q_FUSED, False)}


def _mk(mesh=None):
    tk = TestKit()
    tk.must_exec("set @@tidb_slow_log_threshold = 100000")
    tk.must_exec("create table d (id int primary key, grp int)")
    tk.must_exec("create table f (k int primary key, d_id int, v int)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i},{i % 3})" for i in range(1, NDIM + 1)))
    tk.must_exec("insert into f values " + ",".join(
        f"({i},{i % NDIM + 1},{i})" for i in range(1, NFACT + 1)))
    # one chip unless a mesh is described (the suite forces 8 host
    # devices: a process that sees them would make a mesh of all)
    tk.domain.copr._mesh = mesh if mesh is not None else False
    if mesh is not None:
        tk.must_exec("set global tidb_mpp_min_rows = 0")
    return tk


def _rows(tk):
    """{k: (d_id, v)} of f as the row store holds it: the oracle."""
    return {int(k): (int(d), int(v)) for k, d, v in
            tk.must_query("select k, d_id, v from f use index ()").rows}


def _want(sql, f_rows):
    """The statement's answer from the oracle's rows, in Python."""
    out = {}
    for d_id, v in f_rows.values():
        g = d_id % 3 if sql is Q_FUSED else d_id
        c, s = out.get(g, (0, 0))
        out[g] = (c + 1, s + v)
    return [(g, c, s) for g, (c, s) in sorted(out.items())]


def _got(tk, sql):
    return [(int(g), int(c), int(s)) for g, c, s in tk.must_query(sql).rows]


def _ftab(tk, name="f"):
    return tk.domain.columnar.table(
        tk.domain.infoschema().table_by_name("test", name))


# ---- an acknowledged write is seen by the next autocommit read ---------

WRITES = {
    "insert": ("insert into f values (1001, 4, 77)",
               lambda r: r.update({1001: (4, 77)})),
    "update": ("update f set v = v + 1000 where k = 7",
               lambda r: r.update({7: (r[7][0], r[7][1] + 1000)})),
    "delete": ("delete from f where k in (3, 13)",
               lambda r: [r.pop(k) for k in (3, 13)]),
}


@pytest.mark.parametrize("op", sorted(WRITES))
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_committed_write_is_seen_by_the_next_read(route, op):
    sql, device = ROUTES[route]
    tk = _mk()
    tk.domain.copr.use_device = device
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    for _ in range(2):              # the version's facts built, then hit
        assert _got(tk, sql) == _want(sql, rows)
    before = facts()
    assert _got(tk, sql) == _want(sql, rows)
    assert set(facts(before)) == {"hit"}
    stmt, apply = WRITES[op]
    writer = tk.new_session()       # another connection's commit
    writer.must_exec(stmt)
    apply(rows)
    before = facts()
    assert _got(tk, sql) == _want(sql, rows)
    grown = facts(before)
    # f's new version is built by this read, once; d's facts still hit
    assert grown.get("build") == 1, grown
    assert not [o for o in grown if o.startswith("bypass")], grown
    before = facts()
    assert _got(tk, sql) == _want(sql, rows)
    assert set(facts(before)) == {"hit"}
    assert _rows(tk) == rows


# ---- a snapshot older than the table's newest timestamp bypasses -------

@pytest.mark.parametrize("route", ["fused", "dag", "host"])
@pytest.mark.parametrize("kind", ["txn", "as_of", "staleness"])
def test_older_snapshot_does_not_see_a_later_commit(kind, route):
    sql, device = ROUTES[route]
    tk = _mk()
    tk.domain.copr.use_device = device
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    base = _want(sql, rows)
    assert _got(tk, sql) == base
    # tidb_read_staleness counts whole seconds: the load has to be
    # more than one behind the commit
    time.sleep(1.3 if kind == "staleness" else 0.06)
    mid = micros_to_str(int(time.time() * 1e6), 6)
    if kind == "txn":
        tk.must_exec("begin")
        assert _got(tk, sql) == base        # start_ts is taken
    time.sleep(0.06)
    writer = tk.new_session()
    writer.must_exec("insert into f values (1001, 4, 77)")
    writer.must_exec("delete from f where k = 5")
    rows[1001] = (4, 77)
    rows.pop(5)
    assert _got(writer, sql) == _want(sql, rows)    # the newest: built
    old = sql
    if kind == "as_of":
        old = sql.replace("from f", f"from f as of timestamp '{mid}'") \
            .replace(", d ", f", d as of timestamp '{mid}' ")
    elif kind == "staleness":
        tk.must_exec("set tidb_read_staleness = -1")
    before = facts()
    got = _got(tk, old)
    grown = facts(before)
    tk.must_exec("set tidb_read_staleness = 0")
    assert got == base, (got, base)
    assert grown.get("bypass_read_ts", 0) >= 1, grown
    assert "build" not in grown, grown
    if kind == "txn":
        tk.must_exec("commit")
    # read-latest again: the kept facts, the newest rows
    before = facts()
    assert _got(tk, sql) == _want(sql, rows)
    assert set(facts(before)) == {"hit"}


def test_a_transaction_begun_after_the_last_commit_takes_the_facts():
    tk = _mk()
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    assert _got(tk, Q_FUSED) == _want(Q_FUSED, rows)
    tk.must_exec("begin")
    before = facts()
    assert _got(tk, Q_FUSED) == _want(Q_FUSED, rows)
    assert set(facts(before)) == {"hit"}
    # its own uncommitted row is an overlay: f's mask is the
    # statement's, uploaded raw and counted
    tk.must_exec("update f set v = 0 where k = 9")
    rows[9] = (rows[9][0], 0)
    phase.reset()
    assert _got(tk, Q_FUSED) == _want(Q_FUSED, rows)
    assert phase.snap().get("upload_bytes", 0) >= shape_bucket(NFACT)
    tk.must_exec("rollback")


# ---- what drops the facts ----------------------------------------------

@pytest.mark.parametrize("route", ["fused", "dag", "host"])
@pytest.mark.parametrize("how", ["gc", "truncate", "add_column"])
def test_facts_do_not_outlive_their_version(how, route):
    sql, device = ROUTES[route]
    tk = _mk()
    tk.domain.copr.use_device = device
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    assert _got(tk, sql) == _want(sql, rows)
    ctab = _ftab(tk)
    kept = ctab._facts
    assert kept is not None and kept.n == NFACT
    if how == "gc":
        tk.must_exec("delete from f where k <= 40")
        for k in range(1, 41):
            rows.pop(k)
        assert _got(tk, sql) == _want(sql, rows)
        epoch = ctab.gc_epoch
        assert tk.domain.run_gc() >= 40
        assert ctab.gc_epoch == epoch + 1 and ctab.n == NFACT - 40
    elif how == "truncate":
        tk.must_exec("truncate table f")
        rows.clear()
        assert _got(tk, sql) == []
        tk.must_exec("insert into f values (1, 2, 3), (2, 3, 4)")
        rows.update({1: (2, 3), 2: (3, 4)})
        ctab = _ftab(tk)
        assert ctab._facts is None or ctab._facts is not kept
    else:
        tk.must_exec("alter table f add column w int")
    before = facts()
    assert _got(tk, sql) == _want(sql, rows)
    grown = facts(before)
    # (a schema change may bump the version once more as the new
    # TableInfo reaches the engine: a build each)
    assert grown.get("build") == 1 or how == "add_column" and \
        grown.get("build") == 2, grown
    now = _ftab(tk)._facts
    assert now is not kept and now.version == _ftab(tk).version
    assert now.n == len(now.valid) == _ftab(tk).n
    assert int(now.valid.sum()) == len(rows)


# ---- the table's own contract ------------------------------------------

def _ctab(n=64):
    from tidb_tpu.chunk.column import py_to_datum_fast
    tk = TestKit()
    tk.must_exec("create table u (id int primary key, a int)")
    info = tk.domain.infoschema().table_by_name("test", "u")
    ctab = ColumnarTable(info)
    fts = [c.ft for c in info.columns]
    for i in range(1, n + 1):
        ctab.put_row(i, [py_to_datum_fast(i, fts[0]),
                         None if i % 8 == 0 else
                         py_to_datum_fast(i * 2, fts[1])], commit_ts=10 + i)
    return ctab, fts, [c.id for c in info.columns]


def _brute(ctab, read_ts, n):
    ins, dele = ctab.insert_ts[:n], ctab.delete_ts[:n]
    if read_ts is None:
        return dele == 0
    return (ins <= read_ts) & ((dele == 0) | (dele > read_ts))


CONTRACT = {
    # name: (read_ts as an offset from the newest timestamp or None,
    #        rows asked for as an offset from n, the outcome)
    "latest": (None, 0, "hit"),
    "at_newest": (0, 0, "hit"),
    "past_newest": (1000, 0, "hit"),
    "older": (-1, 0, "bypass_read_ts"),
    "much_older": (-40, 0, "bypass_read_ts"),
    "fewer_rows": (None, -3, "bypass_overlay"),
    "older_and_fewer_rows": (-5, -3, "bypass_read_ts"),
}


@pytest.mark.parametrize("case", sorted(CONTRACT))
def test_what_answers_a_snapshot(case):
    off, dn, outcome = CONTRACT[case]
    ctab, fts, cids = _ctab()
    ctab.delete_row(5, commit_ts=200)
    ctab.delete_row(6, commit_ts=201)
    newest = 201
    before = facts()
    first = ctab.valid_at()
    assert facts(before) == {"build": 1}
    assert not first.flags.writeable
    assert ctab._facts.newest_ts == newest == ctab.max_commit_ts
    assert ctab.version_mask(first) == ctab.version
    read_ts = None if off is None else newest + off
    n = ctab.n + dn
    before = facts()
    got = ctab.valid_at(read_ts, n)
    assert facts(before) == {outcome: 1}
    assert np.array_equal(got, _brute(ctab, read_ts, n))
    assert (got is first) == (outcome == "hit")
    if outcome != "hit":
        assert ctab.version_mask(got) is None
    # snapshot: the same mask, and a column's nulls only where it has any
    before = facts()
    arrays, valid = ctab.snapshot(cids, read_ts)
    assert np.array_equal(valid, _brute(ctab, read_ts, ctab.n))
    assert arrays[cids[0]][1] is None
    assert arrays[cids[1]][1] is not None and arrays[cids[1]][1].sum() == 8
    if outcome == "hit":
        assert valid is first and ctab._facts.any_null == {
            cids[0]: False, cids[1]: True}
    # a write makes the next reader build; the held mask is untouched
    held = first.copy()
    ctab.delete_row(7, commit_ts=300)
    before = facts()
    second = ctab.valid_at()
    assert facts(before) == {"build": 1}
    assert second is not first and not second[6] and first[6]
    assert np.array_equal(first, held)
    assert ctab.version_mask(first) is None
    assert ctab.version_mask(second) == ctab.version
    with pytest.raises(ValueError):
        second[0] = False


def test_facts_are_stamped_with_the_version_read_before_the_build():
    """A commit that lands while a reader builds leaves the facts
    claiming the older version: the next reader builds again and never
    takes a mask that may lack the commit."""
    ctab, fts, cids = _ctab()

    real = ctab.delete_ts
    landed = []

    class DeleteTs:
        """delete_ts whose first slice lets a commit land first."""

        def __getitem__(self, key):
            if not landed:
                landed.append(1)
                ctab.delete_ts = real
                ctab.delete_row(3, commit_ts=500)
            return real[key]

        def __setitem__(self, key, val):
            real[key] = val
    ctab.delete_ts = DeleteTs()
    v0 = ctab.version
    got = ctab.valid_at()
    assert landed and ctab.version == v0 + 1
    assert ctab._facts.version == v0            # the older stamp
    assert ctab._facts.newest_ts == 500         # covers what it saw
    assert not got[2]                           # it saw the commit
    before = facts()
    again = ctab.valid_at()
    assert facts(before) == {"build": 1}
    assert again is not got and not again[2]
    assert ctab._facts.version == v0 + 1


# ---- a writer never changes a mask a reader holds ----------------------

def test_writer_thread_never_changes_a_readers_mask():
    tk = _mk()
    ctab = _ftab(tk)
    stop = threading.Event()
    errors, held, kept = [], [], set()
    deadline = time.time() + 20
    v0 = ctab.version

    def write():
        w = tk.new_session()
        k = 5000
        try:
            while not stop.is_set() and time.time() < deadline:
                w.must_exec(f"insert into f values ({k}, 1, 1)")
                w.must_exec(f"delete from f where k = {k - 3}")
                k += 1
        except Exception as e:                  # noqa: BLE001
            errors.append(e)

    def read():
        try:
            while not stop.is_set() and time.time() < deadline:
                version = ctab.version
                arrays, valid = ctab.snapshot(
                    [c.id for c in ctab.table_info.columns])
                stamped = ctab.version_mask(valid)
                # (a reader that met an append half done got a mask of
                # its own, computed for its rows: that one is writable)
                if valid.flags.writeable and stamped is not None:
                    errors.append(AssertionError("writable kept mask"))
                if len(valid) != len(next(iter(arrays.values()))[0]):
                    errors.append(AssertionError("mask and columns"))
                if stamped is not None and stamped < version:
                    errors.append(AssertionError("facts older than read"))
                # (a mask not seen before is held past the cap: `held`
                # may fill before the writer's next commit)
                if len(held) < 400 or id(valid) not in kept:
                    kept.add(id(valid))
                    held.append((valid, valid.copy()))
        except Exception as e:                  # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=write)] + \
        [threading.Thread(target=read) for _ in range(12)]
    try:
        for t in threads:
            t.start()
        # the 1.5 s of before, and on until the writer has committed
        # three versions and the readers hold the masks of two: on a
        # loaded machine that can take longer than any fixed sleep
        soak = time.time() + 1.5
        while time.time() < deadline and not errors and (
                time.time() < soak or ctab.version < v0 + 3
                or len(kept) < 2):
            time.sleep(0.02)
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(old)
        stop.set()
    assert not [t for t in threads if t.is_alive()]
    assert not errors, errors[:3]
    assert len(held) > 10
    assert len({id(v) for v, _ in held}) > 1    # more than one version
    assert [1 for v, _ in held if not v.flags.writeable]
    for valid, copy in held:
        assert np.array_equal(valid, copy)
    n = ctab.n
    assert np.array_equal(ctab.valid_at(), ctab.delete_ts[:n] == 0)
    rows = _rows(tk)
    assert _got(tk, Q_DAG) == _want(Q_DAG, rows)


# ---- a second execution computes and uploads nothing -------------------

def _mesh4():
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for the mesh")
    return make_mesh(4)


@pytest.mark.parametrize("site", ["fused", "dag", "fused_mesh4",
                                  "dag_mesh4"])
def test_second_execution_hits_only_and_uploads_nothing(site):
    route, _, mesh = site.partition("_")
    sql, _device = ROUTES[route]
    tk = _mk(_mesh4() if mesh else None)
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    for _ in range(2):          # the second run may rebuild with what
        _got(tk, sql)           # the first learned
    store = tk.domain.copr._dev_store
    tables = 2 if route == "fused" else 1
    for _ in range(2):
        before = facts()
        miss = mu.DEV_BUFFER_POOL.labels("miss").value
        resident = store.bytes
        routed = dict(tk.domain.metrics)
        phase.reset()
        assert _got(tk, sql) == _want(sql, rows)
        snap = phase.snap()
        assert facts(before) == {"hit": tables}
        assert snap.get("upload_bytes", 0) == 0, snap
        assert snap.get("uploads", 0) == 0, snap
        assert snap.get("kernel_builds", 0) == 0, snap
        assert mu.DEV_BUFFER_POOL.labels("miss").value == miss
        assert store.bytes == resident
        went = {k: v - routed.get(k, 0)
                for k, v in tk.domain.metrics.items()
                if v != routed.get(k, 0)}
        want = {"fused": "fused_pipeline_hit", "dag": "copr_device_exec",
                "fused_mesh4": "fused_pipeline_mpp_hit",
                "dag_mesh4": "copr_mpp_exec"}[site]
        assert went.get(want) == 1, went
    # the mask is a store entry of the version, one a row block (one
    # chip) or one a table (the mesh), charged like the columns
    ctab = _ftab(tk)
    tag = {"fused": "fragv", "dag": "fragv", "fused_mesh4": "mppfv",
           "dag_mesh4": "mppvalid"}[site]
    masks = [k for k in store._entries if tag in k]
    assert len(masks) == 1 and ctab.version in masks[0], masks
    assert store._by_uid[ctab.uid][masks[0]] == ctab.version
    # a commit drops it with the version; the next read puts the new one
    tk.new_session().must_exec("delete from f where k = 11")
    rows.pop(11)
    phase.reset()
    assert _got(tk, sql) == _want(sql, rows)
    masks = [k for k in store._entries if tag in k]
    assert len(masks) == 1 and ctab.version in masks[0], masks
    assert phase.snap().get("upload_bytes", 0) >= shape_bucket(NFACT - 1)


@pytest.mark.parametrize("route", ["fused", "dag"])
def test_a_program_that_donates_its_mask_gets_a_copy(route, monkeypatch):
    """On the chip the one-chip programs donate their mask operand;
    the resident mask is never the buffer they consume."""
    monkeypatch.setenv("TIDB_TPU_DONATE", "1")
    sql, _device = ROUTES[route]
    tk = _mk()
    rows = {k: (k % NDIM + 1, k) for k in range(1, NFACT + 1)}
    for _ in range(3):
        phase.reset()
        assert _got(tk, sql) == _want(sql, rows)
    assert phase.snap().get("upload_bytes", 0) == 0
    store = tk.domain.copr._dev_store
    masks = [k for k in store._entries if "fragv" in k]
    assert len(masks) == 1
    assert not store._entries[masks[0]].is_deleted()
