"""Fused scan->join->agg pipeline (copr/pipeline.py): routing, parity
with the conventional HashJoin subtree, and runtime fallbacks."""
import numpy as np
import pytest

from tidb_tpu.testkit import TestKit
import tidb_tpu.planner.physical as pp


@pytest.fixture()
def tk():
    tk = TestKit()
    tk.must_exec("create table dim_a (id int primary key, grp int, "
                 "name varchar(16), val int)")
    tk.must_exec("create table dim_b (id int primary key, tag varchar(8))")
    tk.must_exec("create table fact (k int primary key, a_id int, "
                 "b_id int, amt decimal(10,2), q int)")
    rng = np.random.RandomState(3)
    rows = []
    for i in range(1, 41):
        rows.append(f"({i}, {i % 7}, 'n{i % 5}', {i * 3})")
    tk.must_exec("insert into dim_a values " + ",".join(rows))
    rows = [f"({i}, 't{i % 3}')" for i in range(1, 21)]
    tk.must_exec("insert into dim_b values " + ",".join(rows))
    rows = []
    for i in range(1, 501):
        a = rng.randint(1, 45)       # some misses -> inner join drops
        b = rng.randint(1, 21)
        rows.append(f"({i}, {a}, {b}, {rng.randint(1, 9999) / 100.0}, "
                    f"{rng.randint(0, 50)})")
    tk.must_exec("insert into fact values " + ",".join(rows))
    return tk


Q = ("select dim_a.grp, sum(fact.amt), count(*), min(fact.q) "
     "from fact, dim_a, dim_b "
     "where fact.a_id = dim_a.id and fact.b_id = dim_b.id "
     "and fact.q < 40 and dim_b.tag <> 't2' "
     "group by dim_a.grp order by dim_a.grp")

Q_POS = ("select fact.a_id, dim_a.name, sum(fact.q) "
         "from fact, dim_a where fact.a_id = dim_a.id "
         "group by fact.a_id, dim_a.name order by fact.a_id")


def _conventional(tk, sql):
    orig = pp._try_fuse_agg
    pp._try_fuse_agg = lambda *a, **k: None
    tk.domain.invalidate_plan_cache()
    try:
        return tk.must_query(sql).rs.rows
    finally:
        pp._try_fuse_agg = orig
        tk.domain.invalidate_plan_cache()


def test_fused_routed_and_matches(tk):
    plan = tk.must_query("explain " + Q).rs.rows
    assert any("FusedPipeline" in r[0] for r in plan), plan
    before = tk.domain.metrics.get("fused_pipeline_hit", 0)
    got = tk.must_query(Q).rs.rows
    assert tk.domain.metrics.get("fused_pipeline_hit", 0) == before + 1
    assert got == _conventional(tk, Q)


def test_fused_position_dense_group_matches(tk):
    """Group by FK + dependent dim column -> position-dense agg path."""
    got = tk.must_query(Q_POS).rs.rows
    assert got == _conventional(tk, Q_POS)
    assert len(got) > 30


def test_fused_dirty_txn_insert_overlay(tk):
    """Insert-only fact delta stays on the fused device path: the
    uncommitted row mounts as one extra device partition."""
    tk.must_exec("begin")
    tk.must_exec("insert into fact values (1001, 1, 1, 5.00, 1)")
    before = tk.domain.metrics.get("fused_pipeline_dirty_overlay", 0)
    got = tk.must_query(Q_POS).rs.rows
    assert tk.domain.metrics.get(
        "fused_pipeline_dirty_overlay", 0) == before + 1
    tk.must_exec("rollback")
    base = tk.must_query(Q_POS).rs.rows
    # the uncommitted row contributed to group a_id=1
    g1_dirty = next(r for r in got if r[0] == 1)
    g1_base = next(r for r in base if r[0] == 1)
    assert int(g1_dirty[2]) == int(g1_base[2]) + 1


def test_fused_dirty_txn_update_overlay(tk):
    """UPDATE of committed fact rows stays fused: the old version is
    validity-masked and the new values ride the delta partition."""
    base = tk.must_query(Q_POS).rs.rows
    # pick a fact row whose a_id actually joins (a_id goes to 44 but
    # dim_a ids stop at 40)
    k = tk.must_query(
        "select min(k) from fact where a_id <= 40").rs.rows[0][0]
    tk.must_exec("begin")
    tk.must_exec(f"update fact set q = q + 10 where k = {k}")
    before = tk.domain.metrics.get("fused_pipeline_dirty_overlay", 0)
    got = tk.must_query(Q_POS).rs.rows
    assert tk.domain.metrics.get(
        "fused_pipeline_dirty_overlay", 0) == before + 1
    assert got == _conventional(tk, Q_POS)
    tk.must_exec("rollback")
    # exactly one group's sum moved by +10
    diffs = [(b[0], int(g[2]) - int(b[2]))
             for g, b in zip(got, base) if int(g[2]) != int(b[2])]
    assert diffs and all(d == 10 for _, d in diffs)
    assert tk.must_query(Q_POS).rs.rows == base


def test_fused_dirty_txn_delete_overlay(tk):
    """DELETE of committed fact rows stays fused via validity mask."""
    tk.must_exec("begin")
    tk.must_exec("delete from fact where q >= 45")
    before = tk.domain.metrics.get("fused_pipeline_dirty_overlay", 0)
    got = tk.must_query(Q_POS).rs.rows
    assert tk.domain.metrics.get(
        "fused_pipeline_dirty_overlay", 0) == before + 1
    assert got == _conventional(tk, Q_POS)
    tk.must_exec("rollback")


def test_fused_dirty_txn_mixed_overlay(tk):
    """Mixed insert+update+delete in one txn, plus insert-then-delete
    of the same handle (a no-op against the committed snapshot)."""
    tk.must_exec("begin")
    tk.must_exec("insert into fact values (1002, 2, 1, 7.00, 3)")
    tk.must_exec("update fact set q = 0 where k in (2, 3)")
    tk.must_exec("delete from fact where k = 4")
    tk.must_exec("insert into fact values (1003, 3, 1, 1.00, 1)")
    tk.must_exec("delete from fact where k = 1003")
    before = tk.domain.metrics.get("fused_pipeline_dirty_overlay", 0)
    got = tk.must_query(Q_POS).rs.rows
    assert tk.domain.metrics.get(
        "fused_pipeline_dirty_overlay", 0) == before + 1
    assert got == _conventional(tk, Q_POS)
    tk.must_exec("rollback")


def test_fused_dirty_insert_out_of_span_group_key(tk):
    """A delta row whose int group key lies OUTSIDE the snapshot's
    min/max span must form its own group, not clip into a boundary
    group (dense layouts derive their span from the snapshot only —
    delta executions must take the exact sort lowering)."""
    tk.must_exec("create table sp (k int primary key, g int, v int)")
    rows = ",".join(f"({i}, {1 + i % 50}, {i})" for i in range(1, 5001))
    tk.must_exec("insert into sp values " + rows)
    sql = "select g, count(*) from sp group by g order by g"
    base = tk.must_query(sql).rs.rows
    assert len(base) == 50
    tk.must_exec("begin")
    tk.must_exec("insert into sp values (9001, 500, 1)")
    got = tk.must_query(sql).rs.rows
    tk.must_exec("rollback")
    assert len(got) == 51
    assert next(r for r in got if r[0] == 500)[1] == 1
    g50 = next(r for r in got if r[0] == 50)
    assert g50[1] == next(r for r in base if r[0] == 50)[1]


def test_fused_dirty_dim_write_falls_back(tk):
    """Writes to a dim table still drop the query to the host path."""
    tk.must_exec("begin")
    tk.must_exec("update dim_a set val = val + 1 where id = 1")
    before = tk.domain.metrics.get("fused_pipeline_fallback", 0)
    got = tk.must_query(Q_POS).rs.rows
    assert tk.domain.metrics.get(
        "fused_pipeline_fallback", 0) == before + 1
    assert got == _conventional(tk, Q_POS)
    tk.must_exec("rollback")


def test_fused_nonunique_dim_falls_back(tk):
    """Join keyed on a NON-unique dim column must not use the fused
    probe (planner prefers unique, but a query can force it)."""
    sql = ("select sum(fact.q) from fact, dim_a "
           "where fact.a_id = dim_a.grp")
    got = tk.must_query(sql).rs.rows
    assert got == _conventional(tk, sql)


def test_fused_empty_dim(tk):
    tk.must_exec("create table dim_empty (id int primary key, x int)")
    sql = ("select count(*), sum(fact.q) from fact, dim_empty "
           "where fact.b_id = dim_empty.id")
    got = tk.must_query(sql).rs.rows
    assert got[0][0] == 0


def test_fused_null_probe_rows_drop(tk):
    """NULL FK values must not match any dim row (inner join)."""
    tk.must_exec("create table f2 (k int primary key, a_id int, v int)")
    tk.must_exec("insert into f2 values (1, 1, 10), (2, null, 20), "
                 "(3, 2, 30), (4, null, 40)")
    sql = ("select sum(f2.v) from f2, dim_a where f2.a_id = dim_a.id")
    got = tk.must_query(sql).rs.rows
    assert got == _conventional(tk, sql)
    assert int(got[0][0]) == 40


def test_fused_sees_dim_updates(tk):
    """Fused path must see committed dim mutations (version-keyed caches
    invalidate on write) and must STAY on the fused path: MVCC keeps the
    old version row, which must not read as a duplicate key."""
    sql = "select sum(dim_a.val) from fact, dim_a where fact.a_id = dim_a.id"
    before = tk.must_query(sql).rs.rows
    tk.must_exec("update dim_a set val = val + 1000 where id = 1")
    hits = tk.domain.metrics.get("fused_pipeline_hit", 0)
    got = tk.must_query(sql).rs.rows
    assert tk.domain.metrics.get("fused_pipeline_hit", 0) == hits + 1
    assert got == _conventional(tk, sql)
    assert int(got[0][0]) > int(before[0][0])


def test_fused_dim_insert_invalidates_kernel(tk):
    """New dim rows after a cached kernel must join (kernel cache keys
    include dim row counts)."""
    sql = ("select count(*) from fact, dim_a where fact.a_id = dim_a.id")
    n1 = int(tk.must_query(sql).rs.rows[0][0])
    # fact rows reference a_id up to 44; dim_a has 1..40 -> add 41..44
    tk.must_exec("insert into dim_a values (41, 1, 'x', 1), "
                 "(42, 2, 'y', 2), (43, 3, 'z', 3), (44, 4, 'w', 4)")
    n2 = int(tk.must_query(sql).rs.rows[0][0])
    assert n2 > n1
    assert n2 == int(_conventional(tk, sql)[0][0])


def test_fused_semi_join(tk):
    """EXISTS/IN subqueries decorrelate to semi joins; the fused kernel
    masks on key existence (duplicate build keys allowed)."""
    sql = ("select dim_a.grp, count(*) from dim_a "
           "where exists (select 1 from fact "
           "where fact.a_id = dim_a.id and fact.q > 25) "
           "group by dim_a.grp order by dim_a.grp")
    plan = "\n".join(r[0] for r in tk.must_query("explain " + sql).rs.rows)
    assert "FusedPipeline" in plan, plan
    hits = tk.domain.metrics.get("fused_pipeline_hit", 0)
    got = tk.must_query(sql).rs.rows
    # the FILTERED, duplicate-key semi dim must actually run fused
    # (prefiltered meta), not silently fall back
    assert tk.domain.metrics.get("fused_pipeline_hit", 0) == hits + 1
    assert got == _conventional(tk, sql)


def test_fused_left_join(tk):
    sql = ("select dim_a.grp, count(fact.k), count(*) from dim_a "
           "left join fact on fact.a_id = dim_a.id "
           "group by dim_a.grp order by dim_a.grp")
    got = tk.must_query(sql).rs.rows
    assert got == _conventional(tk, sql)


def test_fused_left_join_fact_preserved(tk):
    """fact LEFT JOIN dim: unmatched fact rows keep NULL dim payload."""
    sql = ("select dim_b.tag, count(*), sum(fact.q) from fact "
           "left join dim_b on fact.b_id = dim_b.id "
           "group by dim_b.tag order by dim_b.tag")
    plan = "\n".join(r[0] for r in tk.must_query("explain " + sql).rs.rows)
    assert "FusedPipeline" in plan, plan
    got = tk.must_query(sql).rs.rows
    assert got == _conventional(tk, sql)


def test_fused_left_join_empty_dim(tk):
    """LEFT over an EMPTY dim preserves fact rows with NULL payload
    (review finding: the empty-dim early-exit returned [])."""
    tk.must_exec("create table dim_e2 (id int primary key, g varchar(8))")
    sql = ("select dim_e2.g, count(*) from fact left join dim_e2 "
           "on fact.b_id = dim_e2.id group by dim_e2.g")
    got = tk.must_query(sql).rs.rows
    assert got == _conventional(tk, sql)
    assert got[0][0] is None and int(got[0][1]) == 500


def test_fused_semi_filter_rejects_all_key_zero(tk):
    """EXISTS whose filter rejects EVERY build row matches nothing —
    including probe key 0 (review finding: the always-miss lut used
    sentinel 1, which the kernel's `lut[idx] < n` hit test read as a
    real hit for probe key == lo when the dim had >= 2 rows)."""
    tk.must_exec("insert into dim_a values (0, 0, 'nz', 0)")
    sql = ("select count(*) from dim_a "
           "where exists (select 1 from fact "
           "where fact.a_id = dim_a.id and fact.q > 9999)")
    assert tk.must_query(sql).rs.rows == [(0,)]
    assert _conventional(tk, sql) == [(0,)]


def test_row_blocks_and_the_overlay_merge_on_the_probe_key(monkeypatch):
    """Four row blocks' partials and the transaction's own rows as a
    fifth, under the chip's policy: each names `fact.d_id` as the item
    that identifies the group, the merge groups on it alone
    (tidb_tpu_agg_merge_total{path="ident"}), and the rows are the
    conventional subtree's."""
    import tidb_tpu.copr.agg_lowering as al
    from tidb_tpu.utils import metrics as mu
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    tk = TestKit()
    tk.must_exec("create table d (id int primary key, name varchar(16), "
                 "val int)")
    tk.must_exec("create table f (k int primary key, d_id int, q int)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i}, 'n{i % 11}', {i % 3})" for i in range(1, 201)))
    rng = np.random.RandomState(11)
    tk.must_exec("insert into f values " + ",".join(
        f"({i}, {rng.randint(1, 220)}, {rng.randint(0, 50)})"
        for i in range(1, 2001)))
    monkeypatch.setattr(tk.domain.copr, "device_rows", 500)
    sql = ("select f.d_id, d.name, d.val, sum(f.q), count(*) from f, d "
           "where f.d_id = d.id group by f.d_id, d.name, d.val "
           "order by f.d_id")

    def merged():
        before = mu.agg_merges()
        return tk.must_query(sql).rs.rows, mu.agg_merges(before)
    hit = tk.domain.metrics.get("fused_pipeline_hit", 0)
    got, grown = merged()
    assert tk.domain.metrics.get("fused_pipeline_hit", 0) == hit + 1
    assert grown == {"ident": 1}, grown
    assert got == _conventional(tk, sql) and len(got) == 200
    # the same three facts on the statement's `execute` span, not on a
    # span of the merge's own
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    tk.must_query(sql)
    spans = dict(tk.must_query(
        "select span, attrs from information_schema.tidb_trace_events "
        "where attrs like '%merge=%'").rs.rows)
    tk.must_exec("set tidb_tpu_trace_sample_rate = 0")
    assert list(spans) == ["execute"] and \
        "merge=ident" in spans["execute"] and \
        "merge_groups=200" in spans["execute"], spans
    tk.must_exec("begin")
    tk.must_exec("insert into f values (3001, 7, 5), (3002, 199, 1)")
    overlay = tk.domain.metrics.get("fused_pipeline_dirty_overlay", 0)
    dirty, grown = merged()
    assert tk.domain.metrics.get(
        "fused_pipeline_dirty_overlay", 0) == overlay + 1
    assert grown == {"ident": 1}, grown
    assert dirty == _conventional(tk, sql)
    tk.must_exec("rollback")
    assert [int(r[3]) for r in dirty if r[0] == 7] == \
        [int(r[3]) + 5 for r in got if r[0] == 7]


def test_host_partial_agg_shared_dicts():
    """Raw-string group keys aggregated chunk-by-chunk must encode
    through ONE shared dict: per-chunk dicts give colliding int64 codes
    that _merge_partials cannot tell apart (review finding)."""
    from tidb_tpu.copr.agg_lowering import host_partial_agg
    from tidb_tpu.copr.pipeline import _AggShim
    from tidb_tpu.expression import EvalCtx
    from tidb_tpu.expression.expr import Column
    from tidb_tpu.types.field_type import new_string_type

    class Agg:
        name = "count"
        args = []
        distinct = False
    col = Column(0, new_string_type(16))
    shim = _AggShim([col], [Agg()])
    shared = {}
    outs = []
    for chunk_vals in (["x", "x", "y"], ["y", "z"]):
        data = np.array(chunk_vals, dtype=object)
        ctx = EvalCtx(np, len(data), {0: (data, None, None)}, host=True)
        outs.append(host_partial_agg(
            ctx, shim, np.ones(len(data), dtype=bool),
            shared_dicts=shared))
    # codes from both chunks decode through the SAME dict
    d0 = outs[0].key_dicts[0]
    assert outs[1].key_dicts[0] is d0
    decode = {}
    for out in outs:
        for code, cnt in zip(out.keys[0], out.states[0][0]):
            decode.setdefault(d0.values[int(code)], 0)
            decode[d0.values[int(code)]] += int(cnt)
    assert decode == {"x": 2, "y": 2, "z": 1}


class TestDirtyOverlay:
    """Insert-only transaction deltas mount as an extra device
    partition (VERDICT r3 next #10; reference UnionScan
    builder.go:1473): the fused path survives concurrent OLTP inserts
    instead of falling back to the host join."""

    def _setup(self, tk):
        tk.must_exec("drop table if exists fo_f")
        tk.must_exec("drop table if exists fo_d")
        tk.must_exec("create table fo_d (id int primary key, "
                     "name varchar(10))")
        tk.must_exec("create table fo_f (id int primary key, did int, "
                     "v int)")
        tk.must_exec("insert into fo_d values (1,'a'),(2,'b'),(3,'c')")
        rows = ",".join(f"({i}, {i % 3 + 1}, {i * 10})"
                        for i in range(1, 301))
        tk.must_exec(f"insert into fo_f values {rows}")

    SQL = ("select fo_d.name, count(*), sum(fo_f.v) from fo_f, fo_d "
           "where fo_f.did = fo_d.id group by fo_d.name order by name")

    def test_insert_only_delta_stays_fused(self, tk):
        self._setup(tk)
        m = tk.domain.metrics
        want_clean = tk.must_query(self.SQL).rows
        tk.must_exec("begin")
        tk.must_exec("insert into fo_f values (900, 1, 1000), "
                     "(901, 2, 2000)")
        before = (m.get("fused_pipeline_hit", 0) +
                  m.get("fused_pipeline_mpp_hit", 0),
                  m.get("fused_pipeline_dirty_overlay", 0),
                  m.get("fused_pipeline_fallback", 0))
        got = tk.must_query(self.SQL).rows
        after = (m.get("fused_pipeline_hit", 0) +
                 m.get("fused_pipeline_mpp_hit", 0),
                 m.get("fused_pipeline_dirty_overlay", 0),
                 m.get("fused_pipeline_fallback", 0))
        tk.must_exec("rollback")
        # correctness: dirty rows visible to THIS txn only
        base = {r[0]: (r[1], r[2]) for r in want_clean}
        gmap = {r[0]: (r[1], r[2]) for r in got}
        assert gmap["a"] == (base["a"][0] + 1,
                             str(int(base["a"][1]) + 1000))
        assert gmap["b"] == (base["b"][0] + 1,
                             str(int(base["b"][1]) + 2000))
        assert gmap["c"] == base["c"]
        # routing: fused WITH the overlay, no fallback
        assert after[0] == before[0] + 1, (before, after)
        assert after[1] == before[1] + 1
        assert after[2] == before[2]
        # rolled back: clean again
        assert tk.must_query(self.SQL).rows == want_clean

    def test_update_delta_stays_fused(self, tk):
        self._setup(tk)
        m = tk.domain.metrics
        tk.must_exec("begin")
        tk.must_exec("update fo_f set v = 0 where id = 1")
        before = (m.get("fused_pipeline_dirty_overlay", 0),
                  m.get("fused_pipeline_fallback", 0))
        got = tk.must_query(self.SQL).rows
        assert m.get("fused_pipeline_dirty_overlay", 0) == before[0] + 1
        assert m.get("fused_pipeline_fallback", 0) == before[1]
        tk.must_exec("rollback")
        clean = tk.must_query(self.SQL).rows
        b_dirty = next(r for r in got if r[0] == "b")   # id 1 -> did 2
        b_clean = next(r for r in clean if r[0] == "b")
        assert int(b_dirty[2]) == int(b_clean[2]) - 10  # v 10 -> 0

    def test_dim_write_falls_back(self, tk):
        self._setup(tk)
        m = tk.domain.metrics
        tk.must_exec("begin")
        tk.must_exec("insert into fo_d values (4, 'd')")
        before = m.get("fused_pipeline_fallback", 0)
        tk.must_query(self.SQL)
        assert m.get("fused_pipeline_fallback", 0) == before + 1
        tk.must_exec("rollback")


def test_pipelined_partitions_regrow(monkeypatch):
    """Depth-2 partition pipelining with a consume-time group-bucket
    regrow: partition 0's retry must re-upload ITS OWN buffers (not the
    speculatively dispatched partition 1's, whose _bind_cols call
    overwrote copr._bind_keys), and a successor dispatched with the
    stale smaller bucket must re-run (ngroups is checked against the
    bucket its kernel was BUILT with, agg_param[0], not the regrown
    nonlocal)."""
    monkeypatch.setenv("TIDB_TPU_DEVICE_ROWS", "2048")
    tk = TestKit()
    tk.must_exec("create table wide (id bigint primary key, g bigint, "
                 "v int)")
    n, ngroups = 12000, 5000            # > the 1024 initial bucket
    rows = ",".join(
        f"({i}, {(i % ngroups) * 1000003}, {i % 101})"
        for i in range(n))
    tk.must_exec(f"insert into wide values {rows}")
    got = tk.must_query(
        "select g, sum(v), count(*) from wide group by g "
        "order by g").rs.rows
    exp = {}
    for i in range(n):
        k = (i % ngroups) * 1000003
        a, b = exp.get(k, (0, 0))
        exp[k] = (a + i % 101, b + 1)
    assert [(r[0], int(r[1]), int(r[2])) for r in got] == \
        [(k, *exp[k]) for k in sorted(exp)]
