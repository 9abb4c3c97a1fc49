"""Device-final TopN over fused-pipeline partials (Q3 shape).

When group keys ride a VERIFIED clustered storage order, per-run
partials are exact per-group, so the kernel can return only top-k
candidates (plus partition-boundary groups) instead of every group —
the difference between fetching ~76 rows and ~1M rows over the TPU
link. These tests pin the exactness machinery: the clustered tracker,
boundary-split groups across partitions, and the tie-boundary fallback.
"""
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.pipeline as pl
from tidb_tpu.testkit import TestKit


@pytest.fixture
def runs_impl():
    al._FORCE_SEGMENT_IMPL = "runs"
    try:
        yield
    finally:
        al._FORCE_SEGMENT_IMPL = None


def _mk_star(tk, n_orders=300, lines_per=4, val=lambda i: i % 97):
    """Clustered fact (l.ok monotone) joined to a dim with a filter."""
    tk.must_exec("create table d (ok int, dcat int, dval int)")
    tk.must_exec("create table f (ok int, v int)")
    drows = ",".join(f"({k},{k % 7},{k % 13})" for k in range(1, n_orders + 1))
    tk.must_exec(f"insert into d values {drows}")
    rows = []
    i = 0
    for k in range(1, n_orders + 1):
        for _ in range(lines_per):
            rows.append(f"({k},{val(i)})")
            i += 1
    tk.must_exec("insert into f values " + ",".join(rows))


TOPN_SQL = ("select f.ok, d.dval, sum(f.v) s from f join d on f.ok = d.ok "
            "where d.dcat < 5 group by f.ok, d.dval "
            "order by s desc, f.ok limit 7")


def _host_rows(tk, sql):
    tk.domain.copr.use_device = False
    rows = tk.must_query(sql).rows
    tk.domain.copr.use_device = True
    return rows


def test_fused_topn_candidates_match_host(runs_impl):
    tk = TestKit()
    _mk_star(tk)
    calls = {"n": 0, "sizes": []}
    orig = pl._topn_select

    def spy(res, aggs, topn, bucket, *group_metric):
        calls["n"] += 1
        calls["sizes"].append(topn[3])
        return orig(res, aggs, topn, bucket, *group_metric)
    pl._topn_select = spy
    try:
        dev = tk.must_query(TOPN_SQL).rows
    finally:
        pl._topn_select = orig
    assert calls["n"] == 1          # the kernel traced with topn
    host = _host_rows(tk, TOPN_SQL)
    assert [tuple(map(str, r)) for r in dev] == \
        [tuple(map(str, r)) for r in host]


def test_fused_topn_boundary_split_partitions(runs_impl):
    """A clustered group whose rows straddle the partition edge must
    merge exactly: boundary runs are forced into the candidate set."""
    tk = TestKit()
    _mk_star(tk, n_orders=100, lines_per=8)
    # 8-row groups + a partition size not divisible by 8: every edge
    # splits a group
    tk.domain.copr.device_rows = 251
    dev = tk.must_query(TOPN_SQL).rows
    host = _host_rows(tk, TOPN_SQL)
    assert [tuple(map(str, r)) for r in dev] == \
        [tuple(map(str, r)) for r in host]


def test_fused_topn_negative_sums_split_groups(runs_impl):
    """Sums that go negative across a partition split: the coverage
    proof must not let a boundary partial's inflated local metric vouch
    for dropping complete groups."""
    tk = TestKit()
    _mk_star(tk, n_orders=120, lines_per=8,
             val=lambda i: ((i * 37) % 23) - 11)
    tk.domain.copr.device_rows = 251
    sql = ("select f.ok, sum(f.v) s from f join d on f.ok = d.ok "
           "group by f.ok order by s desc, f.ok limit 5")
    dev = tk.must_query(sql).rows
    host = _host_rows(tk, sql)
    assert [tuple(map(str, r)) for r in dev] == \
        [tuple(map(str, r)) for r in host]


@pytest.mark.parametrize("stride, pin", [(1000003, "sorted"), (1, "dense")])
def test_fused_topn_disabled_after_degrade_pin(runs_impl, monkeypatch,
                                               stride, pin):
    """Once the runs-degradation guard pins a shape to the sorted
    lowering, candidate pruning must switch off (its boundary-forcing
    assumes storage order) and results must stay exact. Keys that span
    a dense integer domain (stride 1: 1..400) are pinned to the dense
    table instead, by the host's count of key changes before any
    program is built (PR 38): no pruning there either."""
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    tk = TestKit()
    # wide unclustered-ish keys: clustered anchor exists (monotone ok)
    # but 1 row per group fires the degrade guard (ngroups > m//4)
    tk.must_exec("create table d (ok bigint, dval int)")
    tk.must_exec("create table f (ok bigint, v int)")
    n = 400
    tk.must_exec("insert into d values " + ",".join(
        f"({k * stride},{k % 13})" for k in range(1, n + 1)))
    tk.must_exec("insert into f values " + ",".join(
        f"({k * stride},{(k * 31) % 50})" for k in range(1, n + 1)))
    sql = ("select f.ok, sum(f.v) s from f join d on f.ok = d.ok "
           "group by f.ok order by s desc, f.ok limit 4")
    calls = {"n": 0}
    orig = pl._topn_select

    def spy(res, aggs, topn, bucket, *group_metric):
        calls["n"] += 1
        return orig(res, aggs, topn, bucket, *group_metric)
    pl._topn_select = spy
    try:
        dev = tk.must_query(sql).rows       # degrades mid-loop
        dev2 = tk.must_query(sql).rows      # pinned sorted: no pruning
    finally:
        pl._topn_select = orig
    host = _host_rows(tk, sql)
    for got in (dev, dev2):
        assert [tuple(map(str, r)) for r in got] == \
            [tuple(map(str, r)) for r in host]
    hc = tk.domain.copr._host_cache
    assert [v for k, v in hc.items()
            if k and k[0] == "aggimpl"] == [pin]


def test_fused_topn_tie_fallback(runs_impl, judged_runs):
    """All groups tie on the metric: the candidate set cannot prove
    coverage, so the shape must fall back (off flag) and still answer
    from full partials."""
    tk = TestKit()
    _mk_star(tk, n_orders=2500, lines_per=1, val=lambda i: 5)
    sql = ("select f.ok, sum(f.v) s from f join d on f.ok = d.ok "
           "group by f.ok order by s desc, f.ok limit 3")
    dev = tk.must_query(sql).rows
    host = _host_rows(tk, sql)
    assert [tuple(map(str, r)) for r in dev] == \
        [tuple(map(str, r)) for r in host]
    hc = tk.domain.copr._host_cache
    assert any(k and k[0] == "ftopn_off" for k in hc)
    # three device runs, one count each: the bucket grows, the
    # candidates' run is thrown away unproven (and not counted as one
    # that stood), the run without top-n answers
    assert judged_runs() == {
        ("fused", "posruns", "retry_grow_bucket"): 1,
        ("fused", "posruns", "retry_topn_unproven"): 1,
        ("fused", "posruns", "stands"): 1}


def test_clustered_tracker():
    from tidb_tpu.storage.columnar import ColumnarTable
    from tidb_tpu.models.schema import TableInfo, ColumnInfo
    from tidb_tpu.types.field_type import new_bigint_type

    ti = TableInfo(id=900, name="t",
                   columns=[ColumnInfo(id=1, name="a", offset=0,
                                       ft=new_bigint_type())])
    tbl = ColumnarTable(ti)
    from tidb_tpu.types.datum import Datum, Kind
    for h, v in enumerate([3, 3, 5, 9], start=1):
        tbl.put_row(h, [Datum(Kind.INT, v)])
    assert tbl.is_clustered(1)
    tbl.put_row(10, [Datum(Kind.INT, 100)])      # still monotone
    assert tbl.is_clustered(1)
    tbl.put_row(11, [Datum(Kind.INT, 4)])        # out of order
    assert not tbl.is_clustered(1)
    # demotion is sticky even if later appends are ordered again
    tbl.put_row(12, [Datum(Kind.INT, 500)])
    assert not tbl.is_clustered(1)


def test_clustered_tracker_null_and_update():
    from tidb_tpu.storage.columnar import ColumnarTable
    from tidb_tpu.models.schema import TableInfo, ColumnInfo
    from tidb_tpu.types.field_type import new_bigint_type
    from tidb_tpu.types.datum import Datum, Kind

    ti = TableInfo(id=901, name="t",
                   columns=[ColumnInfo(id=1, name="a", offset=0,
                                       ft=new_bigint_type())])
    tbl = ColumnarTable(ti)
    tbl.put_row(1, [Datum(Kind.INT, 1)])
    tbl.put_row(2, [Datum(Kind.INT, 2)])
    assert tbl.is_clustered(1)
    # an UPDATE appends a new version at the tail -> order broken
    tbl.put_row(1, [Datum(Kind.INT, 1)], commit_ts=5)
    assert not tbl.is_clustered(1)

    tbl2 = ColumnarTable(TableInfo(id=902, name="t2",
                                   columns=[ColumnInfo(
                                       id=1, name="a", offset=0,
                                       ft=new_bigint_type())]))
    tbl2.put_row(1, [Datum(Kind.INT, 1)])
    tbl2.put_row(2, [None])                      # NULL breaks clustering
    assert not tbl2.is_clustered(1)
