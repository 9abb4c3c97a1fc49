"""TPC-H correctness: SQL-engine results (device copr path) vs independent
numpy computation over the same raw arrays, plus device-vs-host-path
agreement (the reference's vec-vs-row oracle, SURVEY.md §7)."""
import numpy as np
import pytest

from tidb_tpu.testkit import TestKit
from tidb_tpu.bench.tpch import load_tpch, Q1, Q3, Q5, Q6
from tidb_tpu.types.time_types import parse_date


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    load_tpch(tk, sf=0.003, seed=11)
    return tk


def _raw(tk, table, col):
    tbl = tk.domain.infoschema().table_by_name("test", table)
    ctab = tk.domain.columnar.tables[tbl.id]
    ci = tbl.find_column(col)
    data = ctab.data[ci.id][:ctab.n]
    d = ctab.dicts.get(ci.id)
    if d is not None:
        return np.array([d.values[c] for c in data], dtype=object)
    return data.copy()


class TestQ6:
    def test_q6_vs_numpy(self, tk):
        ship = _raw(tk, "lineitem", "l_shipdate")
        disc = _raw(tk, "lineitem", "l_discount")
        qty = _raw(tk, "lineitem", "l_quantity")
        price = _raw(tk, "lineitem", "l_extendedprice")
        lo = parse_date("1994-01-01")
        hi = parse_date("1995-01-01")
        mask = (ship >= lo) & (ship < hi) & (disc >= 5) & (disc <= 7) & \
            (qty < 2400)
        want = int((price[mask] * disc[mask]).sum())  # scale 2+2 = 4
        got = tk.must_query(Q6).rows[0][0]
        if want == 0:
            assert got is None or float(got) == 0
        else:
            assert got == f"{want / 10000:.4f}"

    def test_q6_device_vs_host(self, tk):
        r_dev = tk.must_query(Q6).rows
        tk.domain.copr.use_device = False
        try:
            r_host = tk.must_query(Q6).rows
        finally:
            tk.domain.copr.use_device = True
        assert r_dev == r_host


class TestQ1:
    def test_q1_vs_numpy(self, tk):
        ship = _raw(tk, "lineitem", "l_shipdate")
        rf = _raw(tk, "lineitem", "l_returnflag")
        ls = _raw(tk, "lineitem", "l_linestatus")
        qty = _raw(tk, "lineitem", "l_quantity")
        price = _raw(tk, "lineitem", "l_extendedprice")
        disc = _raw(tk, "lineitem", "l_discount")
        cutoff = parse_date("1998-12-01") - 90
        mask = ship <= cutoff
        groups = {}
        for i in np.nonzero(mask)[0]:
            key = (rf[i], ls[i])
            g = groups.setdefault(key, [0, 0, 0, 0])
            g[0] += int(qty[i])
            g[1] += int(price[i])
            g[2] += int(price[i]) * (100 - int(disc[i]))
            g[3] += 1
        rows = tk.must_query(Q1).rows
        assert len(rows) == len(groups)
        for row in rows:
            key = (row[0], row[1])
            g = groups[key]
            assert row[2] == f"{g[0] / 100:.2f}"          # sum_qty
            assert row[3] == f"{g[1] / 100:.2f}"          # sum_base_price
            assert row[4] == f"{g[2] / 10000:.4f}"        # sum_disc_price
            assert row[9] == g[3]                          # count_order
        # ordered by returnflag, linestatus
        keys = [(r[0], r[1]) for r in rows]
        assert keys == sorted(keys)

    def test_q1_device_vs_host(self, tk):
        r_dev = tk.must_query(Q1).rows
        tk.domain.copr.use_device = False
        try:
            r_host = tk.must_query(Q1).rows
        finally:
            tk.domain.copr.use_device = True
        assert r_dev == r_host


class TestQ3Q5:
    def test_q3_vs_numpy(self, tk):
        seg = _raw(tk, "customer", "c_mktsegment")
        ckey = _raw(tk, "customer", "c_custkey")
        okey = _raw(tk, "orders", "o_orderkey")
        ocust = _raw(tk, "orders", "o_custkey")
        odate = _raw(tk, "orders", "o_orderdate")
        lkey = _raw(tk, "lineitem", "l_orderkey")
        ship = _raw(tk, "lineitem", "l_shipdate")
        price = _raw(tk, "lineitem", "l_extendedprice")
        disc = _raw(tk, "lineitem", "l_discount")
        cut = parse_date("1995-03-15")
        bld = set(ckey[seg == "BUILDING"].tolist())
        ord_ok = {int(k): int(d) for k, d, c in zip(okey, odate, ocust)
                  if d < cut and int(c) in bld}
        rev = {}
        for i in range(len(lkey)):
            k = int(lkey[i])
            if k in ord_ok and ship[i] > cut:
                rev[k] = rev.get(k, 0) + int(price[i]) * (100 - int(disc[i]))
        want = sorted(rev.items(), key=lambda kv: (-kv[1], ord_ok[kv[0]]))[:10]
        rows = tk.must_query(Q3).rows
        assert len(rows) == len(want)
        for row, (k, r) in zip(rows, want):
            assert row[0] == k
            assert row[1] == f"{r / 10000:.4f}"

    def test_q5_runs_and_matches_host(self, tk):
        r_dev = tk.must_query(Q5).rows
        tk.domain.copr.use_device = False
        try:
            r_host = tk.must_query(Q5).rows
        finally:
            tk.domain.copr.use_device = True
        assert r_dev == r_host
        # revenue sorted desc
        revs = [float(r[1]) for r in r_dev]
        assert revs == sorted(revs, reverse=True)

    def test_q3_device_vs_host(self, tk):
        r_dev = tk.must_query(Q3).rows
        tk.domain.copr.use_device = False
        try:
            r_host = tk.must_query(Q3).rows
        finally:
            tk.domain.copr.use_device = True
        assert r_dev == r_host


from tidb_tpu.bench.tpch import ALL_QUERIES


@pytest.mark.parametrize("qname", sorted(ALL_QUERIES.keys(),
                                         key=lambda q: int(q[1:])))
def test_all_queries_device_vs_host(tk, qname):
    """Every TPC-H query runs end-to-end; device copr path agrees with the
    host numpy path (the round-trip vec-vs-row oracle)."""
    sql = ALL_QUERIES[qname]
    r_dev = tk.must_query(sql).rows
    tk.domain.copr.use_device = False
    try:
        r_host = tk.must_query(sql).rows
    finally:
        tk.domain.copr.use_device = True
    assert r_dev == r_host


# a routing regression (silent fall-off to the host join) fails here,
# not just in the benchmark
from tidb_tpu.bench.tpch import FUSED_QUERIES


def test_fused_routing_pinned(tk):
    d = tk.domain
    base_err = d.metrics.get("fused_pipeline_error", 0)
    for q in FUSED_QUERIES:
        before = d.metrics.get("fused_pipeline_hit", 0) + \
            d.metrics.get("fused_pipeline_mpp_hit", 0)
        tk.must_query(ALL_QUERIES[q])
        after = d.metrics.get("fused_pipeline_hit", 0) + \
            d.metrics.get("fused_pipeline_mpp_hit", 0)
        assert after > before, f"{q} fell off the fused device path"
    assert d.metrics.get("fused_pipeline_error", 0) == base_err, \
        "fused pipeline raised during the TPC-H sweep"


class TestMoreOracles:
    def test_q12_vs_numpy(self, tk):
        from tidb_tpu.bench.tpch import Q12
        lkey = _raw(tk, "lineitem", "l_orderkey")
        mode = _raw(tk, "lineitem", "l_shipmode")
        commit = _raw(tk, "lineitem", "l_commitdate")
        receipt = _raw(tk, "lineitem", "l_receiptdate")
        ship = _raw(tk, "lineitem", "l_shipdate")
        okey = _raw(tk, "orders", "o_orderkey")
        oprio = _raw(tk, "orders", "o_orderpriority")
        lo = parse_date("1994-01-01")
        hi = parse_date("1995-01-01")
        prio = {int(k): p for k, p in zip(okey, oprio)}
        want = {}
        for i in range(len(lkey)):
            if mode[i] not in ("MAIL", "SHIP"):
                continue
            if not (commit[i] < receipt[i] and ship[i] < commit[i]
                    and lo <= receipt[i] < hi):
                continue
            p = prio[int(lkey[i])]
            h, l = want.setdefault(mode[i], [0, 0])
            if p in ("1-URGENT", "2-HIGH"):
                want[mode[i]][0] += 1
            else:
                want[mode[i]][1] += 1
        rows = tk.must_query(Q12).rows
        got = {r[0]: [int(r[1]), int(r[2])] for r in rows}
        assert got == want

    def test_q14_vs_numpy(self, tk):
        from tidb_tpu.bench.tpch import Q14
        pkey = _raw(tk, "part", "p_partkey")
        ptype = _raw(tk, "part", "p_type")
        lpart = _raw(tk, "lineitem", "l_partkey")
        ship = _raw(tk, "lineitem", "l_shipdate")
        price = _raw(tk, "lineitem", "l_extendedprice")
        disc = _raw(tk, "lineitem", "l_discount")
        lo = parse_date("1995-09-01")
        hi = parse_date("1995-10-01")
        promo_parts = {int(k) for k, t in zip(pkey, ptype)
                       if str(t).startswith("PROMO")}
        num = den = 0
        for i in range(len(lpart)):
            if not (lo <= ship[i] < hi):
                continue
            rev = int(price[i]) * (100 - int(disc[i]))
            den += rev
            if int(lpart[i]) in promo_parts:
                num += rev
        rows = tk.must_query(Q14).rows
        if den == 0:
            assert rows[0][0] is None
        else:
            got = float(rows[0][0])
            want = 100.0 * num / den
            assert abs(got - want) < 1e-6 * max(abs(want), 1)
