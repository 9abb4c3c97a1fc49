"""The deployment `tpch-sf1-set2-1chip` at a small size on the CPU
backend: the benchmark's own data set and plain numpy references
(benchmark/datasets/tpch_set2.py, loaded by path: Q4, Q9, Q12, Q13, Q17,
Q19), the store behind the wire server as `--serve` starts it, on one
device and on a `dp` mesh of the 8 forced host devices; the counter and
the two spans the deployment brought (`tidb_tpu_fused_dim_probe_total`,
`matdim`, `dict_filter`); and the cell `tpch-sf1-set2.power` driven
through the harness. Counts and answers here are correctness results,
never device times."""
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
from tidb_tpu.parallel import make_mesh
from tidb_tpu.server import Server
from tidb_tpu.session import new_store
from tidb_tpu.testkit import MiniClient
from tidb_tpu.utils import metrics as mu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# a seed under which Q17 and Q19 find rows in a 60,000-row lineitem
SCALE, SEED = 0.01, 3_800_000_005
STATEMENTS = ("q4", "q9", "q12", "q13", "q17", "q19")
DIMENSIONS = {"q4": 1, "q9": 5, "q12": 1, "q13": 1, "q17": 2, "q19": 1}
DEGRADE = ("device_fallback", "device_dispatch_error", "device_retry",
           "device_breaker_open", "fused_pipeline_error")


def _dataset():
    spec = importlib.util.spec_from_file_location(
        "set2_deployment_tpch",
        os.path.join(BENCH, "datasets", "tpch_set2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Deployment:
    def __init__(self, data_dir, ndev):
        self.ds = _dataset()
        self.domain = new_store(str(data_dir))
        self.domain.start_background()
        self.domain.copr._mesh = make_mesh(ndev) if ndev > 1 else False
        self.server = Server(self.domain, port=0).start()
        admin = self.client()
        self.tables = self.ds.generate(SCALE, SEED)
        dom = self.domain
        self.ds.load(self.tables, admin.query, lambda name:
                     dom.columnar.table(
                         dom.infoschema().table_by_name("test", name)))
        admin.query("set global tidb_mpp_min_rows = 0")
        admin.close()

    def client(self):
        return MiniClient(self.server.port, db="test", timeout=120)

    def close(self):
        self.server.shutdown()
        self.domain.timer.stop_all()
        self.domain.close()


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    d = Deployment(tmp_path_factory.mktemp("one"), 1)
    yield d
    d.close()


@pytest.fixture(scope="module")
def mesh8(tmp_path_factory):
    if len(jax.devices()) < 8:
        pytest.skip("needs eight devices for the mesh")
    d = Deployment(tmp_path_factory.mktemp("mesh8"), 8)
    yield d
    d.close()


def _moved(counter, since=None):
    since = since or {}
    now = {tuple(sorted(lb.items())): int(v)
           for _n, lb, v in counter.sample_rows()}
    return {k: n - since.get(k, 0) for k, n in now.items()
            if n - since.get(k, 0)}


def _answer(d, stmt):
    """-> (rows, what the degrade counters and the fused counters grew
    by); no warning 9013."""
    c, dom = d.client(), d.domain
    before = dict(dom.metrics)
    try:
        got = c.query(d.ds.STATEMENTS[stmt])["rows"]
        assert c.query("show warnings")["rows"] == []
    finally:
        c.close()
    return got, {k: dom.metrics.get(k, 0) - before.get(k, 0)
                 for k in DEGRADE + ("fused_pipeline_hit",
                                     "fused_pipeline_mpp_hit")}


@pytest.mark.parametrize("stmt", STATEMENTS)
def test_statement_on_one_device_equals_the_reference(one_device, stmt):
    got, grown = _answer(one_device, stmt)
    want = one_device.ds.reference(one_device.tables, stmt)
    assert not one_device.ds.answer_wrong(got, want), (got[:3], want[:3])
    assert want[0][1][0] is not None        # an answer, not an empty sum
    assert grown.pop("fused_pipeline_hit") >= 1
    assert not any(grown.values()), grown


@pytest.mark.parametrize("stmt", STATEMENTS)
def test_statement_on_the_mesh_route_equals_the_reference(mesh8, stmt):
    """Every statement of the six is eligible: its outermost fused
    pipeline runs as one shard_map program over the 8 devices (Q13's
    and Q17's aggregate dimensions are statements of their own, routed
    by their own fact tables)."""
    got, grown = _answer(mesh8, stmt)
    want = mesh8.ds.reference(mesh8.tables, stmt)
    assert not mesh8.ds.answer_wrong(got, want), (got[:3], want[:3])
    assert grown.pop("fused_pipeline_mpp_hit") >= 1
    grown.pop("fused_pipeline_hit")
    assert not any(grown.values()), grown
    assert mu.mesh_routes().get(("mesh", "ok"), 0) >= 1


def test_customers_without_a_kept_order_come_out_of_the_outer_join(
        one_device):
    """Q13: a third of the customers have no order at all (no order for
    a key divisible by 3) and must come out with `c_count` 0."""
    d = one_device
    got, _ = _answer(d, "q13")
    per = d.ds.q13_counts(d.tables)
    childless = int((per == 0).sum())
    assert childless >= len(per) // 3
    assert (str(0), str(childless)) in [tuple(r) for r in got]
    assert sum(int(r[1]) for r in got) == len(per)


@pytest.mark.parametrize("stmt", ["q9", "q17", "q19"])
def test_float32_control_reads_wrong(stmt):
    """The reference accumulated in float32, in the program's place,
    is not correct where sums pass 2^24 (numpy alone, at a tenth of the
    cell's scale, where Q17 and Q19 sum enough rows)."""
    ds = _dataset()
    tables = ds.generate(0.1 if stmt != "q17" else 0.5, SEED)
    low = [r for _, r in ds.reference(tables, stmt, np.float32)]
    assert ds.answer_wrong(low, ds.reference(tables, stmt))


@pytest.mark.parametrize("stmt", ["q4", "q12", "q13"])
def test_float32_control_reads_right_on_small_counts(stmt):
    ds = _dataset()
    tables = ds.generate(SCALE, SEED)
    low = [r for _, r in ds.reference(tables, stmt, np.float32)]
    assert not ds.answer_wrong(low, ds.reference(tables, stmt))


# ---- the counter and the two spans -------------------------------------

@pytest.mark.parametrize("route", ["one_device", "mesh8"])
@pytest.mark.parametrize("stmt", STATEMENTS)
def test_every_dimension_is_counted_once_under_one_mode(request, route,
                                                        stmt):
    d = request.getfixturevalue(route)
    before = _moved(mu.FUSED_DIM_PROBE)
    _answer(d, stmt)
    grown = _moved(mu.FUSED_DIM_PROBE, before)
    # the aggregate dimensions of Q13 and Q17 are cached by now or run
    # statements without a dimension of their own
    assert sum(grown.values()) == DIMENSIONS[stmt], grown
    modes = {dict(k)["mode"] for k in grown}
    assert modes <= {"folded", "direct", "search", "bucket", "exists",
                     "matdim"}
    joins = {dict(k)["join"] for k in grown}
    assert joins == {{"q4": "semi", "q13": "left"}.get(stmt, "inner")}
    if stmt == "q9":
        # partsupp, on two columns whose packed span is no direct
        # table: buckets on ps_partkey (PR 40), on either route
        assert grown[(("join", "inner"), ("mode", "bucket"))] == 1
        assert grown[(("join", "inner"), ("mode", "folded"))] == 1
        assert "search" not in modes
    else:
        assert "bucket" not in modes


def _spans(c, sql, name):
    """Run `sql` sampled -> the attrs of the recorded spans called
    `name`, oldest first."""
    c.query("set tidb_tpu_trace_sample_rate = 1")
    c.query(sql)
    c.query("set tidb_tpu_trace_sample_rate = 0")
    return [r[0] for r in c.query(
        "select attrs from information_schema.tidb_trace_events "
        f"where span = '{name}' order by time")["rows"]]


def test_matdim_builds_once_a_table_version(one_device):
    """`matdim` reads `build`, then `hit` over unchanged tables, and
    `build` again after a commit to a base table of the subplan."""
    d = one_device
    c = d.client()
    try:
        c.query("insert into lineitem (l_orderkey, l_partkey, l_suppkey, "
                "l_linenumber, l_quantity, l_extendedprice, l_discount, "
                "l_tax, l_returnflag, l_linestatus, l_shipdate, "
                "l_commitdate, l_receiptdate, l_shipinstruct, l_shipmode, "
                "l_comment) values (1, 1, 1, 9, 1.00, 10.00, 0.00, 0.00, "
                "'N', 'O', '1996-01-01', '1996-01-02', '1996-01-03', "
                "'NONE', 'AIR', 'a new line')")
        seen = []
        for _ in range(3):
            before = _moved(mu.MATDIM)
            c.query(d.ds.STATEMENTS["q17"])
            seen.append({dict(k)["outcome"]: n for k, n in
                         _moved(mu.MATDIM, before).items()})
        assert seen == [{"build": 1}, {"hit": 1}, {"hit": 1}], seen
        c.query("delete from lineitem where l_orderkey = 1 and "
                "l_linenumber = 9")
        before = _moved(mu.MATDIM)
        got = c.query(d.ds.STATEMENTS["q17"])["rows"]
        assert {dict(k)["outcome"]: n for k, n in
                _moved(mu.MATDIM, before).items()} == {"build": 1}
        assert not d.ds.answer_wrong(got, d.ds.reference(d.tables, "q17"))
        attrs = _spans(c, d.ds.STATEMENTS["q17"], "matdim")
        assert attrs and "outcome=hit" in attrs[-1], attrs
        assert "groups=" in attrs[-1] and "rows=" in attrs[-1]
    finally:
        c.close()


def test_dict_filter_answers_an_unchanged_dictionary_from_its_table(
        one_device):
    """`dict_filter` reads `build` where a predicate first meets a
    dictionary, nothing while the compiled program holds the table,
    `hit` where another program asks the same predicate of the
    unchanged dictionary, and `build` again once the dictionary has
    grown."""
    d = one_device
    like = "from part where p_comment like '%fox%'"
    c = d.client()

    def grown_by(sql):
        before = _moved(mu.DICT_FILTER)
        rows = c.query(sql)["rows"]
        return rows, {dict(k)["outcome"]: n for k, n in
                      _moved(mu.DICT_FILTER, before).items()}
    try:
        first, how = grown_by("select count(*) " + like)
        assert how == {"build": 1}, how
        assert grown_by("select count(*) " + like) == (first, {})
        other, how = grown_by("select count(*), max(p_size) " + like)
        assert how == {"hit": 1} and other[0][0] == first[0][0], how
        c.query("insert into part values (900001, 'a part', "
                "'Manufacturer#1', 'Brand#11', 'PROMO PLATED TIN', 1, "
                "'SM BOX', 1.00, 'one more fox')")
        again, how = grown_by("select count(*) " + like)
        assert how == {"build": 1}, how
        assert int(again[0][0]) == int(first[0][0]) + 1
        attrs = _spans(c, "select count(*), min(p_size) " + like,
                       "dict_filter")
        assert attrs and "outcome=hit" in attrs[-1], attrs
        assert "values=" in attrs[-1] and "kept=" in attrs[-1]
        c.query("delete from part where p_partkey = 900001")
    finally:
        c.close()


# ---- the chip's lowering policy over keys that do not cluster ----------

@pytest.fixture(scope="module")
def runs_device(tmp_path_factory):
    """A store of its own: what a shape has taught lives with it."""
    d = Deployment(tmp_path_factory.mktemp("runs"), 1)
    yield d
    d.close()


@pytest.mark.parametrize("stmt, sites", [
    ("q17", {"fused"}),             # avg(l_quantity) by l_partkey
    ("q13", {"dag", "fused"})])     # orders by o_custkey; by c_custkey
def test_unclustered_keys_over_a_dense_domain_take_the_dense_table(
        runs_device, monkeypatch, stmt, sites):
    """Under the chip's policy ("runs", forced here) a group key the
    storage does not cluster, over an integer domain of at most
    DENSE_MAX values, goes to the dense table at once: the host counted
    the key changes, so no runs program is thrown away, and the argsort
    program (minutes and 29 GB to compile at a row block's width: what
    ended the first chip run of Q17, PERF.md) is never built."""
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 1024)
    d = runs_device
    before = _moved(mu.AGG_LOWERING)
    got, grown = _answer(d, stmt)
    assert not d.ds.answer_wrong(got, d.ds.reference(d.tables, stmt))
    assert not any(grown[k] for k in DEGRADE), grown
    runs = {tuple(v for _k, v in k): n
            for k, n in _moved(mu.AGG_LOWERING, before).items()}
    # (kind, site, verdict), sorted by label name
    assert {k[2] for k in runs} == {"stands"}, runs
    assert {k[1] for k in runs if k[0] == "dense"} == sites, runs
    assert not [k for k in runs if k[0].startswith("sort")], runs


# ---- the cell through the harness --------------------------------------

def _drive(wrapper=None):
    sys.path.insert(0, BENCH)
    import run
    keep = {}
    result = run.run_cell("tpch-sf1-set2.power", SEED, 3.0, False,
                          need_chips=False, scale=SCALE,
                          client_wrapper=wrapper, keep=keep)
    return result, keep


def test_the_cell_reports_correct():
    result, keep = _drive()
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["answers_compared"][0] >= 6
    assert {q.name for q in keep["queries"]} == set(STATEMENTS)
    assert set(result["metrics"]) == {"query_rate", "query_geomean_ms",
                                      "setup_s"}


def test_the_cell_with_one_answer_altered_is_not_correct():
    def wrapper(clients):
        c = clients[0]
        inner = c.wire.rows
        state = {"n": 0}

        def rows(sql):
            out = inner(sql)
            state["n"] += c.deadline != float("inf") and \
                not sql.startswith("show")
            if state["n"] == 3 and out:      # one answer, inside the window
                out[0] = out[0][:-1] + (out[0][-1] + "1",)
                state["n"] += 1
            return out
        c.wire.rows = rows
    result, _ = _drive(wrapper)
    assert not result["correct"]
    assert result["compared"]["answers_wrong"][0] == 1
    assert result["failed"] == 1


# ---- the form of BENCHMARK.json ----------------------------------------

def _lines_of(entry, keys):
    return [(entry["name"], k, entry[k]) for k in keys]


def test_benchmark_file_keeps_every_line_within_200_characters():
    """The driver refuses the file before any run over one `why`,
    `source` or `layer` that is empty, longer than 200 characters, not on
    one line or not printable ASCII (this PR's first submission: 203)."""
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    lines = [x for c in bench["configs"]
             for x in _lines_of(c, ("source", "why"))]
    lines += [x for w in bench["workloads"] for x in _lines_of(w, ("why",))]
    lines += [x for m in bench["per_layer"] for x in _lines_of(m, ("layer",))]
    lines += [("command", i, word) for i, word in enumerate(bench["command"])]
    bad = [(name, key, len(text)) for name, key, text in lines
           if not 1 <= len(text) <= 200
           or any(not 32 <= ord(ch) < 127 for ch in text)]
    assert not bad
