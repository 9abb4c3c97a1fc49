"""The deployment `tpch-sf1-mesh4` at a small size on the CPU backend:
the benchmark's own data set and numpy references
(benchmark/datasets/tpch.py, loaded by path), the store behind the wire
server as `--serve` starts it, and one `dp` mesh of 4 made from the
first four of the 8 forced host devices; and `tpch-sf3-mesh4`'s
relation at the same size: a shard larger than a one-chip row block
(`shard_over_block`). Counts and answers here are correctness results,
never device times."""
import importlib.util
import os

import jax
import pytest

import tidb_tpu.copr.agg_lowering as al
from tidb_tpu.parallel import make_mesh
from tidb_tpu.server import Server
from tidb_tpu.session import new_store
from tidb_tpu.testkit import MiniClient
from tidb_tpu.utils import failpoint
from tidb_tpu.utils import metrics as mu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE, SEED = 0.01, 3_100_000_007       # a 60,000-row lineitem
DEGRADE = ("device_fallback", "device_dispatch_error", "device_retry",
           "device_breaker_open", "fused_pipeline_error")

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four devices for the mesh")


def _dataset():
    spec = importlib.util.spec_from_file_location(
        "mesh_deployment_tpch",
        os.path.join(ROOT, "benchmark", "datasets", "tpch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Deployment:
    def __init__(self, data_dir, ndev):
        self.ds = _dataset()
        self.domain = new_store(str(data_dir))
        self.domain.start_background()
        # the mesh `_get_mesh` would make of a four-device process
        self.domain.copr._mesh = make_mesh(ndev) if ndev > 1 else False
        self.server = Server(self.domain, port=0).start()
        admin = self.client()
        self.tables = self.ds.generate(SCALE, SEED)
        dom = self.domain
        self.ds.load(self.tables, admin.query, lambda name:
                     dom.columnar.table(
                         dom.infoschema().table_by_name("test", name)))
        # a small lineitem is under the default 65,536
        admin.query("set global tidb_mpp_min_rows = 0")
        admin.close()

    def client(self):
        return MiniClient(self.server.port, db="test", timeout=120)

    def close(self):
        self.server.shutdown()
        self.domain.timer.stop_all()
        self.domain.close()


@pytest.fixture(scope="module")
def mesh4(tmp_path_factory):
    d = Deployment(tmp_path_factory.mktemp("mesh4"), 4)
    yield d
    d.close()


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    d = Deployment(tmp_path_factory.mktemp("one"), 1)
    yield d
    d.close()


routes = mu.mesh_routes
merges = mu.agg_merges


@pytest.mark.parametrize("stmt", ["q1", "q3", "q5", "q6", "q10", "q18"])
def test_statement_on_the_mesh_equals_the_reference(mesh4, stmt):
    c = mesh4.client()
    dom = mesh4.domain
    before = dict(dom.metrics)
    try:
        got = c.query(mesh4.ds.STATEMENTS[stmt])["rows"]
        warned = c.query("show warnings")["rows"]
    finally:
        c.close()
    want = mesh4.ds.reference(mesh4.tables, stmt)
    assert not mesh4.ds.answer_wrong(got, want), (got[:3], want[:3])
    assert warned == []
    grown = {k: dom.metrics.get(k, 0) - before.get(k, 0)
             for k in DEGRADE + ("fused_pipeline_mpp_hit",)}
    assert grown.pop("fused_pipeline_mpp_hit") >= 1
    assert not any(grown.values()), grown
    r = routes()
    assert r.get(("mesh", "ok"), 0) >= 1
    assert not [k for k in r if k[0] == "single_chip"], r
    # one placement a table: nothing held `local` on device 0 beside
    # its sharded or replicated copies
    specs = dom.copr._dev_store.placements()
    assert specs and not [u for u, by in specs.items()
                          if "local" in by and len(by) > 1], specs
    assert any("sharded" in by for by in specs.values())


@pytest.fixture
def runs_policy():
    """The chip's lowering policy on the CPU backend: under it the join
    statements return a partial a shard or a row block ("sort",
    "posruns") and not psum-able slots."""
    al._FORCE_SEGMENT_IMPL = "runs"
    try:
        yield
    finally:
        al._FORCE_SEGMENT_IMPL = None


@pytest.fixture
def three_blocks(one_device):
    copr = one_device.domain.copr
    old, copr.device_rows = copr.device_rows, 24_000
    try:
        yield one_device
    finally:
        copr.device_rows = old


def _merged(d, stmt, prepare=()):
    """The statement, checked against the reference -> the final merges
    it counted, by path."""
    c = d.client()
    try:
        for sql in prepare:
            c.query(sql)
        before = merges()
        got = c.query(d.ds.STATEMENTS[stmt])["rows"]
        grown = merges(before)
    finally:
        c.close()
    assert not d.ds.answer_wrong(got, d.ds.reference(d.tables, stmt))
    return grown


@pytest.mark.parametrize("stmt", ["q10", "q3", "q18"])
@pytest.mark.parametrize("where", ["mesh4", "three_blocks"])
def test_join_statements_merge_on_the_identifying_item(
        request, runs_policy, where, stmt):
    """c_custkey, l_orderkey, o_orderkey: four shards' or three row
    blocks' partials merged on one item of seven, three and five."""
    grown = _merged(request.getfixturevalue(where), stmt)
    # at this size no statement's groups reach the length at which
    # keys in order merge as runs. q18's IN subquery, unless its cached
    # result serves, is a final aggregation of its own, on its one item
    assert grown.pop("ident", 0) == 1, grown
    assert sum(grown.values()) <= (1 if stmt == "q18" else 0), grown


@pytest.mark.parametrize("where", ["mesh4", "three_blocks"])
def test_one_partial_counts_no_merge(request, runs_policy, where):
    """q5's 25 slots are summed on the mesh (psum) or arrive as dense
    slots a block: no identifying item is named, and on the mesh there
    is one partial."""
    grown = _merged(request.getfixturevalue(where), "q5")
    assert grown == ({} if where == "mesh4" else {"all_items": 1}), grown


def test_a_statement_on_the_fallback_merges_on_all_items(
        one_device, monkeypatch):
    """The host's partials (`_fallback_partials`) name no item. Its join
    emits one chunk, so one partial and nothing to count; two of them
    merge on every item."""
    from tidb_tpu.executor.executors import HashAggExec
    dom, seen = one_device.domain, []
    merge = HashAggExec._merge_partials
    monkeypatch.setattr(
        HashAggExec, "_merge_partials",
        lambda self, ps: seen.append((self, ps)) or merge(self, ps))
    monkeypatch.setattr(dom.copr, "use_device", False)
    assert _merged(one_device, "q10") == {}
    agg, partials = seen[-1]
    assert [p.ident for p in partials] == [None]
    before = merges()
    assert len(merge(agg, partials * 2)) == partials[0].ngroups
    assert merges(before) == {"all_items": 1}


def _q6(d, prepare=(), cleanup=()):
    """q6 on a connection of its own -> (rows, the routes it grew)."""
    c = d.client()
    try:
        for sql in prepare:
            c.query(sql)
        before = routes()
        rows = c.query(d.ds.STATEMENTS["q6"])["rows"]
        after = routes()
        for sql in cleanup:
            c.query(sql)
    finally:
        c.close()
    return rows, {k: v - before.get(k, 0) for k, v in after.items()
                  if v - before.get(k, 0)}


LINE = ("insert into lineitem values (7, 1, 1, {n}, 10.00, 1000.00, 0.06, "
        "0.02, 'N', 'O', '1994-03-01', '1994-03-01', '1994-03-02', "
        "'NONE', 'MAIL', 'mesh deployment')")


@pytest.mark.parametrize("reason,prepare,cleanup", [
    ("mpp_off", ["set tidb_enable_mpp = 0"], []),
    ("min_rows", ["set tidb_mpp_min_rows = 1000000000"], []),
    ("delta_overlay", ["begin", LINE.format(n=7)], ["rollback"]),
])
def test_each_reason_off_the_mesh_is_counted(mesh4, reason, prepare,
                                             cleanup):
    want = mesh4.ds.reference(mesh4.tables, "q6")
    rows, grown = _q6(mesh4, prepare, cleanup)
    assert grown == {("single_chip", reason): 1}, grown
    if reason != "delta_overlay":       # the overlay's row counts
        assert not mesh4.ds.answer_wrong(rows, want)


def test_a_degraded_mesh_run_is_counted_and_answers_single_chip(mesh4):
    want = mesh4.ds.reference(mesh4.tables, "q6")
    failpoint.enable("device_guard/fused/mpp", "error:compile")
    try:
        rows, grown = _q6(mesh4)
    finally:
        failpoint.disable("device_guard/fused/mpp")
    assert grown == {("single_chip", "degraded"): 1}, grown
    assert not mesh4.ds.answer_wrong(rows, want)
    assert mesh4.domain.metrics.get("device_fallback", 0) >= 1


@pytest.mark.parametrize("sql,route", [
    # a per-DAG aggregation over a small dense domain: psum on the mesh
    ("select g, sum(v) from mesh_t group by g order by g",
     ("mesh", "ok")),
    # one the mesh has no lowering for: sparse 64-bit group keys
    ("select k, sum(v) from mesh_t group by k order by 2 desc limit 2",
     ("single_chip", "ineligible_no_dense_layout")),
    # filter-only and top-n fragments run on one chip
    ("select id from mesh_t where v > 1990 order by id",
     ("single_chip", "ineligible_no_aggregation")),
    ("select id from mesh_t where v > 10 order by v desc limit 3",
     ("single_chip", "ineligible_no_aggregation")),
])
def test_per_dag_fragments_name_their_route(mesh4, sql, route):
    c = mesh4.client()
    try:
        c.query("create table if not exists mesh_t (id int primary key, "
                "k bigint, g int, v int)")
        if c.query("select count(*) from mesh_t")["rows"][0][0] == "0":
            c.query("insert into mesh_t values " + ",".join(
                f"({i},{i * 1000003},{i % 5},{i})" for i in range(1, 2001)))
        c.query("set tidb_tpu_fragment_min_rows = 0")
        before = routes()
        c.query(sql)
        grown = {k: v - before.get(k, 0) for k, v in routes().items()
                 if v - before.get(k, 0)}
    finally:
        c.close()
    assert grown == {route: 1}, grown


def test_one_device_counts_nothing(one_device):
    want = one_device.ds.reference(one_device.tables, "q6")
    rows, grown = _q6(one_device)
    assert grown == {} and routes() == {}
    assert not one_device.ds.answer_wrong(rows, want)
    rows, grown = _q6(one_device, ["set tidb_enable_mpp = 0"])
    assert grown == {}


# ---- tpch-sf3-mesh4: a shard larger than a row block (PR 34) -----------

@pytest.fixture
def shard_over_block(mesh4):
    """The relation scale 3 has on the chips (a shard of 5,242,880 lanes
    against a row block of 4,194,304): the 60,000-row lineitem buckets
    to 65,536 lanes, 16,384 a shard, and the executor's row block is
    set to half a shard."""
    copr = mesh4.domain.copr
    old, copr.device_rows = copr.device_rows, 8192
    try:
        yield mesh4
    finally:
        copr.device_rows = old


def _traced(d, lowerings, sql, prepare=()):
    """The statement with every span recorded -> (rows, the judged runs
    it grew by (site, kind, verdict), its spans' attributes by name)."""
    c = d.client()
    try:
        for q in prepare:
            c.query(q)
        c.query("set tidb_tpu_trace_sample_rate = 1")
        d.domain.tracer.recorder.clear()
        before = lowerings()
        rows = c.query(sql)["rows"]
        grown = lowerings(before)
        spans = {}
        for e in d.domain.tracer.recorder.events():
            spans.setdefault(e.name, []).append(
                dict(kv.split("=", 1) for kv in e.attrs.split(";") if kv))
    finally:
        c.close()
    return rows, grown, spans


MESH_KIND = {"q1": "dense", "q6": "dense", "q5": "posdense",
             "q3": "sort_runs", "q10": "sort_runs", "q18": "sort_runs"}


@pytest.mark.parametrize("stmt", ["q1", "q3", "q5", "q6", "q10", "q18"])
def test_a_shard_larger_than_a_row_block_runs_whole(
        shard_over_block, runs_policy, judged_runs, stmt):
    """The mesh program takes the shard whole, under the chip's
    lowering policy: the reference's answer, no degrade, one dispatch
    of a shard's lanes, and one `stands` a judged run once the sizes
    are learned."""
    d = shard_over_block
    dom = d.domain
    _traced(d, judged_runs, d.ds.STATEMENTS[stmt])    # learns the sizes
    before = dict(dom.metrics)
    rows, grown, spans = _traced(d, judged_runs, d.ds.STATEMENTS[stmt])
    assert not d.ds.answer_wrong(rows, d.ds.reference(d.tables, stmt))
    assert not any(dom.metrics.get(k, 0) - before.get(k, 0)
                   for k in DEGRADE)
    assert set(grown) == {("fused_mpp", MESH_KIND[stmt], "stands")}, grown
    route = spans["mpp_dispatch"]
    assert len(route) == sum(grown.values())
    for attrs in route:
        assert attrs["lanes"] == "16384"
        assert int(attrs["lanes"]) > dom.copr.device_rows
    judged = [a for a in spans["consume"] if "verdict" in a]
    assert [(a["lowering"], a["verdict"], a["retries"]) for a in judged] \
        == [(MESH_KIND[stmt], "stands", "0")] * len(route)


def _fresh_table(d):
    """2,000 rows whose `k` is sparse (no dense layout) and rises with
    the primary key (a run a row): the per-DAG engine's shape."""
    c = d.client()
    try:
        c.query("create table if not exists low_t (id int primary key, "
                "k bigint, g int, v int)")
        if c.query("select count(*) from low_t")["rows"][0][0] == "0":
            c.query("insert into low_t values " + ",".join(
                f"({i},{i * 1000003},{i % 5},{i})" for i in range(1, 2001)))
    finally:
        c.close()


# a statement a site; `{n}` makes the shape one no other test has taught
SITES = {
    "fused": ("one_device", "select l_suppkey, sum(l_linenumber + {n}) "
              "from lineitem group by l_suppkey"),
    "fused_mpp": ("mesh4", "select l_suppkey, sum(l_linenumber + {n}) "
                  "from lineitem group by l_suppkey"),
    "dag": ("one_device", "select k, sum(v + {n}) from low_t group by k"),
}


# the same shapes over a key with a dense layout (100 suppliers, ids
# 1..2000): past BCR_MAX slots, so not the runs policy's first choice
DENSE_KEY = {"l_suppkey * 1000003": "l_suppkey", " k,": " id,",
             "by k": "by id"}


@pytest.mark.parametrize("site", sorted(SITES))
@pytest.mark.parametrize("reason", ["grow_bucket", "pin_sorted",
                                    "pin_dense"])
def test_a_forced_retry_is_named_by_its_reason(
        request, runs_policy, monkeypatch, judged_runs, site, reason):
    """Keys that do not cluster give a run a row: more partials than
    the first bucket of GROUP_BUCKET_MIN (the bucket grows), and, with
    the floor of `runs_degraded` out of the way, more than half the
    rows (the shape is pinned to the sorted lowering). Each thrown-away
    run is counted under its reason, the run that stands under
    `stands`, on the site whose kernel ran. Where the keys span a dense
    layout (`pin_dense`) the host has counted the key changes before
    any program is built: the shape is pinned to the dense table at
    once, nothing is thrown away, and no argsort program exists."""
    where, sql = SITES[site]
    d = request.getfixturevalue(where)
    _fresh_table(d)
    # a sparse key: no dense layout to fall back on
    sql = sql.replace("l_suppkey", "l_suppkey * 1000003")
    if reason == "pin_dense":
        for sparse, dense in DENSE_KEY.items():
            sql = sql.replace(sparse, dense)
    if reason != "grow_bucket":
        monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 0)
    n = 3400 + sorted(SITES).index(site) * 3 + \
        ["grow_bucket", "pin_sorted", "pin_dense"].index(reason)
    prepare = ["set tidb_tpu_fragment_min_rows = 0"]
    rows, grown, spans = _traced(d, judged_runs, sql.format(n=n), prepare)
    assert len(rows) == (2000 if site == "dag" else 100)
    want = {(site, "sort_runs", "retry_grow_bucket"): 1,
            (site, "sort_runs", "stands"): 1}
    if reason == "pin_sorted":
        want = {(site, "sort_runs", "retry_pin_sorted"): 1,
                (site, "sort_sorted", "stands"): 1}
        if site == "dag":       # 2,000 groups against a bucket of 1,024
            want[(site, "sort_sorted", "retry_grow_bucket")] = 1
    if reason == "pin_dense":
        want = {(site, "dense", "stands"): 1}
    assert grown == want, grown
    # the open `consume` span carries the last verdict and the count
    last = [a for a in spans["consume"] if "verdict" in a][-1]
    assert last["verdict"] == "stands" and \
        last["retries"] == str(sum(grown.values()) - 1)
    # learned: the next run stands at once
    _rows, grown, _spans = _traced(d, judged_runs, sql.format(n=n), prepare)
    assert list(grown.values()) == [1] and \
        list(grown)[0][2] == "stands", grown


def test_the_per_dag_mesh_program_has_no_verdict(mesh4, judged_runs):
    """`_try_execute_mpp` is dense or not at all: no lowering judges
    it, so nothing is counted; its shard's lanes are on the route's
    span."""
    _fresh_table(mesh4)
    _rows, grown, spans = _traced(
        mesh4, judged_runs, "select g, sum(v + 3410) from low_t group by g",
        ["set tidb_tpu_fragment_min_rows = 0"])
    assert grown == {}, grown
    (route,) = spans["mpp_dispatch"]
    assert route["lanes"] == "512"                          # 2,048 / 4


def test_shard_lanes_is_the_bucket_split_evenly():
    from tidb_tpu.chunk.device import shape_bucket, shard_lanes
    # scale 3: 18,003,645 rows -> 20,971,520 lanes, 5,242,880 a shard
    assert shape_bucket(18_003_645) == 20_971_520
    assert shard_lanes(18_003_645, 4) == (20_971_520, 5_242_880)
    assert shard_lanes(6_001_215, 4) == (6_291_456, 1_572_864)
    assert shard_lanes(60_000, 4) == (65_536, 16_384)
    # a bucket that is no lane multiple is rounded up to one
    padded, local = shard_lanes(1000, 3)
    assert padded % (128 * 3) == 0 and local * 3 == padded >= 1000


# last: it changes the mesh deployment's lineitem, and with it what the
# references above were computed from

def test_an_acknowledged_insert_is_seen_by_the_next_q6_on_the_mesh(mesh4):
    """The configuration's isolation guarantee: a read sees every write
    acknowledged before it was sent, on the mesh too (the sharded
    buffers are tail-patched or re-keyed, never stale)."""
    base, _ = _q6(mesh4)
    w = mesh4.client()
    try:
        assert w.query(LINE.format(n=9))["affected"] == 1
    finally:
        w.close()
    rows, grown = _q6(mesh4)
    assert grown == {("mesh", "ok"): 1}, grown
    # 1000.00 x 0.06 inside q6's date, discount and quantity windows
    assert float(rows[0][0]) == pytest.approx(float(base[0][0]) + 60.0,
                                              abs=1e-6)
    assert rows != base
