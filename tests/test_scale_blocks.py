"""What scale adds, at a small size on the CPU (PR 27, `tpch-sf3-1chip`):

  * the six TPC-H statements over the wire against the benchmark's plain
    numpy reference (`benchmark/datasets/tpch.py`), with the row-block
    size forced down so that lineitem is 2 and 5 blocks with a ragged
    last one, on one chip under the TPU's "runs" policy, each statement
    on the lowering it gets on the chip (q1, q6 `dense`; q5 `posdense`;
    q3, q10, q18 `posruns`). Q18's HAVING set has 1 and 5 orders
    (`generate(shape_seed=)`): two more cases put `_BCR_MAX` between
    the two (3 for 64), which is where a position domain leaves the
    packed-slot lowerings. Q18's own domain is orders x customer, far
    past it at any scale, so it stays `posruns` on both sides; what the
    step moves is q5 (25 nations) and q1 (6 slots);
  * resident uploads are the pool's, not the first statement's: a first
    touch larger than `tidb_mem_quota_query` succeeds, a statement whose
    own buffers outgrow the quota still gets ER 8175;
  * the store's budget gauge and byte accounting after evictions;
  * `part` / `parts` on the `dispatch` and `consume` spans.
"""
import os
import sys

import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.pipeline as pl
import tidb_tpu.copr.probe as probe
from tidb_tpu.copr.residency import DeviceResidentStore
from tidb_tpu.errors import MemoryQuotaExceededError
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import metrics as metrics_util

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SCALE, SEED = 0.05, 2_700_000_041
# shape seed -> orders that pass Q18's HAVING at SF0.05
Q18_SET = {6: 1, 4: 5}
# case -> (lineitem row blocks, shape seed, _BCR_MAX)
CONFIGS = {
    "2blocks-set1": (2, 6, 64),
    "5blocks-set5": (5, 4, 64),
    "5blocks-set1-under-bcr3": (5, 6, 3),
    "2blocks-set5-over-bcr3": (2, 4, 3),
}
# statement -> the kind of its (last built) fused program by _BCR_MAX
KINDS = {
    64: {"q1": "dense", "q6": "dense", "q5": "posdense",
         "q3": "posruns", "q10": "posruns", "q18": "posruns"},
    3: {"q1": "sort", "q6": "dense", "q5": "posruns",
        "q3": "posruns", "q10": "posruns", "q18": "posruns"},
}


def _bench():
    """The benchmark's harness and wire client, by path (tests/ is not
    its package)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import run
    from wire import Wire
    return run, Wire


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def served(request, tmp_path_factory):
    parts, shape_seed, bcr_max = CONFIGS[request.param]
    run, Wire = _bench()
    ds = run.load_module("datasets", "tpch", "data set")
    tables = ds.generate(SCALE, SEED, shape_seed=shape_seed)
    n_li = len(tables["lineitem"]["l_orderkey"])
    mp = pytest.MonkeyPatch()
    mp.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    mp.setattr(al, "BCR_MAX", bcr_max)
    kinds = []
    build = pl._build_fused_kernel

    def spy(*a, **k):
        kinds.append(a[7])
        return build(*a, **k)
    mp.setattr(pl, "_build_fused_kernel", spy)
    system = run.System(str(tmp_path_factory.mktemp("blocks")))
    # `parts` blocks, the last one ragged; one chip, as the cell is
    # (the test session has eight virtual devices: what _get_mesh finds
    # with one is False)
    system.domain.copr.device_rows = int(n_li / (parts - 0.7))
    system.domain.copr._mesh = False
    wire = Wire(system.port)
    try:
        ds.load(tables, wire.query, system.bulk_table)
        yield {"ds": ds, "tables": tables, "wire": wire, "kinds": kinds,
               "parts": parts, "rows": n_li, "system": system,
               "q18_set": Q18_SET[shape_seed], "bcr_max": bcr_max}
    finally:
        wire.close()
        system.close()
        mp.undo()


@pytest.mark.parametrize("stmt", sorted(KINDS[64]))
def test_six_statements_equal_reference_over_row_blocks(served, stmt):
    ds, wire, copr = served["ds"], served["wire"], \
        served["system"].domain.copr
    step = copr.device_rows
    assert -(-served["rows"] // step) == served["parts"]
    assert served["rows"] % step            # a ragged last block
    want = ds.reference(served["tables"], stmt)
    if stmt == "q18":
        assert len(want) == served["q18_set"]
        assert (len(want) > served["bcr_max"]) == \
            (served["q18_set"] == 5 and served["bcr_max"] == 3)
    seen = len(served["kinds"])
    miss = metrics_util.DEV_BUFFER_POOL.labels("miss")
    for run_no in range(2):                 # first touch, then resident
        before = miss.value
        got = wire.rows(ds.STATEMENTS[stmt])
        assert not ds.answer_wrong(got, want), (stmt, run_no, got[:3])
        assert not wire.rows("show warnings")
    assert miss.value == before             # every column was resident
    built = served["kinds"][seen:]
    assert built and built[-1] == KINDS[served["bcr_max"]][stmt], built


# ---- who is charged for a resident buffer -----------------------------

@pytest.fixture
def ftk():
    tk = TestKit()
    tk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
    return tk


def _load(tk, n=40000):
    tk.must_exec("create table big (a bigint, b bigint)")
    tk.must_exec("insert into big values " + ",".join(
        f"({(i * 7919) % 10007}, {i})" for i in range(n)))


def test_first_touch_larger_than_quota_is_the_pools(ftk):
    """40k rows x 2 int64 columns pad to 640 KiB of resident
    buffers: five times the statement's quota. The statement that faults
    them in succeeds; the bytes are on the store, not on its tracker."""
    _load(ftk)
    store = ftk.domain.copr._dev_store
    held = store.bytes
    ftk.must_exec("set @@tidb_mem_quota_query = 131072")
    got = ftk.must_query("select sum(b) from big where a < 5000").rows
    assert int(got[0][0]) == sum(
        i for i in range(40000) if (i * 7919) % 10007 < 5000)
    assert store.bytes - held > 4 * 131072 > ftk.sess._stmt_mem_max
    assert ftk.domain.mem_root.consumed == 0
    assert not ftk.must_query("show warnings").rows


def test_own_buffers_past_quota_still_8175(ftk):
    """The same quota still ends a statement that itself outgrows it (a
    cross join's rows have nowhere to spill), resident columns or not."""
    _load(ftk, n=3000)
    ftk.must_query("select sum(b) from big")         # columns resident
    ftk.must_exec("set @@tidb_mem_quota_query = 262144")
    with pytest.raises(MemoryQuotaExceededError) as e:
        ftk.must_query("select count(distinct x.b + y.b) "
                       "from big x, big y")
    assert e.value.code == 8175
    assert ftk.domain.mem_root.consumed == 0
    st = ftk.domain.copr._dev_store.stats()
    assert st["bytes"] == sum(st["bytes_by_spec"].values()) > 0


def test_store_bytes_after_evictions():
    local = metrics_util.DEV_RESIDENT_BYTES.labels("local")
    lru = metrics_util.DEV_BUFFER_EVICTIONS.labels("lru")
    pressure = metrics_util.DEV_BUFFER_EVICTIONS.labels("pressure")
    l0, e0, p0 = local.value, lru.value, pressure.value
    store = DeviceResidentStore(1000)
    for i in range(5):                      # 5 x 300 B into 1000 B
        store.put(("t", i, 300), np.zeros(300, np.int8), 300, uid="t",
                  version=1)
    st = store.stats()
    assert st["budget"] == 1000 and st["entries"] == 3
    assert st["bytes"] == 900 == sum(st["bytes_by_spec"].values())
    assert st["bytes"] == sum(store._sizes.values()) <= st["budget"]
    assert st["max_bytes"] == 900
    assert lru.value == e0 + 2 and local.value == l0 + 900
    assert store.evict_bytes(400) == 600    # whole entries, LRU first
    st = store.stats()
    assert st["bytes"] == 300 == sum(store._sizes.values())
    assert st["max_bytes"] == 900 and pressure.value == p0 + 2
    assert local.value == l0 + 300
    assert store.evict_bytes(1) == 300 and local.value == l0


def test_budget_and_pool_served_over_sql(ftk):
    _load(ftk, n=5000)
    ftk.must_query("select sum(b) from big")
    rows = ftk.must_query(
        "select sum_value from information_schema.metrics_summary where "
        "metrics_name = 'tidb_tpu_device_resident_budget_bytes'").rows
    store = ftk.domain.copr._dev_store
    assert rows and float(rows[0][0]) == store.budget == 8 << 30
    pool = [r for r in ftk.must_query(
        "select scope, consumed, max_consumed, quota, oom_action from "
        "information_schema.memory_usage").rows if r[0] == "device_pool"]
    assert pool == [("device_pool", store.bytes, store.max_bytes,
                     store.budget, "evict")]
    assert store.bytes > 0


# ---- part / parts on the spans -----------------------------------------

def _spans(tk, sql):
    tk.must_exec("set @@tidb_tpu_trace_sample_rate = 1")
    rec = tk.domain.flight_recorder
    rec.clear()
    tk.must_query(sql)
    tk.must_exec("set @@tidb_tpu_trace_sample_rate = 0")
    return [e for e in rec.events() if e.name in ("dispatch", "consume")]


@pytest.mark.parametrize("sql", [
    "select a % 7, sum(b) from big group by a % 7",     # copr/agg
    "select b from big where a = 11",                   # copr/filter
    "select sum(x.b) from big x, dim d where x.a = d.id "
    "group by d.g",                                     # fused pipeline
], ids=["agg", "filter", "fused"])
def test_part_and_parts_on_dispatch_and_consume(ftk, sql):
    _load(ftk, n=10000)
    ftk.must_exec("create table dim (id bigint primary key, g int)")
    ftk.must_exec("insert into dim values " + ",".join(
        f"({i}, {i % 5})" for i in range(0, 10007, 3)))
    ftk.domain.copr.device_rows = 3000      # 3 full blocks and 1,000
    host = ftk.must_query(sql).rows
    evs = _spans(ftk, sql)
    for name in ("dispatch", "consume"):
        got = sorted(e.attrs for e in evs if e.name == name)
        assert got, (name, evs)
        assert all("parts=4" in a for a in got), got
        assert {p for a in got for p in a.split(';')
                if p.startswith("part=")} == \
            {f"part={i}" for i in range(4)}, got
    assert ftk.must_query(sql).rows == host


# ---- sizes that pick kernels, past their SF1 steps ---------------------

def _dim_modes(tk):
    return sorted(v.form for v in tk.domain.copr._host_cache.values()
                  if isinstance(v, probe.ProbeTable))


@pytest.mark.parametrize("budget, mode", [(8 << 30, "direct"),
                                          (512 << 10, "sorted")],
                         ids=["lut-fits", "lut-past-an-eighth"])
def test_direct_lut_is_bounded_by_the_store_not_by_a_constant(
        ftk, budget, mode):
    """Sparse keys as TPC-H's orders have them (8 of every 32): 5,000
    keys span 20,000 slots, a 160 KB lut. It is a direct lut while that
    fits an eighth of the store's budget (at 8 GiB: 128 Mi slots, so
    scale 3's 18,000,000 too, which the old 16 Mi constant refused), and
    a binary search when it does not; the answer is the same."""
    ftk.domain.copr._dev_store.budget = budget
    ftk.must_exec("create table o (k bigint primary key, c int)")
    keys = [(i // 8) * 32 + i % 8 + 1 for i in range(5000)]
    ftk.must_exec("insert into o values " + ",".join(
        f"({k}, {k % 11})" for k in keys))
    ftk.must_exec("create table l (id bigint primary key, k bigint, "
                  "q bigint)")
    ftk.must_exec("insert into l values " + ",".join(
        f"({i}, {keys[i // 4]}, {i % 50})" for i in range(20000)))
    sql = ("select o.c, sum(l.q), count(*) from l, o where l.k = o.k "
           "group by o.c order by o.c")
    got = ftk.must_query(sql).rows
    want = {}
    for i in range(20000):
        c = keys[i // 4] % 11
        s, n = want.get(c, (0, 0))
        want[c] = (s + i % 50, n + 1)
    assert [(int(r[0]), int(r[1]), int(r[2])) for r in got] == \
        [(c,) + want[c] for c in sorted(want)]
    assert _dim_modes(ftk) == [mode]
    assert probe.direct_span(ftk.domain.copr, 18_000_000, 4_500_000) == \
        (budget == 8 << 30)


@pytest.mark.parametrize("run_len, pinned", [(4, False), (2, False),
                                             (1, True)])
def test_runs_of_two_or_more_keep_the_runs_lowering(monkeypatch, run_len,
                                                    pinned):
    """A key the storage clusters in runs of four (TPC-H's lines an
    order) or of two is not degraded: only ~a run a row pins the sort."""
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    tk = TestKit()
    tk.must_exec("set @@tidb_tpu_fragment_min_rows = 0")
    tk.must_exec("create table t (k bigint, v int)")
    n = 1200
    # wide key span: not dense-eligible, so the general runs path runs
    ks = [(i // run_len) * (1 << 30) + 7 for i in range(n)]
    tk.must_exec("insert into t values " + ",".join(
        f"({k}, {i})" for i, k in enumerate(ks)))
    got = tk.must_query("select k, count(*), sum(v) from t group by k "
                        "order by k").rows
    assert len(got) == n // run_len
    assert all(int(r[1]) == run_len for r in got)
    assert sum(int(r[2]) for r in got) == n * (n - 1) // 2
    pins = [v for key, v in tk.domain.copr._host_cache.items()
            if key and key[0] == "aggimpl"]
    assert ("sorted" in pins) == pinned
    assert al.runs_degraded(1_048_600, 4_194_304) is False   # q18's
    assert al.runs_degraded(4_100_000, 4_194_304) is True
