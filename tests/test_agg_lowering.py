"""copr/agg_lowering.py: the decision "which aggregation lowering for
which shape" as a table, and the learn-and-retry verdicts, called
directly — no kernel is built here. Then the one thing both engines
share through it: a shape's pin.

Counts and kinds only (CPU backend), never device times."""
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.pipeline as pl
from tidb_tpu.chunk.device import shape_bucket
from tidb_tpu.expression.expr import AggDesc, Column
from tidb_tpu.testkit import TestKit
from tidb_tpu.types.field_type import new_bigint_type


class _Copr:
    def __init__(self):
        self._host_cache = {}


class _Tbl:
    uid, gc_epoch = "t1", 0


class _Item:
    def __init__(self, fp):
        self.fp = fp

    def fingerprint(self):
        return self.fp


def _state(copr=None, tbl=_Tbl, groups=("g",), aggs=("sum[v]",)):
    return al.ShapeState(copr or _Copr(), tbl,
                         [_Item(g) for g in groups],
                         [_Item(a) for a in aggs])


def _pos(nslots, dims=(0,)):
    """A position grouping over `dims` with `nslots` slots."""
    return ([("dimcol", d, 1) for d in dims], list(dims), nslots)


def _dense(nslots):
    return [(nslots, 0)]


@pytest.fixture
def runs(monkeypatch):
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")


CAP = 1 << 22
# case -> (pos_spec, sizes, site, dims, kind, agg_param[1] or None)
SHAPES = {
    # the benchmark's six statements under the chip's policy
    "q6_global": (None, [], "fused", False, "dense", None),
    "q1_12_slots": (None, [(4, 0), (3, 0)], "fused", False, "dense", None),
    "q5_25_nations": (_pos(25), None, "fused", True, "posdense", None),
    "q3_orders": (_pos(1_500_000), None, "fused", True, "posruns", (0,)),
    "q10_customer": (_pos(150_000, (1,)), None, "fused", True, "posruns",
                     (1,)),
    "q18_orders": (_pos(1_500_000), None, "fused", True, "posruns", (0,)),
    "q18_subquery": (None, None, "fused", False, "sort", "runs"),
    # BCR_MAX, both domains, both sides
    "dense_64": (None, _dense(64), "fused", False, "dense", None),
    "dense_65": (None, _dense(65), "fused", False, "sort", "runs"),
    "pos_64": (_pos(64), None, "fused", True, "posdense", None),
    "pos_65": (_pos(65), None, "fused", True, "posruns", (0,)),
    # few dict codes beat runs over scattered positions
    "pos_65_dense_12": (_pos(65), _dense(12), "fused", True, "dense", None),
    # the mesh: no posruns
    "mesh_pos_65": (_pos(65), None, "fused_mpp", True, "sort", "runs"),
    "mesh_pos_25": (_pos(25), None, "fused_mpp", True, "posdense", None),
    # the per-DAG executor: dense or sort
    "dag_dense_12": (None, _dense(12), "dag", False, "dense", None),
    "dag_dense_65": (None, _dense(65), "dag", False, "sort", "runs"),
}


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_choose_under_the_runs_policy(runs, case):
    pos, sizes, site, dims, kind, second = SHAPES[case]
    low = al.Lowering(_state(), pos, sizes, site=site, dims=dims)
    got, param, ecap = low.choose(CAP)
    assert got == kind and ecap is None
    assert sum(x is not None for x in (low.pos, low.posruns, low.sizes)) \
        <= 1
    if kind == "posdense":
        assert param == (tuple(pos[1]), pos[2])
    elif kind == "dense":
        assert param == tuple(low.sizes)
    else:
        assert param == (al.GROUP_BUCKET_MIN, second, None, None)


def test_sizes_are_asked_for_only_without_a_position_domain(runs):
    asked = []

    def sizes():
        asked.append(1)
        return _dense(12)
    assert al.Lowering(_state(), _pos(25), sizes).choose(CAP)[0] == \
        "posdense"
    assert not asked
    assert al.Lowering(_state(), _pos(65), sizes).choose(CAP)[0] == "dense"
    assert asked == [1]


# case -> (pos_spec, sizes, kind, agg_param[1])
CPU_DEFAULT = {
    "dense_65": (None, _dense(65), "dense", None),
    "pos_65": (_pos(65), None, "posdense", None),
    "pos_over_pos_dense_max": (_pos(al.POS_DENSE_MAX + 1), None, "sort",
                               "scatter"),
    "general": (None, None, "sort", "scatter"),
}


@pytest.mark.parametrize("case", sorted(CPU_DEFAULT))
def test_choose_under_the_cpu_default(case):
    assert al.policy() == "scatter"
    pos, sizes, kind, impl = CPU_DEFAULT[case]
    got, param, _ = al.Lowering(_state(), pos, sizes, dims=True).choose(CAP)
    assert got == kind
    if kind == "sort":
        assert param[1] == impl


def test_delta_outside_the_dense_span_takes_the_sort_lowering(runs):
    """The fused pipeline's dense sizes, as it asks for them: a
    transaction's row whose key lies outside the span drops the layout
    (pipeline._delta_in_span), and the choice is the exact sort kind."""
    g = Column(0, new_bigint_type())
    shim = pl._AggShim([g], [])
    sizes = [(12, 0)]               # values 0..10

    def delta(v):
        return {0: (np.array([v], dtype=np.int64), None, None)}, \
            np.ones(1, dtype=bool)
    for v, kind in ((10, "dense"), (11, "sort")):
        low = al.Lowering(
            _state(), None,
            lambda: sizes if pl._delta_in_span(shim, sizes, delta(v))
            else None)
        assert low.choose(CAP)[0] == kind


def test_a_learned_onehot_table_against_posruns(runs):
    """A learned table (it can only date from a pinned spell) keeps the
    one-hot kind on one chip; the mesh and the per-DAG executor do not
    see it; past ONEHOT_CAP_MAX lanes the sort kind, not posruns."""
    st = _state()
    st.onehot = {"scap": 128, "spans": [26, 8]}
    assert al.Lowering(st, _pos(65), dims=True).choose(CAP) == \
        ("onehot", (128, "search", "mxu"), None)
    assert al.Lowering(st, _pos(65), dims=True).choose(
        al.ONEHOT_CAP_MAX * 2)[0] == "sort"
    assert al.Lowering(st, _pos(65), site="fused_mpp").choose(CAP)[0] == "sort"
    assert al.Lowering(st, None, site="dag").choose(CAP)[0] == "sort"
    st.onehot = False               # the tombstone is no table
    assert al.Lowering(st, _pos(65), dims=True).choose(CAP)[0] == "posruns"


def test_a_pinned_shape(runs):
    st = _state()
    st.pin = "sorted"
    low = al.Lowering(st, _pos(65), dims=True, topn=("agg", 0, True, 10))
    assert low.choose(CAP) == \
        ("sort", (al.GROUP_BUCKET_MIN, "sorted", None, None), None)


@pytest.mark.parametrize("k, bucket, want", [
    (10, 1024, 76),         # k + 66 candidates
    (1000, 1024, 1024),     # capped at the bucket
    (1022, 1024, 1024),     # bucket == k + 2: the proof can still pass
    (1023, 1024, None),     # it cannot: no candidate kernel
])
def test_topn_candidate_width(runs, k, bucket, want):
    st = _state()
    st.grow_bucket(bucket)
    low = al.Lowering(st, _pos(65), dims=True, topn=("agg", 0, True, k))
    topn = low.choose(CAP)[1][2]
    assert topn == (want and ("agg", 0, True, want))
    st.topn_off = True
    assert low.choose(CAP)[1][2] is None
    assert al.Lowering(st, _pos(65), site="fused_mpp",
                       topn=("agg", 0, True, k)).topn is None


def test_compaction_capacities(runs):
    st = _state()
    st.compact, st.early_compact = 4096, 8192
    joined = al.Lowering(st, _pos(65), dims=True)
    # early set: the late stage would re-gather the same buffer
    assert joined.choose(CAP) == \
        ("posruns", (al.GROUP_BUCKET_MIN, (0,), None, None), 8192)
    # an early buffer no smaller than the partition is none
    assert joined.choose(8192) == \
        ("posruns", (al.GROUP_BUCKET_MIN, (0,), None, 4096), None)
    # zero-dim plan: no early compaction
    assert al.Lowering(st, None).choose(CAP) == \
        ("sort", (al.GROUP_BUCKET_MIN, "runs", None, 4096), None)
    # the mesh keeps the late buffer only, the per-DAG executor neither
    assert al.Lowering(st, None, site="fused_mpp", dims=True).choose(CAP) == \
        ("sort", (al.GROUP_BUCKET_MIN, "runs", None, 4096), None)
    assert al.Lowering(st, None, site="dag").choose(CAP) == \
        ("sort", (al.GROUP_BUCKET_MIN, "runs", None, None), None)
    st.compact = st.early_compact = "off"
    assert joined.choose(CAP)[1:] == \
        ((al.GROUP_BUCKET_MIN, (0,), None, None), None)
    # dense kinds carry the early capacity too
    st.early_compact = 8192
    assert al.Lowering(st, _pos(25), dims=True).choose(CAP)[2] == 8192


ROWS = 4_194_304
SORT = ("sort", (1024, "runs", None, None))
# case -> (kind, param, ecap, rows, ngroups, nvalid, fnvalid,
#          verdict, what the state holds afterwards)
VERDICTS = {
    "degraded_over_half": (
        *SORT, None, ROWS, ROWS // 2 + 1, None, None, "retry",
        {"pin": "sorted"}),
    "half_is_not_degraded": (
        "sort", (ROWS, "runs", None, None), None, ROWS, ROWS // 2, None,
        None, None, {"pin": None}),
    "q18_subquery_runs_of_four": (
        "sort", (ROWS // 2, "runs", None, None), None, ROWS, 1_048_366,
        None, None, None, {"pin": None}),
    "small_partition_under_the_floor": (
        "sort", (65536, "runs", None, None), None, 1000, 1000, None, None,
        None, {"pin": None}),
    "posruns_degrades_too": (
        "posruns", (1024, (0,), None, None), None, ROWS, ROWS - 1, None,
        None, "retry", {"pin": "sorted"}),
    "sorted_never_degrades": (
        "sort", (ROWS, "sorted", None, None), None, ROWS, ROWS - 1, None,
        None, None, {"pin": None}),
    "bucket_overflow": (
        *SORT, None, ROWS, 5000, None, None, "retry",
        {"bucket": shape_bucket(5000), "pin": None}),
    "bucket_fits": (
        *SORT, None, ROWS, 1024, None, None, None, {"bucket": 1024}),
    "late_compaction_learns": (
        *SORT, None, ROWS, 10, 3000, None, None,
        {"compact": shape_bucket(3000)}),
    "late_compaction_first_sight_off": (
        *SORT, None, ROWS, 10, ROWS // 8 + 1, None, None,
        {"compact": "off"}),
    "late_compaction_regrows": (
        "sort", (1024, "runs", None, 4096), None, ROWS, 10, 5000, None,
        "retry", {"compact": shape_bucket(5000)}),
    "late_compaction_drifts_off": (
        "sort", (1024, "runs", None, 4096), None, ROWS, 10, ROWS // 4 + 1,
        None, "retry", {"compact": "off"}),
    "early_compaction_learns": (
        "dense", ((12, 0),), None, ROWS, None, None, 3000, None,
        {"early_compact": shape_bucket(3000)}),
    "early_compaction_regrows": (
        "dense", ((12, 0),), 4096, ROWS, None, None, 5000, "retry",
        {"early_compact": shape_bucket(5000)}),
    # every verdict at once: the early one is taken, nothing else learned
    "order_early_first": (
        "sort", (1024, "runs", None, 4096), 4096, ROWS, ROWS - 1, 5000,
        5000, "retry",
        {"early_compact": shape_bucket(5000), "compact": None, "pin": None,
         "bucket": 1024}),
    "order_late_before_degrade": (
        "sort", (1024, "runs", None, 4096), None, ROWS, ROWS - 1, 5000,
        None, "retry",
        {"compact": shape_bucket(5000), "pin": None, "bucket": 1024}),
    "order_degrade_before_bucket": (
        *SORT, None, ROWS, ROWS - 1, None, None, "retry",
        {"pin": "sorted", "bucket": 1024}),
}


@pytest.mark.parametrize("case", sorted(VERDICTS))
def test_observe(runs, case):
    kind, param, ecap, rows, ngroups, nvalid, fnvalid, verdict, after = \
        VERDICTS[case]
    st = _state()
    low = al.Lowering(st, None, dims=True)
    assert low.observe(kind, param, ecap, ROWS, rows, ngroups, nvalid,
                       fnvalid) == verdict
    for name, value in after.items():
        assert getattr(st, name) == value, name


@pytest.mark.parametrize("why", [None, "onehot_miss", "topn_unproven"])
def test_a_held_run_is_counted_once_by_its_consumer(runs, judged_runs, why):
    """A run that stands by its sizes but whose consumer can still throw
    it away (`hold`) is counted by `settle`, once, under its final
    verdict; a retry `observe` sees itself is counted there."""
    low = al.Lowering(_state(), None, dims=True)
    kind, param = "posruns", (1024, (0,), ("agg", 0, True, 7), None)
    assert low.observe(kind, param, None, ROWS, ROWS, 10, hold=True) is None
    assert judged_runs() == {}
    low.settle(kind, param, why)
    verdict = "retry_" + why if why else "stands"
    assert judged_runs() == {("fused", "posruns", verdict): 1}
    assert low.observe(kind, param, None, ROWS, ROWS, 5000,
                       hold=True) == "retry"
    assert judged_runs().get(("fused", "posruns", "retry_grow_bucket")) == 1


# table -> (slot_by, reducer): the packed code is the slot where every
# code of the spans is under `scap`, whatever the reducer; a coded table
# of at most ONEHOT_CMP_MAX slots is reduced by compare
ONEHOT_FORMS = {
    "q9_26x8_under_256": ({"scap": 256, "spans": [26, 8]}, "code", "cmp"),
    "spans_fill_scap": ({"scap": 256, "spans": [32, 8]}, "code", "cmp"),
    "spans_past_scap": ({"scap": 128, "spans": [26, 8]}, "search", "mxu"),
    "sparse_keys": ({"scap": 256, "spans": [38105, 6]}, "search", "mxu"),
    "wide_spans_do_not_overflow": (
        {"scap": 256, "spans": [1 << 40, 1 << 20]}, "search", "mxu"),
    "one_key_column": ({"scap": 128, "spans": [100]}, "code", "cmp"),
    "at_the_crossover": ({"scap": None, "spans": [4]}, "code", "cmp"),
    "coded_past_the_crossover": ({"scap": None, "spans": [4, 8]},
                                 "code", "mxu"),
    "searched_past_the_crossover": ({"scap": None, "spans": [1 << 20]},
                                    "search", "mxu"),
}


@pytest.mark.parametrize("case", sorted(ONEHOT_FORMS))
def test_onehot_form_follows_the_table(case):
    table, slot_by, reducer = ONEHOT_FORMS[case]
    if table["scap"] is None:
        table = dict(table, scap=al.ONEHOT_CMP_MAX * (
            1 if case.startswith("at_") else 2))
    assert al.onehot_form(table) == (slot_by, reducer)
    st = _state()
    st.onehot = table
    assert al.Lowering(st, None, dims=True).choose(CAP)[:2] == \
        ("onehot", (table["scap"], slot_by, reducer))


@pytest.mark.parametrize("form, kind", [
    (("search", "mxu"), "onehot"), (("code", "mxu"), "onehot"),
    (("code", "cmp"), "onehot_cmp")], ids="_".join)
@pytest.mark.parametrize("why", [None, "onehot_miss"])
def test_judged_names_the_reducer_of_a_onehot_run(runs, judged_runs, form,
                                                  kind, why):
    """The matmul keeps the label every earlier reading has, wherever
    its slot came from; a run reduced by compare, which searches
    nothing, counts `onehot_cmp`."""
    low = al.Lowering(_state(), None, dims=True)
    param = (256, *form)
    assert low.observe("onehot", param, None, ROWS, ROWS, hold=True) is None
    assert judged_runs() == {}
    low.settle("onehot", param, why)
    assert judged_runs() == {
        ("fused", kind, "retry_" + why if why else "stands"): 1}


def test_state_is_one_per_shape_and_epoch():
    copr = _Copr()
    a, b = _state(copr), _state(copr)
    a.pin, a.topn_off = "sorted", True
    a.grow_bucket(5000)
    assert (b.pin, b.topn_off, b.bucket) == \
        ("sorted", True, shape_bucket(5000))
    assert _state(copr, groups=("h",)).pin is None
    assert _state(copr, aggs=("count[]",)).bucket == al.GROUP_BUCKET_MIN

    class Compacted(_Tbl):
        gc_epoch = 1
    c = _state(copr, Compacted)
    # a compaction lifts the pins; the bucket is the data's, not the order's
    assert (c.pin, c.topn_off, c.bucket) == (None, None, shape_bucket(5000))
    del a.pin
    assert b.pin is None
    # the keys tests and tools read by prefix
    assert {k[0] for k in copr._host_cache} == {"gb", "ftopn_off"}


def test_thresholds_are_constants_not_switches(monkeypatch):
    import inspect
    src = inspect.getsource(al)
    assert "os.environ" not in src and "getenv" not in src and \
        "import os" not in src
    assert (al.BCR_MAX, al.RUNS_DEGRADE_MIN, al.ONEHOT_MAX,
            al.POS_DENSE_MAX, al.DENSE_MAX) == \
        (64, 65536, 32768, 1 << 22, 1 << 18)
    assert 256 <= al.ONEHOT_CMP_MAX <= al.ONEHOT_MAX
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "hash")
    with pytest.raises(ValueError):
        al.policy()


def test_onehot_is_for_accelerators_and_does_not_follow_the_policy(
        runs, monkeypatch):
    one = {0: (np.zeros(1, dtype=np.int64), None, None)}

    class Sum:
        name, args = "sum", [Column(0, new_bigint_type())]
    low = al.Lowering(_state(), None)
    assert low.onehot_learnable([Sum], [Sum], one, None) is False
    monkeypatch.setattr(al, "_FORCE_ONEHOT", True)
    assert low.onehot_learnable([Sum], [Sum], one, None) is True
    assert low.onehot_learnable([Sum], [Sum], one, [(1, [])]) is False
    assert al.Lowering(_state(), None, site="fused_mpp").onehot_learnable(
        [Sum], [Sum], one, None) is False
    assert al.Lowering(_state(), _pos(65), dims=True).onehot_learnable(
        [Sum], [Sum], one, None) is False
    assert al.onehot_fits(al.ONEHOT_MAX) and not al.onehot_fits(0) and \
        not al.onehot_fits(al.ONEHOT_MAX + 1)


# ---- one key for the impl pin, both engines ---------------------------

def test_a_pin_learned_per_dag_holds_for_the_fused_pipeline(monkeypatch):
    """Keys uncorrelated with storage order: the per-DAG executor (the
    table is under the planner's 4,096 rows) pins the shape to "sorted";
    grown past 4,096 rows the same statement is the zero-dim fused
    pipeline's, which starts from the pin and never builds the runs
    kernel; a compaction (gc_epoch) frees the shape to try runs again."""
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    built = []
    orig = pl._build_fused_kernel

    def spy(*a, **k):
        built.append((a[7], a[8][1]))
        return orig(*a, **k)
    monkeypatch.setattr(pl, "_build_fused_kernel", spy)
    tk = TestKit()
    tk.must_exec("create table t (id int primary key, g bigint, v int)")
    rng = np.random.RandomState(5)

    def insert(lo, hi):
        tk.must_exec("insert into t values " + ",".join(
            f"({i}, {int(rng.randint(0, 1 << 40))}, {i % 7})"
            for i in range(lo, hi)))
    sql = "select g, count(*), sum(v) from t group by g"
    insert(0, 2000)
    assert len(tk.must_query(sql).rows) == 2000
    copr = tk.domain.copr
    pins = [v for k, v in copr._host_cache.items() if k[0] == "aggimpl"]
    assert pins == ["sorted"] and not built      # learned per DAG
    insert(2000, 6000)
    tk.must_exec("analyze table t")
    sql += " order by g"        # a new text: the cached plan is per-DAG
    assert len(tk.must_query(sql).rows) == 6000
    # held by the fused pipeline: no runs kernel (the second build is
    # the bucket grown to 6,000 groups)
    assert built and set(built) == {("sort", "sorted")}
    tbl = copr.engine.table(
        tk.domain.infoschema().table_by_name("test", "t"))
    tbl.gc_epoch += 1
    copr._kernel_cache.clear()
    del built[:]
    assert len(tk.must_query(sql).rows) == 6000
    assert built[0] == ("sort", "runs") and built[-1] == ("sort", "sorted")


# ---- a shape with a dense layout and keys that do not cluster ----------

def test_a_dense_domain_past_bcr_is_kept_for_the_day_runs_degrade(runs):
    """200,001 part keys: no shape's first choice under the runs policy
    (a scatter on the chip), so the runs lowering, with the layout kept;
    a degraded run pins the shape to it and not to the argsort
    program."""
    st = _state()
    low = al.Lowering(st, None, _dense(200_001))
    assert low.sizes is None and low.dense_alt == _dense(200_001)
    kind, param, _ = low.choose(CAP)
    assert (kind, param[1]) == ("sort", "runs")
    assert low.observe(kind, param, None, CAP, CAP, CAP - 1, CAP,
                       None) == "retry"
    assert st.pin == "dense"
    assert low.choose(CAP) == ("dense", tuple(_dense(200_001)), None)
    assert low.sizes == _dense(200_001)     # what the consumer decodes by
    # the same shape seen by a statement that knows the layout
    assert al.Lowering(st, None, _dense(200_001),
                       site="dag").choose(CAP)[0] == "dense"
    # and by one that does not: the policy's lowering, then "sorted"
    other = al.Lowering(st, None, None)
    kind, param, _ = other.choose(CAP)
    assert (kind, param[1]) == ("sort", "runs")
    assert other.observe(kind, param, None, CAP, CAP, CAP - 1, CAP,
                         None) == "retry"
    assert st.pin == "sorted"


def test_the_degraded_verdict_names_the_pin(runs, judged_runs):
    low = al.Lowering(_state(), None, _dense(70_000), site="dag")
    kind, param, _ = low.choose(CAP)
    low.observe(kind, param, None, CAP, CAP, CAP - 1)
    assert judged_runs() == {("dag", "sort_runs", "retry_pin_dense"): 1}


@pytest.mark.parametrize("counted, pin, kind", [
    (True, "dense", "dense"), (False, None, "sort")])
def test_keys_the_host_counted_unclustered_build_no_runs_program(
        runs, counted, pin, kind):
    asked = []
    st = _state()
    low = al.Lowering(st, None, _dense(200_001),
                      unclustered=lambda: asked.append(1) or counted)
    assert asked == [1] and st.pin == pin
    assert low.choose(CAP)[0] == kind
    # asked once a shape: a pin answers from then on
    al.Lowering(st, None, _dense(200_001),
                unclustered=lambda: asked.append(1) or counted)
    assert len(asked) == (1 if counted else 2)


def test_the_host_is_not_asked_where_dense_is_the_first_choice(runs):
    asked = []
    for sizes in (_dense(64), None):
        al.Lowering(_state(), None, sizes,
                    unclustered=lambda: asked.append(1) or True)
    assert asked == []


def test_the_cpu_default_never_keeps_a_layout_aside():
    asked = []
    low = al.Lowering(_state(), None, _dense(200_001),
                      unclustered=lambda: asked.append(1) or True)
    assert low.sizes == _dense(200_001) and low.dense_alt is None
    assert asked == [] and low.choose(CAP)[0] == "dense"


def test_dense_form_under_the_runs_policy(runs):
    assert [al.dense_form(n) for n in (1, 64, 65, al.DENSE_MAX,
                                       al.DENSE_MAX + 1)] == \
        ["reduce", "bcr", "scatter", "scatter", None]
    assert [al.dense_first(n) for n in (1, 64, 65, al.DENSE_MAX + 1)] == \
        [True, True, False, False]


class _Dag:
    def __init__(self, *items):
        self.group_items = list(items)


@pytest.mark.parametrize("keys, want", [
    ("shuffled", True), ("ordered_runs_of_four", False), ("unique", True)])
def test_host_unclustered_counts_key_changes(monkeypatch, keys, want):
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 1024)
    n = 1 << 14
    data = {"shuffled": np.random.default_rng(7).integers(0, 1000, n),
            "ordered_runs_of_four": np.arange(n) // 4,
            "unique": np.arange(n)}[keys].astype(np.int64)
    g = Column(0, new_bigint_type(), "k")
    assert al.host_unclustered(_Dag(g), {0: (data, None, None)}, n) is want


def test_host_unclustered_leaves_what_it_cannot_count_to_the_device():
    n = 1 << 14
    g = Column(0, new_bigint_type(), "k")
    floats = np.random.default_rng(7).random(n)
    assert not al.host_unclustered(_Dag(g), {0: (floats, None, None)}, n)
    assert not al.host_unclustered(_Dag(), {}, n)


# ---- inverting a prefix count (PR 44) ---------------------------------
# `prefix_select` is held, position for position, to what it replaced:
# `jnp.searchsorted` over the int64 prefix count.

def _searched(flags, probes):
    import jax.numpy as jnp
    cs = jnp.cumsum(jnp.asarray(flags).astype(jnp.int64))
    return np.asarray(jnp.searchsorted(cs, jnp.asarray(probes)))


# 28,672 and 1,835,008 are 7 x 2^k (a row block's tail), 40,960 is
# 5 x 2^k (a mesh shard at scale 3): levels `SELECT_ROW` does not divide
@pytest.mark.parametrize("share", [0.0, 0.015, 1.0])
@pytest.mark.parametrize("cap", [1, 7, 128, 1000, 16384, 28672, 40960,
                                 1835008])
def test_prefix_select_equals_the_search(cap, share):
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(cap + int(share * 1000))
    flags = rng.random(cap) < share
    count = int(flags.sum())
    sel = jax.jit(lambda f, p: al.prefix_select(f, p))
    jflags = jnp.asarray(flags)
    # probes under, at and past the count
    for k in sorted({max(count // 2, 1), max(count, 1),
                     min(count + 1000, cap + 7)}):
        probes = np.arange(1, k + 1)
        pos, n = sel(jflags, jnp.asarray(probes))
        assert pos.dtype == np.int32 and n.dtype == np.int64
        assert int(n) == count
        want = _searched(flags, probes)
        assert np.array_equal(np.asarray(pos), want), (cap, share, k)
        assert k <= count or int(np.asarray(pos)[-1]) == cap
    # sorted probes that are no arange, as a run's end asks: repeats,
    # zero, negatives, past the count
    probes = np.sort(rng.integers(-2, count + 3, min(cap, 4096) + 5))
    pos, _n = sel(jflags, jnp.asarray(probes))
    assert np.array_equal(np.asarray(pos), _searched(flags, probes))


@pytest.mark.parametrize("row, top", [(8, 16), (16, 512), (128, 2048)])
def test_prefix_search_levels(row, top):
    """Other rows and tops than the module's: more and fewer levels
    over the same count give the same positions."""
    import jax.numpy as jnp
    rng = np.random.default_rng(row)
    flags = rng.random(5 * 2 ** 14 + 3) < 0.02
    probes = np.arange(1, 3000)
    got = al.prefix_search(al.prefix_count(jnp.asarray(flags)),
                           jnp.asarray(probes), row=row, top=top)
    assert np.array_equal(np.asarray(got), _searched(flags, probes))


def _runs_case(case, cap=4096):
    rng = np.random.default_rng(len(case))
    if case == "clustered":
        keys = np.sort(rng.integers(0, cap // 6, cap))
        mask = rng.random(cap) < 0.7
    elif case == "unclustered":
        keys = rng.integers(0, 50, cap)
        mask = rng.random(cap) < 0.7
    elif case == "masked_runs":
        # every third run has no visible row, the last run among them
        keys = np.sort(rng.integers(0, cap // 6, cap))
        mask = (keys % 3 != 0) & (keys != keys[-1])
    else:                              # one run, nothing visible
        keys = np.zeros(cap, dtype=np.int64)
        mask = np.zeros(cap, dtype=bool)
    return keys.astype(np.int64), mask


@pytest.mark.parametrize("case", ["clustered", "unclustered",
                                  "masked_runs", "all_masked"])
def test_run_ends_from_the_scan_equal_the_searched(case):
    """`next_flag` against the search it replaced, at the lanes
    `runs_agg_core` asks at (every lane, so every `posc`); then the
    core's groups against a plain loop over the runs."""
    import jax.numpy as jnp
    from tidb_tpu.expression import EvalCtx
    keys, mask = _runs_case(case)
    cap = len(keys)
    change = np.concatenate([[True], keys[1:] != keys[:-1]])
    cs_change = np.cumsum(change.astype(np.int64))
    at = np.arange(cap)
    want = np.minimum(np.searchsorted(cs_change, cs_change[at] + 1), cap)
    got = al.next_flag(jnp.asarray(change), jnp.asarray(at), cap)
    assert np.array_equal(np.asarray(got), want)

    vals = np.arange(cap, dtype=np.int64) % 97
    col = Column(0, new_bigint_type())
    ctx = EvalCtx(jnp, cap, {0: (jnp.asarray(vals), None, None)},
                  host=False)
    aggs = [AggDesc("sum", [col]), AggDesc("count", []),
            AggDesc("max", [col]), AggDesc("first_row", [col])]
    res = al.runs_agg_core([jnp.asarray(keys)], None, jnp.asarray(mask),
                           ctx, aggs, cap, cap)
    ref = []
    starts = np.flatnonzero(change)
    for s, e in zip(starts, list(starts[1:]) + [cap]):
        m = mask[s:e]
        if m.any():
            ref.append((keys[s], vals[s:e][m].sum(), m.sum(),
                        vals[s:e][m].max(), vals[s:e][m][0]))
    n = int(res["ngroups"])
    assert n == len(ref)
    got = list(zip(np.asarray(res["keys"][0])[:n],
                   np.asarray(res["states"][0][0])[:n],
                   np.asarray(res["states"][1][0])[:n],
                   np.asarray(res["states"][2][0])[:n],
                   np.asarray(res["states"][3][0])[:n]))
    assert got == ref
