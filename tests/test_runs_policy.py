"""End-to-end checks of the TPU "runs" segment lowering on CPU.

The full suite runs with the CPU default (scatter oracle); this module
re-runs the headline query shapes with the TPU policy forced so the
scatter-free kernels (reduce / broadcast-compare / contiguous-run
partials) stay covered in CI. See copr/agg_lowering.py `policy` for
the policy and what was measured behind it.
"""
import jax
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.pipeline as pl
from tidb_tpu.testkit import TestKit
from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES, QUERIES
from tidb_tpu.utils import metrics


@pytest.fixture
def runs_impl():
    al._FORCE_SEGMENT_IMPL = "runs"
    try:
        yield
    finally:
        al._FORCE_SEGMENT_IMPL = None


@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    load_tpch(tk, sf=0.003, seed=7)
    return tk


def _dev_vs_host(tk, sql, runs=1):
    tk.domain.copr.use_device = True
    dev = [tk.must_query(sql).rows for _ in range(runs)][-1]
    tk.domain.copr.use_device = False
    try:
        host = tk.must_query(sql).rows
    finally:
        tk.domain.copr.use_device = True
    assert len(dev) == len(host) and len(dev) > 0
    for rd, rh in zip(dev, host):
        for a, b in zip(rd, rh):
            if isinstance(a, float) or isinstance(b, float):
                np.testing.assert_allclose(float(a), float(b), rtol=1e-9)
            else:
                assert a == b, (sql, rd, rh)
    return dev


@pytest.mark.parametrize("q", ["q1", "q3", "q5", "q6"])
def test_tpch_headline_runs_vs_host(tk, runs_impl, q):
    _dev_vs_host(tk, QUERIES[q])


def test_first_row_skips_empty_partials(runs_impl):
    """A run (or partition) whose rows all have NULL agg args emits a
    cnt=0 first_row partial whose value slot is garbage; the merge must
    take the first partial that actually saw a value."""
    tk = TestKit()
    tk.must_exec("create table t (k int, v int)")
    tk.must_exec("insert into t values (1, null), (1, null), (2, 7), "
                 "(2, 8), (1, 42), (1, 43)")
    got = tk.must_query("select k, v from t group by k order by k").rows
    assert [(int(r[0]), int(r[1])) for r in got] == [(1, 42), (2, 7)]


def test_runs_degradation_pins_sorted(runs_impl, monkeypatch):
    """Unclustered keys explode into ~per-row runs: the guard must pin
    the query shape to the sorted lowering and still answer exactly."""
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    tk = TestKit()
    tk.must_exec("create table t (k bigint, v int)")
    rng = np.random.RandomState(5)
    # wide key span: not BCR-eligible, so the general runs path runs
    ks = rng.randint(0, 1 << 40, 800)
    rows = ",".join(f"({k},{i})" for i, k in enumerate(ks))
    tk.must_exec(f"insert into t values {rows}")
    got = tk.must_query(
        "select k, count(*) from t group by k order by k").rows
    assert len(got) == len(set(ks.tolist()))
    for row in got:
        assert int(row[1]) == int((ks == int(row[0])).sum())
    pinned = [v for key, v in tk.domain.copr._host_cache.items()
              if key and key[0] == "aggimpl"]
    assert "sorted" in pinned


def test_unclustered_group_by_runs(runs_impl):
    """Unclustered keys produce duplicate-run partials; the merge must
    still return exact aggregates (bucket regrow path included)."""
    tk = TestKit()
    tk.must_exec("create table t (k int, v int, f double)")
    rng = np.random.RandomState(3)
    ks = rng.randint(0, 50, 600)
    vs = rng.randint(-1000, 1000, 600)
    rows = ",".join(
        f"({k},{v},{v / 7.0})" for k, v in zip(ks, vs))
    tk.must_exec(f"insert into t values {rows}")
    got = tk.must_query(
        "select k, count(*), sum(v), min(v), max(v), avg(f) from t "
        "group by k order by k").rows
    assert len(got) == len(set(ks.tolist()))
    for row in got:
        k = row[0]
        m = ks == k
        assert int(row[1]) == int(m.sum())
        assert int(row[2]) == int(vs[m].sum())
        assert int(row[3]) == int(vs[m].min())
        assert int(row[4]) == int(vs[m].max())
        np.testing.assert_allclose(float(row[5]),
                                   float((vs[m] / 7.0).mean()), rtol=1e-9)


# ---- position-grouped runs lowering ("posruns", copr/pipeline.py) ----

@pytest.fixture(scope="module")
def tkp():
    """d: 200 rows, 91 names (NULLs among them; several rows a name), 7
    grps. f: runs of 15 rows a d_id, every 40th row a d_id no d has (a
    miss in the middle of a run), d_id 40..44 absent from d (whole runs
    miss). g: the same runs without the mid-run misses, so g.d_id is
    stored in order (what the device top-n asks of its anchor)."""
    tk = TestKit()
    tk.must_exec("create table d (id int primary key, name varchar(16), "
                 "grp int, val int)")
    tk.must_exec("create table d2 (id int primary key, tag int)")
    tk.must_exec("create table f (k int primary key, d_id int, "
                 "amt decimal(10,2), q int)")
    tk.must_exec("create table g (k int primary key, d_id int, "
                 "amt decimal(10,2), q int)")
    tk.must_exec("insert into d values " + ",".join(
        "(%d, %s, %d, %d)" % (i, "null" if i % 11 == 0 else f"'n{i % 90}'",
                              i % 7, (i * 37) % 1000)
        for i in range(1, 206) if not 40 <= i < 45))
    tk.must_exec("insert into d2 values " + ",".join(
        f"({i}, {i % 3})" for i in range(1, 101)))
    rng = np.random.RandomState(11)
    frows, grows = [], []
    for k in range(3000):
        d_id = k // 15 + 1
        row = (rng.randint(1, 99999) / 100.0, rng.randint(0, 100))
        grows.append("(%d, %d, %s, %d)" % ((k, d_id) + row))
        frows.append("(%d, %d, %s, %d)" % (
            (k, 9999 if k % 40 == 7 else d_id) + row))
    tk.must_exec("insert into f values " + ",".join(frows))
    tk.must_exec("insert into g values " + ",".join(grows))
    return tk


@pytest.fixture
def kinds(monkeypatch):
    """[(agg_kind, agg_param, build args, call shapes)] of every fused
    kernel built while the fixture is live."""
    seen = []
    orig = pl._build_fused_kernel

    def spy(*a, **k):
        kern = orig(*a, **k)
        rec = [a[7], a[8], (a, k), None]
        seen.append(rec)

        def call(fjc, fvv, kargs):
            if rec[3] is None:
                rec[3] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                                   np.asarray(x).dtype),
                    (fjc, fvv, kargs))
            return kern(fjc, fvv, kargs)
        return call
    monkeypatch.setattr(pl, "_build_fused_kernel", spy)
    return seen


def _posruns_count(tk):
    """Device runs of a `posruns` program whose result stood (the typed
    counter is the process's: the tests here run one store at a
    time)."""
    from tidb_tpu.utils import metrics
    return sum(v for _n, lb, v in metrics.AGG_LOWERING.sample_rows()
               if lb["kind"] == "posruns" and lb["verdict"] == "stands")


_AGGS = "sum(f.amt), count(*), min(f.q), max(f.q), avg(f.amt)"
_SYN = {
    # NULL names; several d rows a name: positions differ, values merge
    "null_and_equal_payload":
        f"select d.name, {_AGGS} from f, d where f.d_id = d.id "
        "group by d.name order by d.name",
    "equal_payload_few_groups":
        f"select d.grp, {_AGGS} from f, d where f.d_id = d.id "
        "group by d.grp order by d.grp",
    "probe_key_and_payload":
        f"select f.d_id, d.name, d.val, {_AGGS} from f, d "
        "where f.d_id = d.id and f.q < 90 "
        "group by f.d_id, d.name, d.val order by f.d_id",
    "two_position_dims":
        f"select d.name, d2.tag, {_AGGS} from f, d, d2 "
        "where f.d_id = d.id and f.q = d2.id "
        "group by d.name, d2.tag order by d.name, d2.tag",
}


@pytest.mark.parametrize("case", sorted(_SYN))
def test_posruns_synthetic_vs_host(tkp, runs_impl, kinds, case):
    before = _posruns_count(tkp)
    _dev_vs_host(tkp, _SYN[case])
    assert _posruns_count(tkp) > before
    assert {k[0] for k in kinds} == {"posruns"}


@pytest.mark.parametrize("q", ["q3", "q10", "q18"])
def test_posruns_tpch_vs_host(tk, runs_impl, kinds, q):
    before = _posruns_count(tk)
    tk.domain.copr._kernel_cache.clear()
    # at SF0.003 no order passes q18's HAVING of 300; 244 pass 200
    _dev_vs_host(tk, ALL_QUERIES[q].replace("> 300", "> 200"))
    assert _posruns_count(tk) > before
    # q18's subquery groups by a fact column alone: today's kind
    want = {"posruns", "sort"} if q == "q18" else {"posruns"}
    assert {k[0] for k in kinds} == want


def test_posruns_group_straddles_partitions(tkp, runs_impl, kinds):
    """1000-row partitions cut runs of 15: both halves' partials merge."""
    copr = tkp.domain.copr
    old = copr.device_rows
    copr.device_rows = 1000
    before = _posruns_count(tkp)
    try:
        _dev_vs_host(tkp, _SYN["probe_key_and_payload"])
    finally:
        copr.device_rows = old
    assert _posruns_count(tkp) == before + 3
    assert {k[0] for k in kinds} == {"posruns"}


def test_posruns_uncommitted_insert_overlay(tkp, runs_impl):
    """The transaction's rows ride the same kernel as one more
    partition; one lands in a group the snapshot has, one in a new one
    (d_id 200 has no committed f row), one misses."""
    sql = _SYN["probe_key_and_payload"]
    base = _dev_vs_host(tkp, sql)
    tkp.must_exec("begin")
    try:
        tkp.must_exec("insert into f values (90001, 3, 5.00, 1), "
                      "(90002, 205, 7.00, 2), (90003, 42, 1.00, 3)")
        before = _posruns_count(tkp)
        got = _dev_vs_host(tkp, sql)
        assert _posruns_count(tkp) == before + 2
    finally:
        tkp.must_exec("rollback")
    assert len(got) == len(base) + 1
    assert _dev_vs_host(tkp, sql) == base


def test_posruns_late_compaction(tkp, runs_impl, kinds):
    """A selective dimension filter leaves under an eighth of the
    partition: the second run gathers survivors (positions beside the
    columns) before the run extraction."""
    sql = (f"select f.d_id, d.name, {_AGGS} from f, d "
           "where f.d_id = d.id and d.val < 100 "
           "group by f.d_id, d.name order by f.d_id")
    _dev_vs_host(tkp, sql, runs=2)
    assert [k[0] for k in kinds] == ["posruns", "posruns"]
    assert kinds[0][1][3] is None and isinstance(kinds[1][1][3], int)


@pytest.mark.parametrize("order, kind", [("s desc", "agg"),
                                         ("d.val desc", "group"),
                                         ("g.d_id", "group")])
def test_posruns_device_topn(tkp, runs_impl, kinds, order, kind):
    """The candidate cut runs on the position-grouped partials too; an
    ordering group item is read at bucket width from its dimension."""
    sql = ("select g.d_id, d.val, sum(g.amt) s from g, d "
           f"where g.d_id = d.id group by g.d_id, d.val order by {order} "
           "limit 5")
    _dev_vs_host(tkp, sql)
    assert [k[0] for k in kinds] == ["posruns"]
    topn = kinds[0][1][2]
    assert topn is not None and topn[0] == kind


_OLD_KIND = {
    "fact_column":
        "select f.q, d.grp, count(*) from f, d where f.d_id = d.id "
        "group by f.q, d.grp order by f.q, d.grp",
    "expression_over_dim_column":
        "select d.val + 1, count(*) from f, d where f.d_id = d.id "
        "group by d.val + 1 order by 1",
    "left_dim":
        "select d.val, count(*) from f left join d on f.d_id = d.id "
        "group by d.val order by d.val",
}


@pytest.mark.parametrize("case", sorted(_OLD_KIND))
def test_posruns_not_taken(tkp, runs_impl, kinds, case):
    before = _posruns_count(tkp)
    _dev_vs_host(tkp, _OLD_KIND[case])
    assert _posruns_count(tkp) == before
    assert "posruns" not in {k[0] for k in kinds}


def test_posruns_yields_to_pinned_sorted(runs_impl, kinds, monkeypatch):
    """Positions scattered over storage order: the first partition's
    partials exceed the degrade limit, the shape is pinned to "sorted"
    and that run and every later one take today's kind."""
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    tk = TestKit()
    tk.must_exec("create table d (id int primary key, val int)")
    tk.must_exec("create table f (k int primary key, d_id int, q int)")
    tk.must_exec("insert into d values " + ",".join(
        f"({i}, {i * 3})" for i in range(1, 101)))
    rng = np.random.RandomState(2)
    tk.must_exec("insert into f values " + ",".join(
        f"({k}, {rng.randint(1, 101)}, {k % 9})" for k in range(800)))
    sql = ("select d.val, count(*), sum(f.q) from f, d "
           "where f.d_id = d.id group by d.val order by d.val")
    _dev_vs_host(tk, sql, runs=2)
    assert _posruns_count(tk) == 0
    assert [k[0] for k in kinds] == ["posruns", "sort"]
    assert kinds[1][1][1] == "sorted"


def _walk(jaxpr, visit):
    for e in jaxpr.eqns:
        visit(e)
        for v in e.params.values():
            for j in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _walk(inner, visit)


def _wide_gather_operands(build, shapes, agg_kind=None, agg_param=None):
    """Argument paths of the arrays the body gathers from at fact
    width ("-" for an intermediate)."""
    a, k = build
    a = list(a)
    if agg_kind is not None:
        a[7], a[8] = agg_kind, agg_param
    cj = jax.make_jaxpr(pl._make_pipeline_body(
        *a, **dict(k, want_fnvalid=True)))(*shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    name = {id(v): p for v, p in zip(cj.jaxpr.invars, paths)}
    cap, out = a[1], []

    def visit(e):
        if e.primitive.name == "gather" and \
                e.outvars[0].aval.shape[:1] == (cap,):
            out.append(name.get(id(e.invars[0]), "-"))
    _walk(cj.jaxpr, visit)
    return out


@pytest.mark.parametrize("folded", [False, True])
def test_posruns_q10_gathers_no_group_payload(tk, runs_impl, kinds,
                                              monkeypatch, folded):
    """q10 groups by seven dimension columns nothing else reads: under
    "posruns" none is gathered at fact width (they are decoded from the
    positions on the host); the control, the same plan under today's
    kind, gathers every one. With the chain folded (copr/dimfold.py)
    customer and nation are not probed at fact width at all, and a
    statement that falls to the "sort" kind reads the seven as fields
    of the words orders' key addresses."""
    import tidb_tpu.copr.dimfold as df
    if not folded:
        monkeypatch.setattr(df, "fold_plan",
                            lambda plan: df.FoldPlan(len(plan.dims)))
    before = _posruns_count(tk)
    tk.domain.copr._kernel_cache.clear()
    tk.domain.copr.use_device = True
    tk.must_query(ALL_QUERIES["q10"])
    assert _posruns_count(tk) > before
    kind, param, build, shapes = kinds[0]
    assert kind == "posruns"
    plan = build[0][0]
    gmap, pos_dims = pl._pos_group_items(plan)
    assert pos_dims == ([1] if folded else [1, 2])
    root = {1: 0, 2: 0} if folded else {1: 1, 2: 2}
    payload = {f"[2][{root[di]}]['cols'][{g.idx}][0]"
               for g, (_k, di, _c) in zip(plan.group_items, gmap)}
    assert len(payload) == 7
    now = _wide_gather_operands(build, shapes)
    assert not payload & set(now)
    # the control: the statement again with the shape pinned to today's
    # kind, which evaluates the group items at fact width
    state = al.ShapeState(
        tk.domain.copr,
        tk.domain.copr.engine.table(plan.fact_dag.table_info),
        plan.group_items, plan.aggs)
    monkeypatch.setattr(state, "pin", "sorted")
    tk.domain.copr._kernel_cache.clear()
    del kinds[:]
    tk.must_query(ALL_QUERIES["q10"])
    kind, param, build, shapes = kinds[0]
    assert kind == "sort"
    old = _wide_gather_operands(build, shapes)
    if folded:
        text = build[0][6][0]["pack"]
        assert {("col", g.idx) for g in plan.group_items} <= \
            {t[:2] for t in text}
        words = [p for p in old if p.startswith("[2]")]
        assert words == [f"[2][0]['pk'][{i}]"
                         for i in range(len(shapes[2][0]["pk"]))]
        assert 1 <= len(words) <= 2 and not shapes[2][0]["cols"]
        return
    assert payload <= set(old)
    # seven payload gathers more, one of the positions' kind's own less
    assert len(old) - len(now) >= 6


# ---- where the k-th set lane is: no search left (PR 44) ----------------
# The compactions and the runs lowering invert a prefix count through
# `agg_lowering.prefix_select` (rows of block ends, int32) and read a
# run's end off a scan. Counts of the traced program, never times.

# statement -> the call sites its compacting program counts
_SELECT_SITES = {
    "q3": {"late_compact", "runs_pos", "runs_end"},
    "q10": {"late_compact", "runs_pos", "runs_end"},
    "q18": {"late_compact", "runs_pos", "runs_end"},
    "q12": {"early_compact"},
    "q19": {"early_compact"},
}


def _q(name):
    # at SF0.003 no order passes q18's HAVING of 300; 244 pass 200
    return ALL_QUERIES[name].replace("> 300", "> 200")


def _body_jaxpr(build, shapes):
    a, k = build
    return jax.make_jaxpr(pl._make_pipeline_body(
        *a, **dict(k, want_fnvalid=True)))(*shapes)


def _select_census(build, shapes):
    """-> (primitives under the `compact` and `group_agg` scopes, the
    operand types of the row gathers there, the types of the prefix
    counts a gather under `compact` reads): a loop's body is walked
    under its eqn's scope."""
    seen, rows, counts, cums = set(), [], [], {}

    def walk(jaxpr, scope):
        for e in jaxpr.eqns:
            at = f"{scope}/{e.source_info.name_stack}"
            staged = "compact" in at or "group_agg" in at
            name = e.primitive.name
            if name == "cumsum" or e.params.get("name") == "cumsum":
                cums[id(e.outvars[0])] = e.outvars[0].aval.dtype
            if staged:
                seen.add(name)
            if staged and name == "gather":
                src = e.invars[0]
                if e.params["slice_sizes"][-1] > 1:
                    rows.append(src.aval.dtype)
                if "compact" in at and id(src) in cums:
                    counts.append(cums[id(src)])
            for v in e.params.values():
                for j in v if isinstance(v, (list, tuple)) else [v]:
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        walk(inner, at)
    walk(_body_jaxpr(build, shapes).jaxpr, "")
    return seen, rows, counts


def _compacting_kernel(tk, kinds, q):
    """The statement's program once its compaction is learned: the
    last one built over three runs."""
    tk.domain.copr._kernel_cache.clear()
    del kinds[:]
    tk.domain.copr.use_device = True
    before = metrics.prefix_selects()
    for _ in range(3):
        tk.must_query(_q(q))
    kind, param, build, shapes = kinds[-1]
    ecap = build[1].get("ecap")
    assert (ecap is not None) if kind == "dense" else \
        (kind == "posruns" and param[3] is not None), (kind, param)
    return build, shapes, metrics.prefix_selects(before)


def _searching(monkeypatch):
    """The control: the parent's form, a search over the count."""
    import jax.numpy as jnp
    monkeypatch.setattr(
        al, "prefix_search",
        lambda cs, probes, site=None: jnp.searchsorted(
            cs.astype(jnp.int64), probes.astype(jnp.int64)))


@pytest.mark.parametrize("q", sorted(_SELECT_SITES))
def test_census_no_search_under_compact_and_group_agg(
        tk, runs_impl, kinds, monkeypatch, q):
    """q3, q10, q18 (`posruns` behind a late compaction), q12, q19 (an
    early compaction) under the chip's policy: no loop under the
    `compact` and `group_agg` scopes (`searchsorted`'s steps trace as
    `scan`, the chip's compiler writes them as `while`), the rows of
    block ends are gathered from int32 and no gather under `compact`
    reads an int64 count. (The columns a compaction moves and the sums
    of values stay int64 and are gathered so: once, at no loop's
    step.) The control, with the search put back, holds the loop."""
    build, shapes, _grown = _compacting_kernel(tk, kinds, q)
    seen, rows, counts = _select_census(build, shapes)
    assert "gather" in seen and "cumsum" in seen
    assert not {"while", "scan", "sort"} & seen, sorted(seen)
    assert rows and set(rows) == {np.dtype(np.int32)}, rows
    assert not [t for t in counts if t == np.int64]
    _dev_vs_host(tk, _q(q))
    _searching(monkeypatch)
    build, shapes, _grown = _compacting_kernel(tk, kinds, q)
    seen, rows, _counts = _select_census(build, shapes)
    assert {"while", "scan"} & seen and not rows


@pytest.mark.parametrize("q", sorted(_SELECT_SITES))
def test_prefix_select_counter_names_site_and_form(tk, runs_impl, kinds, q):
    """One count a built program a call site: `rows` for the searches
    that were, `scan` for a run's end; the same on the span open while
    the program is traced."""
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    try:
        _b, _s, grown = _compacting_kernel(tk, kinds, q)
        tagged = [r[0] for r in tk.must_query(
            "select attrs from information_schema.tidb_trace_events "
            "where attrs like '%select_%'").rows]
    finally:
        tk.must_exec("set tidb_tpu_trace_sample_rate = 0")
    want = {(s, "scan" if s == "runs_end" else "rows")
            for s in _SELECT_SITES[q]}
    assert set(grown) == want, grown
    for site, form in want:
        assert [a for a in tagged if f"select_{site}={form}" in a], tagged
    # a program found in the kernel cache is not traced again
    before = metrics.prefix_selects()
    tk.must_query(_q(q))
    assert not metrics.prefix_selects(before)


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_scans_select_nothing_and_keep_their_program(
        tk, runs_impl, kinds, monkeypatch, q):
    """The dense scans compact nothing and group by no run: the counter
    stands still and the body is the control's, equation for
    equation."""
    tk.domain.copr._kernel_cache.clear()
    tk.domain.copr.use_device = True
    before = metrics.prefix_selects()
    tk.must_query(_q(q))
    assert kinds and not metrics.prefix_selects(before)
    bodies = [str(_body_jaxpr(k[2], k[3])) for k in kinds]
    _searching(monkeypatch)
    tk.domain.copr._kernel_cache.clear()
    del kinds[:]
    tk.must_query(_q(q))
    assert [str(_body_jaxpr(k[2], k[3])) for k in kinds] == bodies
