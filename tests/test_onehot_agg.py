"""The learned slot table (copr/agg_lowering onehot_agg_body): a
host-learned slot table replaces the device argsort for small group
domains under the TPU segment policy. Where every packed code of the
learned spans is under the table's capacity the code is the slot and
nothing is searched; such a table of at most ONEHOT_CMP_MAX slots is
reduced by a compare at the slot and an int64 select-and-sum ("cmp"),
every other by int8 limb matmuls ("mxu"). Exactness guards: miss
detection on new/out-of-span keys, zero-slot drop for deletes,
wrap-around identical between the reducers.
Forced on here through the module's two test seams: the runs policy
and the one-hot kind on the CPU backend (which would otherwise take
its scatter impl and skip it). Which form a case runs follows its keys
(977-multiples are searched, 0..40 are their own codes) and, for the
coded matmul, the crossover moved to 0."""
import jax
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.pipeline as pl
from tidb_tpu.testkit import TestKit

# form -> (the i-th of 40 group keys, a key inside their span that none
# of them is, ONEHOT_CMP_MAX or None for the module's): 42 x 6 codes of
# the dense keys fit the 256 slots that 200 groups take
FORMS = {
    "search_mxu": (lambda i: i * 977, 500, None),
    "code_mxu": (lambda i: i + (i >= 20), 20, 0),
    "code_cmp": (lambda i: i + (i >= 20), 20, None),
}


@pytest.fixture()
def seams(monkeypatch):
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    monkeypatch.setattr(al, "_FORCE_ONEHOT", True)


@pytest.fixture(params=["search_mxu", "code_cmp"])
def tk(request, seams):
    key, hole, _crossover = FORMS[request.param]
    tk = TestKit()
    tk.key, tk.hole = key, hole
    tk.must_exec("create table f (id bigint primary key, g bigint, "
                 "h bigint, v bigint, w bigint)")
    rng = np.random.RandomState(7)
    rows = []
    for i in range(30000):
        rows.append(
            f"({i},{key(int(rng.randint(0, 40)))},"
            f"{int(rng.randint(0, 5))},"
            f"{int(rng.randint(-1000000, 1000000))},"
            f"{int(rng.randint(0, 1 << 40))})")
    tk.must_exec("insert into f values " + ",".join(rows))
    return tk


Q = ("select g, h, count(*), sum(v), sum(w), avg(v) from f "
     "where v > -900000 group by g, h order by g, h")


def test_onehot_learns_and_matches(tk):
    r1 = tk.must_query(Q).rs.rows          # learns from sorted/runs
    m0 = tk.domain.metrics.get("fused_onehot_agg", 0)
    r2 = tk.must_query(Q).rs.rows          # one-hot path
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > m0
    assert len(r1) == len(r2) == 200
    for a, b in zip(r1, r2):
        assert list(a) == list(b)


def test_onehot_miss_invalidates(tk):
    tk.must_query(Q)
    tk.must_query(Q)
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > 0
    # a brand-new group key must be a miss -> exact fallback + relearn
    tk.must_exec("insert into f values (100000, 99991, 9, 5, 5)")
    r3 = tk.must_query(Q).rs.rows
    assert len(r3) == 201
    r4 = tk.must_query(Q).rs.rows
    assert [list(x) for x in r3] == [list(x) for x in r4]


def test_onehot_zero_slot_drop(tk):
    tk.must_query(Q)
    tk.must_query(Q)
    tk.must_exec("delete from f where g = 0")
    r = tk.must_query(Q).rs.rows
    assert 0 not in {x[0] for x in r}
    assert len(r) == 195 or len(r) == 196      # 5 h-groups under g=0


def test_onehot_negative_and_wide_sums(tk):
    # sums with negatives (sign-bit limb) and 40-bit values must be
    # bit-exact vs the host oracle
    dev = tk.must_query("select g, sum(v), sum(w) from f group by g "
                        "order by g").rs.rows
    dev2 = tk.must_query("select g, sum(v), sum(w) from f group by g "
                         "order by g").rs.rows
    tk.domain.copr.use_device = False
    host = tk.must_query("select g, sum(v), sum(w) from f group by g "
                         "order by g").rs.rows
    tk.domain.copr.use_device = True
    assert [list(x) for x in dev] == [list(x) for x in host]
    assert [list(x) for x in dev2] == [list(x) for x in host]


def test_onehot_pipelined_miss_on_one_partition(tk, monkeypatch):
    """A new key whose rows land in only ONE partition: the sibling
    pipelined partition consumes its dispatched one-hot state cleanly
    while the miss pops the cache — must fall back, not crash."""
    tk.domain.copr.device_rows = 8192      # ~4 partitions
    tk.must_query(Q)
    tk.must_query(Q)
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > 0
    # key 99991*977 only ever lands in the last partition
    tk.must_exec("insert into f values (100001, 97661207, 0, 1, 1)")
    r = tk.must_query(Q).rs.rows
    assert len(r) == 201
    r2 = tk.must_query(Q).rs.rows
    assert [list(x) for x in r] == [list(x) for x in r2]


def test_onehot_delta_fold_zero_rebuilds_on_append(tk):
    """ISSUE 15 satellite (ROADMAP item #5 learned-structure tail):
    an in-bucket append — existing keys AND a brand-new in-span key —
    extends the learned slot table at bind time through the
    version-advance/delta contract, with ZERO dispatch-time
    miss-pop-relearns; the one-hot path keeps serving and stays
    host-identical."""
    tk.must_query(Q)
    tk.must_query(Q)
    m = tk.domain.metrics
    served0 = m.get("fused_onehot_agg", 0)
    assert served0 > 0
    # the hole is inside the learned span (500 among 977-multiples in
    # [0, 38103]; 20 among 0..40) but not a learned key -> a genuinely
    # new slot
    tk.must_exec(f"insert into f values (100000, {tk.hole}, 3, 7, 7), "
                 f"(100001, {tk.key(1)}, 0, 1, 1)")
    r = tk.must_query(Q).rows
    assert m.get("fused_onehot_miss", 0) == 0
    assert m.get("fused_onehot_rebuild", 0) == 0
    assert m.get("fused_onehot_delta_fold", 0) == 1
    assert m.get("fused_onehot_agg", 0) > served0   # still one-hot
    assert len(r) == 201
    r2 = tk.must_query(Q).rows
    assert [list(x) for x in r] == [list(x) for x in r2]
    # host oracle
    tk.domain.copr.use_device = False
    host = tk.must_query(Q).rows
    tk.domain.copr.use_device = True
    assert [list(x) for x in r2] == [list(x) for x in host]


def test_onehot_delta_fold_out_of_span_relearns(tk):
    """A key the learned packing cannot represent still relearns
    cleanly (the only rebuild left) and stays correct."""
    tk.must_query(Q)
    tk.must_query(Q)
    m = tk.domain.metrics
    tk.must_exec("insert into f values (100002, 99999977, 3, 1, 1)")
    r = tk.must_query(Q).rows
    assert m.get("fused_onehot_rebuild", 0) == 1
    assert len(r) == 201
    r2 = tk.must_query(Q).rows
    assert [list(x) for x in r] == [list(x) for x in r2]


def test_onehot_full_range_keys_rejected(tk):
    # key spans beyond the 61-bit pack budget must be rejected BEFORE
    # packing (no OverflowError), falling back to the exact lowering
    tk.must_exec("create table wide (id bigint primary key, g bigint, "
                 "v int)")
    tk.must_exec(f"insert into wide values (1, {-(1 << 62)}, 1), "
                 f"(2, {1 << 62}, 2), (3, 0, 3)")
    q = "select g, sum(v) from wide group by g order by g"
    r1 = tk.must_query(q).rs.rows
    r2 = tk.must_query(q).rs.rows
    assert [list(x) for x in r1] == [list(x) for x in r2]
    assert len(r1) == 3


# ---- the three forms ---------------------------------------------------

def _fill(tk, name, keys, n=30000, big=0):
    """Table `name` (id, g, h, v, w): g from `keys`, h under 5; v
    within a million either side, or `big` either side where given."""
    tk.must_exec(f"create table {name} (id bigint primary key, g bigint, "
                 "h bigint, v bigint, w bigint)")
    rng = np.random.RandomState(11)
    g = rng.choice(keys, n)
    h = rng.randint(0, 5, n)
    v = rng.randint(-1000000, 1000000, n)
    if big:
        v = np.where(rng.randint(0, 2, n) == 1, big, -big) + v
    w = rng.randint(0, 1 << 40, n, dtype=np.int64)
    tk.must_exec(f"insert into {name} values " + ",".join(
        f"({i},{g[i]},{h[i]},{v[i]},{w[i]})" for i in range(n)))


def _q(name):
    return (f"select g, h, count(*), sum(v), sum(w), avg(v) from {name} "
            "group by g, h order by g, h")


def _host(tk, sql):
    tk.domain.copr.use_device = False
    try:
        return [list(r) for r in tk.must_query(sql).rows]
    finally:
        tk.domain.copr.use_device = True


def _form(monkeypatch, form):
    """-> the 40 group keys that make a learned table take `form`, with
    the crossover moved where the form needs it."""
    key, _hole, crossover = FORMS[form]
    if crossover is not None:
        monkeypatch.setattr(al, "ONEHOT_CMP_MAX", crossover)
    return np.array([key(i) for i in range(40)])


def _kind(form):
    return "onehot_cmp" if form.endswith("_cmp") else "onehot"


@pytest.fixture
def kinds_built(monkeypatch):
    """[[agg_kind, agg_param, build args, call shapes]] of every fused
    kernel built."""
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


@pytest.fixture(params=sorted(FORMS))
def formed(request, seams, monkeypatch, judged_runs, kinds_built):
    """(tk, form, sql): a table learned and served once under `form`."""
    form = request.param
    tk = TestKit()
    _fill(tk, "d", _form(monkeypatch, form))
    sql = _q("d")
    tk.must_query(sql)
    before = judged_runs()
    tk.must_query(sql)
    assert judged_runs(before) == {("fused", _kind(form), "stands"): 1}
    return tk, form, sql


def test_every_form_answers_as_the_scatter_oracle(formed, kinds_built):
    tk, form, sql = formed
    assert [r[1] for r in kinds_built if r[0] == "onehot"] == \
        [(256, *form.split("_"))]
    assert [list(r) for r in tk.must_query(sql).rows] == _host(tk, sql)
    assert len(_host(tk, sql)) == 200


# what the table cannot hold -> the row that brings it
LACKS = {
    "null_key_code_0": "(100000, null, 3, 7, 7)",
    "out_of_span_key": "(100000, 99999977, 3, 7, 7)",
    "in_span_key_the_table_lacks": "(100000, 39, 3, 7, 7), "
                                   "(100001, 38, 9, 1, 1)",
}


@pytest.mark.parametrize("lack", sorted(LACKS))
def test_a_key_the_table_lacks_is_a_miss_and_relearns(
        formed, monkeypatch, judged_runs, lack):
    """A NULL key, a key outside the learned spans and a code inside
    them that the table lacks (`h` 9 is out of span, `g` 38 x `h` 3 in:
    where the code is the slot the device cannot tell, the row count at
    that code does) each throw the run away and relearn; none becomes a
    group of its own or joins another's."""
    tk, form, sql = formed
    if lack.startswith("in_span"):
        tk.must_exec("delete from d where g >= 38 and h = 3")
        tk.must_exec("delete from d where id >= 100000")
        tk.domain.copr._host_cache.clear()
        tk.must_query(sql)
        tk.must_query(sql)
    # the fold of appended rows would teach the table first (it cannot
    # see a dim-joined key: this is that statement's path)
    monkeypatch.setattr(pl, "_oh_fold_delta", lambda *a, **k: None)
    tk.must_exec("insert into d values " + LACKS[lack])
    before = judged_runs()
    m0 = tk.domain.metrics.get("fused_onehot_miss", 0)
    got = [list(r) for r in tk.must_query(sql).rows]
    assert judged_runs(before).get(
        ("fused", _kind(form), "retry_onehot_miss")) == 1
    assert tk.domain.metrics.get("fused_onehot_miss", 0) == m0 + 1
    assert got == _host(tk, sql)
    assert [list(r) for r in tk.must_query(sql).rows] == got


def test_a_slot_with_no_live_row_is_dropped(formed):
    tk, _f, sql = formed
    tk.must_exec("delete from d where g = 0 or (g in (1, 977) and h = 2)")
    got = [list(r) for r in tk.must_query(sql).rows]
    assert tk.domain.metrics.get("fused_onehot_miss", 0) == 0
    assert len(got) == 194 and got == _host(tk, sql)


@pytest.mark.parametrize("matmul", ["search_mxu", "code_mxu"])
def test_sums_that_wrap_agree_bit_for_bit_between_the_reducers(
        seams, monkeypatch, matmul):
    """Thousands of values of 2^62 either side a group: the true sum
    leaves int64, and what is left mod 2^64 is the same number whether
    limbs are recombined on the host or the device adds in int64."""
    got = {}
    for form in (matmul, "code_cmp"):
        with monkeypatch.context() as mp:
            keys = _form(mp, form)
            tk = TestKit()
            _fill(tk, "d", keys, n=20000, big=1 << 62)
            sql = "select g, h, sum(v), count(v) from d group by g, h " \
                  "order by g, h"
            tk.must_query(sql)
            # (the keys differ between the forms, their order does not)
            got[form] = [list(r)[1:] for r in tk.must_query(sql).rows]
            assert tk.domain.metrics.get("fused_onehot_agg", 0) == 1
    assert got[matmul] == got["code_cmp"] and len(got[matmul]) == 200
    sums = np.array([int(r[1]) for r in got["code_cmp"]], dtype=object)
    assert (abs(sums) > 1 << 61).any()      # the data did wrap


# ---- the census of q9's program ---------------------------------------

def _walk(jaxpr, visit, scope=""):
    """visit(eqn, its name stack under every enclosing eqn's): a loop's
    body starts a name stack of its own."""
    for e in jaxpr.eqns:
        at = f"{scope}/{e.source_info.name_stack}"
        visit(e, at)
        for v in e.params.values():
            for j in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _walk(inner, visit, at)


@pytest.mark.parametrize("form", ["code_mxu", "code_cmp"])
def test_census_of_q9s_group_agg(seams, monkeypatch, kinds_built, form):
    """TPC-H Q9 (nations x years: 26 x 8 codes under 256 slots; a wider
    LIKE than the spec's, which at this scale leaves 72 groups and 128
    slots): under the compare form the `group_agg` scope holds no
    `dot_general`, no int8 and no `while` or `scan` (nothing searched,
    nothing looped); the matmul form, the control, holds them."""
    from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES
    _form(monkeypatch, form)
    tk = TestKit()
    load_tpch(tk, sf=0.01, seed=7)
    q9 = ALL_QUERIES["q9"].replace("%green%", "%e%")
    for _ in range(2):
        tk.must_query(q9)
    (_kind, param, (a, k), shapes), = [r for r in kinds_built
                                       if r[0] == "onehot"]
    assert param == (256, *form.split("_"))
    cj = jax.make_jaxpr(pl._make_pipeline_body(
        *a, **dict(k, want_fnvalid=True)))(*shapes)
    seen = set()

    def visit(e, scope):
        if "group_agg" not in scope:
            return
        seen.add(e.primitive.name)
        if any(getattr(v.aval, "dtype", None) == np.int8
               for v in e.outvars):
            seen.add("int8")
    _walk(cj.jaxpr, visit)
    assert "reduce_sum" in seen
    # (a `fori_loop` and `searchsorted`'s steps trace as `scan`; the
    # chip's compiler writes both as `while`)
    loops = {"dot_general", "int8", "scan"}
    assert (loops <= seen) if form == "code_mxu" else \
        not ((loops | {"while"}) & seen), \
        " ".join(sorted(seen))
