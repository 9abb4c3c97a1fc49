"""The final merge of aggregation partials on the group items that
identify the group (`PartialAggResult.ident`, executors.HashAggExec.
_merge_partials): grouping on a subset that determines the rest gives
the rows that grouping on every item gives, and the fused pipeline's
helper names that subset from the plan. CPU backend; counts and
answers, never timings."""
from types import SimpleNamespace

import numpy as np
import pytest

import tidb_tpu.copr.pipeline as pl
from tidb_tpu.bench.tpch import ALL_QUERIES, load_tpch
from tidb_tpu.copr.agg_lowering import PartialAggResult
from tidb_tpu.executor.executors import HashAggExec
from tidb_tpu.parser import parse
from tidb_tpu.planner.optimize import optimize
from tidb_tpu.planner.physical import PhysFusedPipeline
from tidb_tpu.testkit import TestKit
from tidb_tpu.types.field_type import new_bigint_type
from tidb_tpu.utils import metrics as mu

BIG = new_bigint_type()
AGGS = ("count", "sum", "min", "max", "first_row")
NKEYS = 7


def _merger(nkeys=NKEYS):
    """A final HashAggExec over `nkeys` group items and AGGS, with no
    child: `_merge_partials` reads its plan and schema only."""
    ex = HashAggExec.__new__(HashAggExec)
    arg = SimpleNamespace(ft=BIG)
    ex.plan = SimpleNamespace(
        mode="final", group_items=[SimpleNamespace(ft=BIG)] * nkeys,
        aggs=[SimpleNamespace(name=a, ft=BIG, args=[arg]) for a in AGGS])
    ex.schema = SimpleNamespace(
        cols=[SimpleNamespace(col=SimpleNamespace(ft=BIG))]
        * (nkeys + len(AGGS)))
    return ex


def _item(ids, i):
    """Group item i of the groups `ids`: a function of the id, so the
    id identifies the group. Item 5 is NULL for every fourth id."""
    data = (ids * (2 * i + 3) + i) % 97 if i < 6 else ids // 3
    nulls = (ids % 4 == 0) if i == 5 else np.zeros(len(ids), dtype=bool)
    return data.astype(np.int64), nulls


def _partial(rng, ids, at, ident, nkeys=NKEYS, empty_first_row=False):
    """One partial over the groups `ids`: the identifying items sit at
    the indices `at` (the id itself, or two halves of it), the rest are
    `_item`s of it."""
    n = len(ids)
    keys, key_nulls = [], []
    for i in range(nkeys):
        if i in at:
            data = ids if len(at) == 1 else \
                (ids // 10 if i == at[0] else ids % 10)
            nulls = np.zeros(n, dtype=bool)
        else:
            data, nulls = _item(ids, i)
        keys.append(data.astype(np.int64))
        key_nulls.append(nulls)
    cnt = rng.randint(1, 9, n).astype(np.int64)
    val = rng.randint(-1000, 1000, n).astype(np.int64)
    seen = np.zeros(n, dtype=np.int64) if empty_first_row \
        else np.ones(n, dtype=np.int64)
    # first_row: the value is the id's own, but a partial that saw none
    # (cnt 0) carries garbage in its slot
    first = np.where(seen > 0, ids * 7, -12345).astype(np.int64)
    states = [[cnt], [val, cnt], [val, cnt], [val, cnt], [first, seen]]
    return PartialAggResult(ngroups=n, keys=keys, key_nulls=key_nulls,
                            states=states, ident=ident)


def _without_ident(partials):
    return [PartialAggResult(ngroups=p.ngroups, keys=p.keys,
                             key_nulls=p.key_nulls, states=p.states)
            for p in partials]


def _rows(chunk):
    """Every row, NULL as None, sorted on all columns."""
    cols = []
    for c in chunk.columns:
        nulls = c.nulls if c.nulls is not None else \
            np.zeros(len(c.data), dtype=bool)
        cols.append([None if nl else int(v)
                     for v, nl in zip(c.data, nulls)])
    return sorted(zip(*cols), key=lambda r: tuple(
        (v is None, 0 if v is None else v) for v in r))


_paths = _grown = mu.agg_merges     # a reading; what grew since one


def _overlapping(rng, nparts, ngroups=3000):
    """Group ids of `nparts` partials that overlap: every partial holds
    about 60 % of the groups; group 0 is in the first partial only."""
    out = []
    for p in range(nparts):
        ids = np.nonzero(rng.rand(ngroups) < 0.6)[0] + 1
        if p == 0:
            ids = np.concatenate([[0], ids])
        out.append(rng.permutation(ids))
    return out


CASES = {
    # name: (partials, identifying indices, sorted across partials,
    #        the path the merge must count)
    "one_of_seven_2_partials": (2, (3,), False, "ident"),
    "one_of_seven_4_partials": (4, (3,), False, "ident"),
    "one_of_seven_5_partials": (5, (0,), False, "ident"),
    "two_identifying_items": (3, (1, 4), False, "ident"),
    "arrives_sorted": (4, (2,), True, "sorted_runs"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_on_ident_equals_merge_on_all_items(case):
    nparts, at, in_order, path = CASES[case]
    rng = np.random.RandomState(len(case))
    if in_order:
        # range partitions of a clustered key: partial p holds the ids
        # of its own range, ascending; the edges' groups are in both
        bounds = np.linspace(0, 6000, nparts + 1).astype(int)
        idss = [np.arange(max(lo - 1, 0), hi)
                for lo, hi in zip(bounds[:-1], bounds[1:])]
    else:
        idss = _overlapping(rng, nparts)
    partials = [_partial(rng, ids, at, at) for ids in idss]
    ex = _merger()
    before = _paths()
    got = ex._merge_partials(partials)
    assert _grown(before) == {path: 1}
    before = _paths()
    want = ex._merge_partials(_without_ident(partials))
    assert _grown(before) == {"all_items": 1}
    assert len(got) == len(want) == len(np.unique(np.concatenate(idss)))
    assert _rows(got) == _rows(want)
    # the group in one partial only is there, with that partial's states
    if not in_order and len(at) == 1:
        p0 = partials[0]
        i0 = int(np.nonzero(p0.keys[at[0]] == 0)[0][0])
        row = [r for r in _rows(got) if r[at[0]] == 0]
        assert len(row) == 1 and row[0][NKEYS] == p0.states[0][0][i0]


def test_null_in_a_non_identifying_key_stays_one_group():
    rng = np.random.RandomState(5)
    idss = _overlapping(rng, 3, ngroups=400)
    partials = [_partial(rng, ids, (3,), (3,)) for ids in idss]
    assert any(p.key_nulls[5].any() for p in partials)
    ex = _merger()
    got = ex._merge_partials(partials)
    assert _rows(got) == _rows(ex._merge_partials(_without_ident(partials)))
    nulls = got.columns[5].nulls
    assert nulls is not None and nulls.sum() == sum(
        1 for i in np.unique(np.concatenate(idss)) if i % 4 == 0)


def test_partials_that_disagree_on_ident_merge_on_all_items():
    rng = np.random.RandomState(6)
    idss = _overlapping(rng, 3, ngroups=500)
    partials = [_partial(rng, ids, (3,), ident)
                for ids, ident in zip(idss, [(3,), None, (3,)])]
    ex = _merger()
    before = _paths()
    got = ex._merge_partials(partials)
    assert _grown(before) == {"all_items": 1}
    assert _rows(got) == _rows(ex._merge_partials(_without_ident(partials)))
    partials[1].ident = (0, 3)
    before = _paths()
    ex._merge_partials(partials)
    assert _grown(before) == {"all_items": 1}


def test_first_row_skips_the_partial_that_saw_no_value():
    rng = np.random.RandomState(7)
    ids = rng.permutation(np.arange(50))
    # the first partial, whose slots win a min-index race, saw nothing
    partials = [_partial(rng, ids, (3,), (3,), empty_first_row=True),
                _partial(rng, rng.permutation(ids), (3,), (3,))]
    ex = _merger()
    got = ex._merge_partials(partials)
    first = got.columns[NKEYS + AGGS.index("first_row")]
    assert first.nulls is None
    assert sorted(first.data.tolist()) == sorted((ids * 7).tolist())
    assert _rows(got) == _rows(ex._merge_partials(_without_ident(partials)))


def test_one_live_partial_counts_nothing():
    rng = np.random.RandomState(8)
    ids = np.arange(20)
    dead = _partial(rng, ids[:0], (3,), (3,))
    before = _paths()
    got = _merger()._merge_partials([_partial(rng, ids, (3,), (3,)), dead])
    assert len(got) == 20 and _grown(before) == {}


def test_ident_of_every_item_is_the_all_items_merge():
    rng = np.random.RandomState(9)
    partials = [_partial(rng, rng.permutation(np.arange(30)), (0,), (0,),
                         nkeys=1) for _ in range(2)]
    before = _paths()
    got = _merger(1)._merge_partials(partials)
    assert len(got) == 30 and _grown(before) == {"all_items": 1}


def test_ident_does_not_cross_hosts():
    """cluster/rpc.py: a worker's partial arrives with `ident` None (the
    coordinator cannot know the workers' dimensions agree) and merges on
    all items, to the same rows."""
    from tidb_tpu.cluster.rpc import (deserialize_partials,
                                      serialize_partials)
    rng = np.random.RandomState(10)
    partials = [_partial(rng, ids, (3,), (3,))
                for ids in _overlapping(rng, 2, ngroups=200)]
    arrived = deserialize_partials(*serialize_partials(partials))
    assert [p.ident for p in arrived] == [None, None]
    ex = _merger()
    before = _paths()
    got = ex._merge_partials(arrived)
    assert _grown(before) == {"all_items": 1}
    assert _rows(got) == _rows(ex._merge_partials(partials))


# ---- which items the fused pipeline names, from the plan --------------

@pytest.fixture(scope="module")
def tk():
    tk = TestKit()
    load_tpch(tk, sf=0.003, seed=7)
    return tk


def _fused_plan(tk, sql):
    stack = [optimize(parse(sql)[0], tk.sess._plan_ctx())]
    while stack:
        node = stack.pop()
        if isinstance(node, PhysFusedPipeline):
            return node
        stack.extend(node.children or [])
    raise AssertionError("no fused pipeline in the plan")


_JOINED = ("from customer, orders, lineitem "
           "where c_custkey = o_custkey and o_orderkey = l_orderkey ")

PLANS = {
    "q10": (ALL_QUERIES["q10"], ["customer.c_custkey"]),
    "q3": (ALL_QUERIES["q3"], ["lineitem.l_orderkey"]),
    "q18": (ALL_QUERIES["q18"], ["orders.o_orderkey"]),
    "q5": (ALL_QUERIES["q5"], None),        # n_name: no key of nation
    "q1": (ALL_QUERIES["q1"], None),        # no dimension
    "q6": (ALL_QUERIES["q6"], None),        # no group item
    "c_name_alone": ("select c_name, sum(l_quantity) " + _JOINED +
                     "group by c_name", None),
    "left_join_dimension": (
        "select o_orderkey, c_name, sum(l_quantity) from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "left join customer on o_custkey = c_custkey "
        "group by o_orderkey, c_name", None),
    "two_roots": ("select l_orderkey, s_suppkey, s_name, o_orderdate, "
                  "sum(l_quantity) from lineitem, orders, supplier "
                  "where l_orderkey = o_orderkey and l_suppkey = s_suppkey "
                  "group by l_orderkey, s_suppkey, s_name, o_orderdate",
                  ["lineitem.l_orderkey", "supplier.s_suppkey"]),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_the_items_the_plan_names(tk, name):
    sql, want = PLANS[name]
    plan = _fused_plan(tk, sql)
    ident = pl._ident_items(plan)
    if want is None:
        assert ident is None
    else:
        assert [repr(plan.group_items[i]) for i in ident] == want
