"""Folded dimensions of the fused pipeline (copr/dimfold.py): a chain
root's probe table carries its own mask and the hit of every dimension
resolved under it, and what a fact lane reads through the root's
position -- a column, a null bit, a descendant's position -- is a field
of the word the table holds at its key: one gather a root.

Counts here are counts of the traced program (CPU backend), never
device times."""
import time

import jax
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
import tidb_tpu.copr.dimfold as df
import tidb_tpu.copr.pipeline as pl
import tidb_tpu.copr.probe as probe
from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import metrics as mu
from tidb_tpu.utils import phase


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


def _unfold(monkeypatch):
    """The control: every dimension keeps its own probe and mask."""
    monkeypatch.setattr(df, "fold_plan",
                        lambda plan: df.FoldPlan(len(plan.dims)))


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


def _counts():
    return {k[0]: c.value for k, c in mu.DIM_FOLD._children.items()}


def _grown(before):
    now = _counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def _host(tk, sql):
    tk.domain.copr.use_device = False
    try:
        return tk.must_query(sql).rows
    finally:
        tk.domain.copr.use_device = True


def _dev_vs_host(tk, sql, runs=1):
    tk.domain.copr.use_device = True
    dev = [tk.must_query(sql).rows for _ in range(runs)][-1]
    assert tk.domain.last_fused_reason is None
    host = _host(tk, sql)
    assert len(dev) == len(host)
    for rd, rh in zip(dev, host):
        for a, b in zip(rd, rh):
            if isinstance(a, float) or isinstance(b, float):
                np.testing.assert_allclose(float(a), float(b), rtol=1e-9)
            else:
                assert a == b, (sql, rd, rh)
    return dev


def _walk(jaxpr, visit):
    for e in jaxpr.eqns:
        visit(e)
        for v in e.params.values():
            for j in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _walk(inner, visit)


def _body_jaxpr(build, shapes):
    a, k = build
    return jax.make_jaxpr(pl._make_pipeline_body(
        *a, **dict(k, want_fnvalid=True)))(*shapes)


def _wide_census(build, shapes):
    """[(argument path of what the body gathers from at fact width, "-"
    for an intermediate; bytes an element of it)]."""
    cj = _body_jaxpr(build, shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    name = {id(v): p for v, p in zip(cj.jaxpr.invars, paths)}
    cap, out = build[0][1], []

    def visit(e):
        if e.primitive.name == "reshape" and id(e.invars[0]) in name:
            # (a flat table gathered by rows keeps its operand's name)
            name[id(e.outvars[0])] = name[id(e.invars[0])]
        if e.primitive.name == "gather" and \
                e.outvars[0].aval.shape[:1] == (cap,):
            out.append((name.get(id(e.invars[0]), "-"),
                        e.invars[0].aval.dtype.itemsize))
    _walk(cj.jaxpr, visit)
    return out


def _wide_gathers(build, shapes):
    return [p for p, _ in _wide_census(build, shapes)]


def _q(name):
    # at SF0.003 no order passes q18's HAVING of 300; 244 pass 200
    return ALL_QUERIES[name].replace("> 300", "> 200")


def _main_kernel(tk, kinds, sql):
    """(build, shapes) of the statement's program with dimensions."""
    tk.domain.copr._kernel_cache.clear()
    del kinds[:]
    tk.domain.copr.use_device = True
    tk.must_query(sql)
    got = [(k[2], k[3]) for k in kinds if k[2][0][0].dims]
    assert len(got) == 1
    return got[0]


# ---- (a) the census of fact-wide gathers ------------------------------

# query -> (most with the fold, of them from a dimension's operands,
# fewest without: ISSUE 28's table, ISSUE 30's for the composed word;
# bytes a fact lane gathers from the dimensions' operands: ISSUE 37's,
# every one of these tables fits 31 bits and was 8 bytes a slot before)
_CENSUS = {"q5": (2, 2, 27, 8), "q10": (3, 1, 15, 4), "q3": (2, 1, 10, 4),
           "q18": (2, 1, 7, 4)}
# the roots whose payload is composed with their probe table
_PACKED = {"q5": 2, "q10": 1, "q3": 0, "q18": 0}
# the tables a statement binds for a 32-bit gather: words and `lut`s
_WORD32 = {"q5": 2, "q10": 1, "q3": 1, "q18": 1}


@pytest.mark.parametrize("q", sorted(_CENSUS))
def test_census_fact_wide_gathers(tk, runs_impl, kinds, monkeypatch, q):
    most, of_dims, control, lane_bytes = _CENSUS[q]
    build, shapes = _main_kernel(tk, kinds, _q(q))
    census = _wide_census(build, shapes)
    folded = [p for p, _ in census]
    assert len(folded) <= most, folded
    assert sum(b for p, b in census if p.startswith("[2]")) == lane_bytes
    # one gather a root: its probe table, of positions or of words
    dimops = [p for p in folded if p.startswith("[2]")]
    assert len(dimops) <= of_dims and (q in ("q5", "q10") or
                                       len(dimops) == of_dims), folded
    packed = [da for da in shapes[2] if "pk" in da]
    assert len(packed) == _PACKED[q]
    for da in packed:
        # no root-width column or position rides beside the word
        assert not da["cols"] and "fpos" not in da and "lut" not in da \
            and "ord" not in da and "valid" not in da
        assert all(p.endswith("['pk'][0]") for p in dimops)
    # no mask, no child's table and no group payload at fact width
    assert not [p for p in folded
                if "'valid'" in p or "'cols'" in p or "'fpos'" in p]
    plan = kinds[-1][2][0][0]
    fp = df.fold_plan(plan)
    kids = [di for di, p in enumerate(fp.parent) if p is not None]
    assert kids and not [p for p in folded
                         for di in kids if p.startswith(f"[2][{di}]")]
    _unfold(monkeypatch)
    old = _wide_gathers(*_main_kernel(tk, kinds, _q(q)))
    assert len(old) >= control, old
    assert [p for p in old if "'valid'" in p] or q == "q3"


@pytest.mark.parametrize("q", ["q1", "q6"])
def test_scans_keep_their_program(tk, runs_impl, kinds, monkeypatch, q):
    """No dimension, no fold plan: the body is the control's, equation
    for equation."""
    tk.domain.copr._kernel_cache.clear()
    tk.domain.copr.use_device = True
    tk.must_query(_q(q))
    assert kinds and all(k[2][1].get("fold") is None for k in kinds)
    now = [str(_body_jaxpr(k[2], k[3])) for k in kinds]
    _unfold(monkeypatch)
    tk.domain.copr._kernel_cache.clear()
    del kinds[:]
    tk.must_query(_q(q))
    assert [str(_body_jaxpr(k[2], k[3])) for k in kinds] == now


@pytest.mark.parametrize("q", ["q3", "q18"])
def test_position_only_root_keeps_the_table_of_positions(
        tk, runs_impl, kinds, monkeypatch, q):
    """A root that reads its position and nothing else: the composed
    word would be the `lut`, so it takes `lut` and `lo`, no layout
    operand, and the body is the one built with no packing at all
    (the parent's program text: a persistent-cache hit)."""
    for _ in range(3):          # what a run learns (bucket, top-n cut)
        tk.must_query(_q(q))    # picks the next one's program
    before = _counts()
    build, shapes = _main_kernel(tk, kinds, _q(q))
    assert not [k for k in _grown(before) if "pack" in k]
    root = shapes[2][0]
    assert set(root) == {"cols", "lut", "lo"}
    assert root["lut"].dtype == np.int32        # positions under n
    assert all(lay.get("pack") is None for lay in build[0][6])
    now = str(_body_jaxpr(build, shapes))
    monkeypatch.setattr(df, "pack_fields", lambda *a: ())
    assert str(_body_jaxpr(*_main_kernel(tk, kinds, _q(q)))) == now


def test_nothing_folds_keeps_todays_operands(kinds):
    """A plan whose only dimension is a left join folds nothing: the
    builder is handed no fold plan and every column, and `valid`."""
    tk = _chain_tk()
    before = _counts()
    _dev_vs_host(tk, "select c.seg, count(*) from d left join c "
                 "on d.cid = c.id group by c.seg order by c.seg")
    assert _grown(before) == {"declined_left": 1, "word32": 1}
    (kind, _param, build, shapes), = kinds
    assert build[1].get("fold") is None
    assert "valid" in shapes[2][0] and len(shapes[2][0]["cols"]) == 2


# ---- (b) all 22 queries, device == host --------------------------------

# the queries whose fused plan has a chain at this scale
_CHAINED = {"q2", "q3", "q5", "q7", "q8", "q9", "q10", "q11", "q18",
            "q21"}


@pytest.mark.parametrize("q", [f"q{i}" for i in range(1, 23)])
def test_tpch_device_equals_host(tk, q):
    before = _counts()
    tk.domain.copr.use_device = True
    dev = tk.must_query(_q(q)).rows
    grown = _grown(before)
    host = _host(tk, _q(q))
    assert len(dev) == len(host)
    for rd, rh in zip(dev, host):
        for a, b in zip(rd, rh):
            if isinstance(a, float) or isinstance(b, float):
                np.testing.assert_allclose(float(a), float(b), rtol=1e-9)
            else:
                assert a == b, (q, rd, rh)
    if q in _CHAINED:
        assert grown.get("folded", 0) > 0, grown
    if q == "q9":
        assert grown.get("declined_composite_key", 0) > 0, grown
    if q in ("q1", "q6"):
        assert grown == {}


# ---- (b2) the composed word: unpack(pack(x)) == x ------------------------

_I62 = (1 << 62) - 1
# case -> (columns, words the first fit has to give[, and their types
# where not int64: a word is int32 where its fields end within 31 bits])
_PACK = {
    "negative_values": ([np.array([-7, -1, 0, 5]),
                         np.array([-(1 << 40), 3, 9, -2])], 1),
    "nulls_are_a_bit": ([np.array([10, 11, 12, 13], dtype=np.int32),
                         np.array([True, False, False, True])], 1,
                        ("int32",)),
    "a_word_of_one_bit": ([np.array([False, True, True, False])], 1,
                          ("int32",)),
    "a_negative_lo_in_a_narrow_word": (
        [np.array([-(1 << 40) - 3, -(1 << 40), -(1 << 40) + 900, -1 << 40]),
         np.array([-5, 5, 0, 1], dtype=np.int8)], 1, ("int32",)),
    "a_word_at_31_bits": ([np.array([0, (1 << 30) - 1, 5, 1 << 29]),
                           np.array([True, False, False, True])], 1,
                          ("int32",)),
    "a_word_at_32_bits": ([np.array([0, (1 << 31) - 1, 5, 1 << 30]),
                           np.array([True, False, False, True])], 1),
    "a_field_of_31_bits_beside_a_wide_word": (
        [np.array([0, 1 << 39, 5, 6]), np.array([7, (1 << 31) + 6, 8, 9]),
         np.array([0, 3, 2, 1])], 2, ("int64", "int32")),
    "no_rows": ([np.array([], dtype=np.int64),
                 np.array([], dtype=bool)], 1, ("int32",)),
    "a_field_at_62_bits": ([np.array([0, _I62, 17, 1 << 61]),
                            np.array([False, True, True, False])], 1),
    "a_field_at_63_bits_all_ones": (
        [np.array([-(1 << 62), (1 << 62) - 1, 0, -1])], 1),
    "spill_to_a_second_word": ([np.array([0, 1 << 39, 5, 6]),
                                np.array([-(1 << 39), 0, 1, 2]),
                                np.array([0, 1 << 19, 2, 3])], 2),
    "constant_columns_take_no_bits": ([np.full(4, 42), np.full(4, -3),
                                       np.arange(4)], 1, ("int32",)),
    "narrow_unsigned": ([np.array([0, 255, 7, 9], dtype=np.uint8),
                         np.array([65535, 0, 1, 2], dtype=np.uint16)], 1,
                        ("int32",)),
    # no room beside the miss bit: a word of its own, as it is
    "doubles_are_their_bit_pattern": (
        [np.array([0.5, -1.5, np.inf, -0.0]), np.arange(4)], 2,
        ("int32", "int64")),
    "range_past_63_bits": (
        [np.arange(4), np.array([-(1 << 62) - 5, (1 << 62) + 5, 0, -1]),
         np.array([1, 1 << 63, 3, (1 << 64) - 1], dtype=np.uint64)], 3,
        ("int32", "int64", "int64")),
    "float32_is_32_bits": ([np.array([0.5, -1.5, 3.25, 1e30],
                                     dtype=np.float32),
                            np.array([1, 2, 3, 4], dtype=np.int32)], 1),
}


def _round_trip(cols):
    """unpack(pack(x)) == x bit for bit, from words as wide as the rule
    says; -> the words' types."""
    words, word, shift, mask, lo = df.pack_words(cols)
    for i, c in enumerate(cols):
        back = df.unpack_field(words[word[i]], shift[i], mask[i], lo[i],
                               c.dtype)
        assert back.dtype == c.dtype
        np.testing.assert_array_equal(back.view(f"u{c.dtype.itemsize}"),
                                      c.view(f"u{c.dtype.itemsize}"))
    # a word is int32 exactly where its fields end within 31 bits
    for wi, w in enumerate(words):
        used = max((int(shift[i]) + (64 if mask[i] == -1 else
                                     int(mask[i]).bit_length())
                    for i in range(len(cols)) if word[i] == wi), default=0)
        assert w.dtype == (np.int32 if used <= 31 else np.int64), used
    # a hit never reads as the miss: word 0's sign bit belongs to no
    # field, and a miss, widened as the program widens it after the
    # gather, is negative and reads the minimum of every field of word 0
    miss = np.array([df.miss(words[0].dtype)], dtype=words[0].dtype)
    assert (words[0] >= 0).all() and miss.astype(np.int64)[0] < 0
    assert all(df.unpack_field(miss.astype(np.int64), shift[i], mask[i],
                               lo[i], np.int64)[0] == lo[i]
               for i in range(len(cols)) if word[i] == 0)
    return words, word


@pytest.mark.parametrize("case", sorted(_PACK))
def test_pack_round_trip(case):
    cols, nwords, wtypes = (_PACK[case] + (("int64",) * _PACK[case][1],))[:3]
    words, word = _round_trip(cols)
    assert len(words) == nwords and len(set(word)) == nwords
    assert tuple(w.dtype.name for w in words) == wtypes


@pytest.mark.parametrize("seed", range(8))
def test_pack_round_trip_property(seed):
    """Random field sets about the two edges (31 | 32 and 63 | 64 bits),
    with negative minima and a double among them."""
    rng = np.random.RandomState(seed)
    cols = []
    for _ in range(rng.randint(1, 7)):
        bits = int(rng.choice([0, 1, 5, 20, 30, 31, 32, 40, 62, 63, 64]))
        if bits == 64 and rng.randint(2):
            cols.append(rng.standard_normal(16) * 1e9)      # its pattern
            continue
        lo = -int(rng.randint(0, 1 << 30)) << int(rng.randint(0, 30))
        span = (1 << bits) - 1
        v = [lo + (int(rng.randint(0, 1 << 31)) * int(rng.randint(1, 1 << 31))
                   % (span + 1)) for _ in range(14)] + [lo, lo + span]
        if bits == 64:
            v = [x - lo + np.iinfo(np.int64).min for x in v]
        cols.append(np.array(v, dtype=np.int64))
    _round_trip(cols)


def test_positions_take_the_type_their_miss_fits():
    assert df.pos_dtype(1) == np.int32 == df.pos_dtype((1 << 31) - 1)
    assert df.pos_dtype(1 << 31) == np.int64
    assert df.miss(np.int32) == -(1 << 31) and df.miss(np.int64) == -(1 << 63)


# ---- (c) synthetic chains ---------------------------------------------

def _chain_tk():
    """f -> d -> c -> n. f: runs of 15 rows a d_id, every 40th row a
    d_id no d has. d: every 11th cid NULL, cids 20..24 have no c (a
    child miss in the middle of f's runs). c: every 13th nid NULL, nid 9
    has no n. cs: c's rows under sparse keys (a sorted, not a direct,
    probe table); f2 probes it from the fact. cdup: duplicate keys.
    p: a composite key. w: d's keys under wide columns (two of 40
    bits and one of 20: a second word; `huge`: a range past 63 bits,
    `x`: a double, each a word of its own). f3: f's shape with d_id in storage order (the anchor a
    device top-n needs)."""
    tk = TestKit()
    tk.must_exec("create table n (id int primary key, name varchar(16), "
                 "rid int)")
    tk.must_exec("create table c (id int primary key, nid int, seg int, "
                 "name varchar(16))")
    tk.must_exec("create table cs (id bigint primary key, nid int, "
                 "seg int)")
    tk.must_exec("create table cdup (id int, nid int)")
    tk.must_exec("create table d (id int primary key, cid int, grp int, "
                 "val int, a int, b int, csid bigint)")
    tk.must_exec("create table p (a int, b int, w int, "
                 "primary key (a, b))")
    tk.must_exec("create table w (id int primary key, a bigint, "
                 "b bigint, c int, huge bigint, x double)")
    tk.must_exec("create table f (k int primary key, d_id int, "
                 "amt decimal(10,2), q int)")
    tk.must_exec("create table f2 (k int primary key, csid bigint, "
                 "amt decimal(10,2), q int)")
    tk.must_exec("create table f3 (k int primary key, d_id int, q int)")
    tk.must_exec("insert into n values " + ",".join(
        f"({i}, 'n{i}', {i % 3})" for i in range(1, 8)))
    tk.must_exec("insert into c values " + ",".join(
        "(%d, %s, %d, 'c%d')" % (
            i, "null" if i % 13 == 0 else (9 if i % 10 == 0
                                           else i % 7 + 1), i % 4, i % 17)
        for i in range(1, 61) if not 20 <= i < 25))
    tk.must_exec("insert into cs values " + ",".join(
        "(%d, %d, %d)" % (i * 1000003, i % 7 + 1, i % 4)
        for i in range(1, 61)))
    tk.must_exec("insert into cdup values " + ",".join(
        f"({i % 30 + 1}, {i % 7 + 1})" for i in range(60)))
    tk.must_exec("insert into d values " + ",".join(
        "(%d, %s, %d, %d, %d, %d, %d)" % (
            i, "null" if i % 11 == 0 else (i * 7) % 60 + 1, i % 7,
            (i * 37) % 1000, i % 5, i % 3, ((i * 7) % 70 + 1) * 1000003)
        for i in range(1, 201)))
    tk.must_exec("insert into p values " + ",".join(
        f"({a}, {b}, {a * 10 + b})" for a in range(5) for b in range(3)
        if (a, b) != (4, 2)))
    tk.must_exec("insert into w values " + ",".join(
        "(%d, %d, %d, %d, %d, %s)" % (
            i, (i * 5497558139) % (1 << 40) - (1 << 39),
            (i * 7297558133) % (1 << 40), (i * 7919) % (1 << 20),
            (-1) ** i * ((1 << 62) + i), i / 4.0)
        for i in range(1, 201)))
    rng = np.random.RandomState(11)
    frows, f2rows = [], []
    for k in range(3000):
        row = (rng.randint(1, 99999) / 100.0, rng.randint(0, 100))
        frows.append("(%d, %d, %s, %d)" % (
            (k, 9999 if k % 40 == 7 else k // 15 + 1) + row))
        f2rows.append("(%d, %d, %s, %d)" % (
            (k, (k // 15 % 70 + 1) * 1000003) + row))
    tk.must_exec("insert into f values " + ",".join(frows))
    tk.must_exec("insert into f2 values " + ",".join(f2rows))
    tk.must_exec("insert into f3 values " + ",".join(
        f"({k}, {k // 15 + 1}, {(k * 31) % 100})" for k in range(3000)))
    return tk


@pytest.fixture(scope="module")
def tkc():
    return _chain_tk()


_AG = "sum(f.amt), count(*), min(f.q), max(f.q)"
_FDC = "from f, d, c where f.d_id = d.id and d.cid = c.id"
_FW = "from f, w where f.d_id = w.id"
# case -> (sql, what the fold counter has to grow by in one execution[,
# and of the pack outcomes, where not the one root packed in one word])
_SYN = {
    # NULL d.cid, cids without a c row: misses in the middle of a run
    "child_payload_groups":
        (f"select c.seg, {_AG} {_FDC} group by c.seg order by c.seg",
         {"mask_folded": 1, "folded": 1}),
    "three_deep_filtered_leaf":
        (f"select n.name, {_AG} {_FDC} and c.nid = n.id and n.rid = 1 "
         "group by n.name order by n.name".replace(
             "from f, d, c", "from f, d, c, n"),
         {"mask_folded": 1, "folded": 2}),
    # n's position is a function of c's: decoded from it on the host
    "position_under_position":
        (f"select c.id, c.name, n.name, {_AG} {_FDC} and c.nid = n.id "
         "group by c.id, c.name, n.name order by c.id".replace(
             "from f, d, c", "from f, d, c, n"),
         {"mask_folded": 1, "folded": 2}),
    "child_filter_rejects_every_row":
        (f"select c.seg, {_AG} {_FDC} and c.seg = 77 "
         "group by c.seg order by c.seg",
         {"mask_folded": 1, "folded": 1}),
    "child_column_in_post_filter":
        (f"select d.grp, {_AG} {_FDC} and c.seg < f.q "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "folded": 1}),
    "child_column_in_aggregate":
        (f"select d.grp, sum(c.seg + f.q), count(*) {_FDC} "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "folded": 1}),
    "sparse_child_keys":
        (f"select cs.seg, {_AG} from f, d, cs where f.d_id = d.id "
         "and d.csid = cs.id group by cs.seg order by cs.seg",
         {"mask_folded": 1, "folded": 1}),
    "sparse_root_keys":
        ("select n.name, sum(f2.amt), count(*) from f2, cs, n "
         "where f2.csid = cs.id and cs.nid = n.id and cs.seg < 3 "
         "group by n.name order by n.name",
         {"mask_folded": 1, "folded": 1}),
    "semi_child":
        (f"select d.grp, {_AG} from f, d where f.d_id = d.id and exists "
         "(select 1 from c where c.id = d.cid and c.seg = 1) "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "folded": 1}, {}),
    "anti_child_declined":
        (f"select d.grp, {_AG} from f, d where f.d_id = d.id and "
         "not exists (select 1 from c where c.id = d.cid and c.seg = 1) "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "declined_anti": 1}),
    "left_child_declined":
        (f"select c.seg, {_AG} from f join d on f.d_id = d.id "
         "left join c on d.cid = c.id group by c.seg order by c.seg",
         {"mask_folded": 1, "declined_left": 1}),
    "composite_key_declined":
        (f"select p.w, {_AG} from f, d, p where f.d_id = d.id "
         "and d.a = p.a and d.b = p.b group by p.w order by p.w",
         {"mask_folded": 1, "declined_composite_key": 1}),
    "topn_orders_by_child_column":
        (f"select c.id, c.seg, sum(f.amt) s {_FDC} "
         "group by c.id, c.seg order by c.seg desc, c.id limit 5",
         {"mask_folded": 1, "folded": 1}),
    # d's position is all the program reads of d: the table of positions
    "topn_orders_by_root_column":
        (f"select f.d_id, d.val, c.name, sum(f.amt) s {_FDC} "
         "group by f.d_id, d.val, c.name order by d.val desc limit 5",
         {"mask_folded": 1, "folded": 1}, {}),
    # ... and with c.seg read at fact width the position is a field of
    # the word, while the ordering column stays at d's width
    "topn_root_column_beside_the_word":
        ("select f3.d_id, d.val, c.name, sum(f3.q) s from f3, d, c "
         "where f3.d_id = d.id and d.cid = c.id and c.seg < f3.q "
         "group by f3.d_id, d.val, c.name order by d.val desc limit 5",
         {"mask_folded": 1, "folded": 1}),
    "packed_direct_root_own_and_child_columns":
        (f"select d.grp, sum(d.val + c.seg + f.q), count(*) {_FDC} "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "folded": 1}),
    "packed_sorted_root":
        ("select n.name, sum(f2.amt + cs.seg), count(*) from f2, cs, n "
         "where f2.csid = cs.id and cs.nid = n.id "
         "group by n.name order by n.name",
         {"mask_folded": 1, "folded": 1}),
    # c.nid is NULL in every 13th row of c
    "nullable_folded_column":
        (f"select d.grp, count(c.nid), sum(c.nid + f.q), count(*) {_FDC} "
         "group by d.grp order by d.grp",
         {"mask_folded": 1, "folded": 1}),
    "spill_to_a_second_word":
        (f"select f.q, sum(w.a + f.q), sum(w.b), min(w.c), count(*) {_FW} "
         "group by f.q order by f.q",
         {"mask_folded": 1}, {"packed": 1, "packed_spill": 1}),
    # a field with no room beside the miss bit: a word of its own
    "range_past_63_bits_is_a_word":
        (f"select f.q, min(w.huge), max(w.c), count(*) {_FW} "
         "group by f.q order by f.q",
         {"mask_folded": 1}, {"packed": 1, "packed_spill": 1}),
    "double_column_is_a_word":
        (f"select f.q, sum(w.x), max(w.c), count(*) {_FW} "
         "group by f.q order by f.q",
         {"mask_folded": 1}, {"packed": 1, "packed_spill": 1}),
}


# the tables a case binds for a 32-bit gather, where not the root's one:
# a declined dimension's own `lut` beside it; two words of 40 bits and
# more stay int64, a double or a 64-bit range beside a narrow word 0 too
_SYN_WORD32 = {"anti_child_declined": 2, "left_child_declined": 2,
               "composite_key_declined": 2, "spill_to_a_second_word": 0}


@pytest.mark.parametrize("policy", ["runs", "scatter"])
@pytest.mark.parametrize("case", sorted(_SYN))
def test_synthetic_chain_vs_host(tkc, case, policy):
    sql, want, pack = (_SYN[case] + ({"packed": 1},))[:3]
    want = dict(want, **pack)
    if _SYN_WORD32.get(case, 1):
        want["word32"] = _SYN_WORD32.get(case, 1)
    al._FORCE_SEGMENT_IMPL = "runs" if policy == "runs" else None
    try:
        before = _counts()
        dev = _dev_vs_host(tkc, sql)
        grown = _grown(before)
    finally:
        al._FORCE_SEGMENT_IMPL = None
    grown.pop("build", None)
    grown.pop("cache_hit", None)
    assert grown == want
    assert (len(dev) == 0) == (case == "child_filter_rejects_every_row")


def test_sparse_keys_take_the_sorted_table(tkc, kinds):
    """The two sparse cases really probe a sorted table: once at fact
    width with the composed words in the row order's place (the root),
    once on the host only (the child)."""
    tkc.domain.copr._kernel_cache.clear()
    _dev_vs_host(tkc, _SYN["sparse_root_keys"][0])
    root = kinds[-1][3][2][0]
    assert "sk" in root and "pk" in root and "valid" not in root and \
        "ord" not in root and "lo" not in root
    _dev_vs_host(tkc, _SYN["packed_sorted_root"][0])
    assert set(kinds[-1][3][2][0]) == set(root)
    _dev_vs_host(tkc, _SYN["sparse_child_keys"][0])
    assert "lo" in kinds[-1][3][2][0] and kinds[-1][3][2][1] == {"cols": {}}


def test_topn_keeps_its_root_width_column(tkc, runs_impl, kinds):
    """The top-n's ordering column is gathered at bucket width through
    the groups' positions: it stays a column of d's beside the word,
    and nothing else does."""
    tkc.domain.copr._kernel_cache.clear()
    _dev_vs_host(tkc, _SYN["topn_root_column_beside_the_word"][0], runs=3)
    (kind, param, build, shapes) = kinds[-1]
    assert kind == "posruns" and param[2] is not None     # a device top-n
    root = shapes[2][0]
    assert "pk" in root and len(root["cols"]) == 1
    assert {t[0] for t in build[0][6][0]["pack"]} == {"pos", "col"}
    wide = [p for p in _wide_gathers(build, shapes) if p.startswith("[2]")]
    assert wide == ["[2][0]['pk'][0]"]


@pytest.mark.parametrize("case,words,fields,wtypes", [
    # two fields of 40 bits: neither word fits 31, both stay int64
    ("spill_to_a_second_word", 2, 3, ("int64", "int64")),
    ("nullable_folded_column", 1, 3, ("int32",)),
    ("range_past_63_bits_is_a_word", 2, 2, ("int32", "int64")),
    ("double_column_is_a_word", 2, 2, ("int32", "int64"))])
def test_words_and_their_gathers(tkc, kinds, case, words, fields, wtypes):
    """A spill is a second key-addressed table and a second gather,
    never more than the gathers of the table of positions and of the
    columns read through it; a null mask is a field; a word is gathered
    in the type that holds it."""
    tkc.domain.copr._kernel_cache.clear()
    _dev_vs_host(tkc, _SYN[case][0])
    (_kind, _param, build, shapes) = kinds[-1]
    root = shapes[2][0]
    census = [(p, b) for p, b in _wide_census(build, shapes)
              if p.startswith("[2]")]
    wide = [p for p, _ in census]
    assert tuple(t.dtype.name for t in root["pk"]) == wtypes == \
        build[0][6][0]["words"]
    assert [b for _, b in census] == [np.dtype(t).itemsize for t in wtypes]
    assert len(root["pk"]) == words == len(wide) <= fields
    assert not root["cols"] and "lut" not in root
    assert root["fshift"].shape == (fields,) == root["fmask"].shape
    assert {t[2] for t in build[0][6][0]["pack"]} == set(range(words))


def test_a_word_that_outgrows_31_bits_takes_a_second_program(kinds):
    """One commit widens a field past 31 bits between two statements:
    the root's word goes from int32 to int64, the statement builds the
    program that gathers it and answers as the host does."""
    tk = TestKit()
    tk.must_exec("create table dd (id int primary key, v bigint, g int)")
    tk.must_exec("create table f (k int primary key, d_id int, q int)")
    tk.must_exec("insert into dd values " + ",".join(
        f"({i}, {i * 1000}, {i % 5})" for i in range(1, 101)))
    tk.must_exec("insert into f values " + ",".join(
        f"({k}, {k % 110 + 1}, {k % 9})" for k in range(1500)))
    sql = ("select dd.g, sum(dd.v + f.q), count(*) from f, dd "
           "where f.d_id = dd.id group by dd.g order by dd.g")
    before = _counts()
    _dev_vs_host(tk, sql)
    assert _grown(before)["word32"] == 1
    assert kinds[-1][3][2][0]["pk"][0].dtype == np.int32
    phase.reset()
    tk.must_query(sql)
    assert phase.snap().get("kernel_builds", 0) == 0
    tk.must_exec(f"update dd set v = {1 << 40} where id = 7")
    before, built = _counts(), len(kinds)
    wide = _dev_vs_host(tk, sql)
    assert "word32" not in _grown(before) and len(kinds) == built + 1
    assert [t.dtype for t in kinds[-1][3][2][0]["pk"]] == [np.int64]
    assert kinds[-1][2][0][6][0]["words"] == ("int64",)
    assert sum(int(r[1]) for r in wide) > 1 << 40
    phase.reset()
    tk.must_query(sql)
    assert phase.snap().get("kernel_builds", 0) == 0


def test_the_words_type_keys_the_kernel_cache(tkc, kinds):
    """The type a word is gathered in is program text: the same
    statement over the same shapes with another type is another key."""
    tkc.domain.copr._kernel_cache.clear()
    _dev_vs_host(tkc, _SYN["child_payload_groups"][0])
    (a, k), = [k[2] for k in kinds]
    lay = dict(a[6][0])
    assert lay["words"] == ("int32",)
    keys = set()
    copr = tkc.domain.copr
    for words in (("int32",), ("int64",)):
        lay["words"] = words
        plan = a[0]
        fact = copr.engine.table(plan.fact_dag.table_info)
        metas = [{"tbl": copr.engine.table(d.dag.table_info),
                  "probe": probe.ProbeTable.always_miss(1)}
                 for d in plan.dims]
        keys.add(pl._fused_cache_key(
            copr, plan, fact, metas, a[1], tuple(a[3]), tuple(a[4]),
            tuple(a[5]), a[7], a[8], fold=k.get("fold"),
            dim_layouts=(lay,) + tuple(a[6][1:])))
    assert len(keys) == 2


def test_positions_past_31_bits_keep_the_int64_table(monkeypatch, kinds):
    """No table of this suite has 2**31 rows: with the edge moved under
    d's 200 the `lut`, the folded table and the word stay int64 (the
    program of before), nothing is counted as bound for a 32-bit gather
    and the answers are the host's."""
    monkeypatch.setattr(df, "_NARROW_BITS", 4)
    tk = _chain_tk()
    before = _counts()
    for case in ("topn_orders_by_root_column", "child_payload_groups",
                 "left_child_declined"):
        _dev_vs_host(tk, _SYN[case][0])
        root = kinds[-1][3][2][0]
        assert [t.dtype for t in root.get("pk", [root.get("lut")])] == \
            [np.int64]
    assert "word32" not in _grown(before)


@pytest.mark.parametrize("join,rows", [
    ("left join e on f.d_id = e.id", 1),
    ("where not exists (select 1 from e where e.id = f.d_id)", 1),
    ("join e on f.d_id = e.id", 0)])
def test_a_root_of_no_rows(kinds, join, rows):
    """An empty dimension under a left or an anti join is the one-slot
    always-miss table, as narrow as any other table of positions; under
    an inner join the statement has no row and no program."""
    tk = TestKit()
    tk.must_exec("create table e (id int primary key, v int)")
    tk.must_exec("create table f (k int primary key, d_id int, q int)")
    tk.must_exec("insert into f values " + ",".join(
        f"({k}, {k % 7}, {k % 9})" for k in range(300)))
    before = _counts()
    dev = _dev_vs_host(tk, f"select count(*), sum(f.q) from f {join}")
    assert (int(dev[0][0]) == 300) == bool(rows)
    assert _grown(before).get("word32", 0) == rows == len(kinds)
    if rows:
        lut = kinds[-1][3][2][0]["lut"]
        assert lut.dtype == np.int32        # one slot, padded to a bucket


def test_duplicate_child_keys_decline(tkc):
    """No unique position to resolve at the parent's width: the fold is
    declined with the fused statement, and the host answers."""
    before = _counts()
    sql = (f"select d.grp, {_AG} from f, d, cdup where f.d_id = d.id "
           "and d.cid = cdup.id group by d.grp order by d.grp")
    tkc.domain.copr.use_device = True
    dev = tkc.must_query(sql).rows
    assert "duplicated" in tkc.domain.last_fused_reason
    assert _grown(before) == {"declined_child_ineligible": 1}
    assert dev == _host(tkc, sql) and len(dev) == 7


def test_group_straddles_two_partitions(tkc, runs_impl, kinds):
    """1000-row partitions cut f's runs of 15: both halves' partials
    merge, and the fold is built once for the three."""
    copr = tkc.domain.copr
    old = copr.device_rows
    copr.device_rows = 1000
    before = _counts()
    try:
        _dev_vs_host(tkc, (
            f"select f.d_id, d.val, c.name, n.name, {_AG} {_FDC} "
            "and c.nid = n.id group by f.d_id, d.val, c.name, n.name "
            "order by f.d_id").replace("from f, d, c", "from f, d, c, n"))
    finally:
        copr.device_rows = old
    grown = _grown(before)
    assert grown.get("build", 0) + grown.get("cache_hit", 0) == 1
    assert {k[0] for k in kinds} == {"posruns"}
    # d's position is the only run key: c's and n's are decoded from it
    assert kinds[0][1][1] == (0,)


def test_lowering_change_between_blocks_reuploads(monkeypatch, kinds):
    """Positions scattered over storage order: the first block's partials
    exceed the degrade limit, the shape is pinned to "sorted", and the
    statement uploads the group items' columns it had left out."""
    monkeypatch.setattr(al, "RUNS_DEGRADE_MIN", 8)
    al._FORCE_SEGMENT_IMPL = "runs"
    try:
        tk = TestKit()
        tk.must_exec("create table c (id int primary key, seg int)")
        tk.must_exec("create table d (id int primary key, cid int, "
                     "val int)")
        tk.must_exec("create table f (k int primary key, d_id int, q int)")
        tk.must_exec("insert into c values " + ",".join(
            f"({i}, {i % 5})" for i in range(1, 41)))
        tk.must_exec("insert into d values " + ",".join(
            f"({i}, {i % 40 + 1}, {i * 3})" for i in range(1, 101)))
        rng = np.random.RandomState(2)
        tk.must_exec("insert into f values " + ",".join(
            f"({k}, {rng.randint(1, 101)}, {k % 9})" for k in range(800)))
        sql = ("select d.val, c.seg, count(*), sum(f.q) from f, d, c "
               "where f.d_id = d.id and d.cid = c.id "
               "group by d.val, c.seg order by d.val")
        before = _counts()
        _dev_vs_host(tk, sql, runs=2)
        grown = _grown(before)
    finally:
        al._FORCE_SEGMENT_IMPL = None
    # the first execution's second upload and the second execution's
    # only one (the host twin's run counts nothing)
    assert grown["packed"] == 2 and grown["build"] == 1
    assert [k[0] for k in kinds] == ["posruns", "sort"]
    # the positions are the keys: d's table of positions and no column
    first, second = kinds[0][3][2][0], kinds[1][3][2][0]
    assert not first["cols"] and "lut" in first and "pk" not in first
    # the group items' values at fact width: a second upload, of the
    # word that holds both (another field set, another table)
    assert not second["cols"] and "lut" not in second
    assert len(second["pk"]) == 1 and second["fshift"].shape == (2,)
    assert {t[:2] for t in kinds[1][2][0][6][0]["pack"]} == \
        {("col", c.idx) for c in kinds[1][2][0][0].group_items}


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a mesh")
@pytest.mark.parametrize("case", ["three_deep_filtered_leaf",
                                  "child_column_in_post_filter",
                                  "nullable_folded_column",
                                  "packed_sorted_root"])
def test_mesh_takes_the_folded_tables(case):
    """Broadcast dimensions ride the mesh program folded: one program
    over the whole fact, the same answer as the host's."""
    tk = _chain_tk()
    tk.must_exec("set tidb_mpp_min_rows = 0")
    hits = tk.domain.metrics.get("fused_pipeline_mpp_hit", 0)
    before = _counts()
    _dev_vs_host(tk, _SYN[case][0])
    assert tk.domain.metrics.get("fused_pipeline_mpp_hit", 0) == hits + 1
    grown = _grown(before)
    assert grown.get("folded", 0) >= 1 and grown.get("packed") == 1
    assert grown.get("word32") == 1
    # the composed table went up replicated, in the lut's place, as
    # narrow as the host holds it
    store = tk.domain.copr._dev_store
    pk = [k for k in store._entries
          if isinstance(k[1], tuple) and k[1][0] == "pk"]
    assert pk and all(store._spec_of[k] == "replicated" for k in pk)
    assert all(store.get(k).dtype == np.int32 for k in pk)
    assert not [k for k in store._entries if k[1] in ("lut", "ord")]


# ---- (d) MVCC ----------------------------------------------------------

_MV = (f"select n.name, {_AG} {_FDC} and c.nid = n.id "
       "group by n.name order by n.name").replace("from f, d, c",
                                                  "from f, d, c, n")


def test_chain_versions_key_the_fold():
    """A commit anywhere in the chain is seen by the next execution."""
    tk = _chain_tk()
    base = _dev_vs_host(tk, _MV)
    tk.must_exec("update c set nid = 3 where id = 8")       # the middle
    moved = _dev_vs_host(tk, _MV)
    assert moved != base
    tk.must_exec("delete from d where id = 2")              # the root
    fewer = _dev_vs_host(tk, _MV)
    assert sum(int(r[2]) for r in fewer) < sum(int(r[2]) for r in moved)
    tk.must_exec("insert into c values (21, 2, 1, 'new')")  # a missing key
    more = _dev_vs_host(tk, _MV)
    assert sum(int(r[2]) for r in more) > sum(int(r[2]) for r in fewer)
    tk.must_exec("insert into n values (9, 'n9', 0)")       # the leaf
    assert len(_dev_vs_host(tk, _MV)) == len(more) + 1


def test_uncommitted_dimension_write_is_its_writers_alone():
    tk = _chain_tk()
    base = _dev_vs_host(tk, _MV)
    other = TestKit(domain=tk.domain)
    tk.must_exec("begin")
    try:
        tk.must_exec("update c set nid = 3 where id = 8")
        tk.domain.copr.use_device = True
        mine = tk.must_query(_MV).rows
        assert mine == _host(tk, _MV) and mine != base
        assert other.must_query(_MV).rows == base
    finally:
        tk.must_exec("rollback")
    before = _counts()
    assert _dev_vs_host(tk, _MV) == base
    assert _grown(before).get("build", 0) == 0      # nothing was cached


def test_dirty_fact_overlay_bypasses_the_fold_cache():
    """Only the fact is dirty: the statement stays on the device, builds
    its folds and leaves none behind."""
    tk = _chain_tk()
    base = _dev_vs_host(tk, _MV)
    tk.must_exec("begin")
    try:
        tk.must_exec("insert into f values (90001, 1, 5.00, 1)")
        before = _counts()
        got = _dev_vs_host(tk, _MV)
        grown = _grown(before)
        assert grown.get("build") == 1 and "cache_hit" not in grown
        assert sum(int(r[2]) for r in got) == \
            sum(int(r[2]) for r in base) + 1
    finally:
        tk.must_exec("rollback")
    before = _counts()
    assert _dev_vs_host(tk, _MV) == base
    assert _grown(before).get("cache_hit") == 1


def test_older_snapshot_does_not_see_a_later_fold():
    from tidb_tpu.types.time_types import micros_to_str
    tk = _chain_tk()
    base = _dev_vs_host(tk, _MV)
    time.sleep(0.05)
    mid = micros_to_str(int(time.time() * 1e6), 6)
    time.sleep(0.05)
    tk.must_exec("update c set nid = 3 where id = 8")
    tk.must_exec("delete from d where id = 2")
    now = _dev_vs_host(tk, _MV)
    assert now != base
    asof = _MV.replace("from f, d, c, n", " ".join(
        ["from"] + [f"{t} as of timestamp '{mid}'," for t in "fdc"] +
        [f"n as of timestamp '{mid}'"]))
    tk.domain.copr.use_device = True
    assert tk.must_query(asof).rows == base
    assert _dev_vs_host(tk, _MV) == now


# ---- (e) a second execution builds nothing -----------------------------

@pytest.mark.parametrize("q", ["q3", "q5", "q10", "q18"])
def test_second_execution_builds_nothing(tk, runs_impl, q):
    tk.domain.copr.use_device = True
    for _ in range(2):          # the second run may rebuild with what
        tk.must_query(_q(q))    # the first learned (bucket, top-n cut)
    before = _counts()
    phase.reset()
    tk.must_query(_q(q))
    snap = phase.snap()
    grown = _grown(before)
    assert "build" not in grown and grown["cache_hit"] >= 1
    # one `packed` a packed root an execution; the words themselves are
    # the fold's, found with it
    assert grown.get("packed", 0) == _PACKED[q]
    assert grown.get("word32", 0) == _WORD32[q]
    assert not [k for k in grown if k.startswith(("declined_pack",
                                                  "packed_spill"))]
    assert snap.get("upload_bytes", 0) == 0
    assert snap.get("dispatches", 0) <= 2
    assert snap.get("kernel_builds", 0) == 0


def test_bind_span_carries_fold_counts(tk):
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    try:
        tk.domain.copr.use_device = True
        tk.must_query(_q("q5"))
        rows = tk.must_query(
            "select attrs from information_schema.tidb_trace_events "
            "where span = 'bind' and attrs like '%folds%'").rows
        packed = tk.must_query(
            "select attrs from information_schema.tidb_trace_events "
            "where span = 'bind' and attrs like '%packed_roots%'").rows
    finally:
        tk.must_exec("set tidb_tpu_trace_sample_rate = 0")
    assert rows and "fold_builds" in rows[-1][0]
    assert [r for r in packed if "packed_roots=2" in r[0]
            and "word32=2" in r[0]], packed


# ---- (f) a composite key's table of buckets (PR 40) --------------------
#
# A dimension joined on several columns whose packed span is no direct
# table is probed through buckets on one of them (`probe._bucket_table`):
# the small tables against a plain join in Python, the census of q9's
# program, and the first-set statements' programs left as they were.

_BQ = ("select count(*), sum(fb.q), sum(ps.w), min(ps.w) from fb, ps "
       "where fb.a = ps.a and fb.b = ps.b")
_BQ3 = _BQ + " and fb.c = ps.c"


def _ps_rows(case):
    """-> [(a, b, c, w)] of the dimension: unique on (a, b) and on
    (a, b, c), spans of 300 x 1000 (x 41) so that no packed key is a
    direct table at these sizes (`direct_span`'s floor is 4,096
    slots)."""
    rng = np.random.RandomState(40)
    rows = []
    for a in range(1, 301):
        if a % 7 == 0:
            continue                    # a bucket of empty slots alone
        k = 1 + a % 4                   # 1 to 4 rows a bucket
        if case == "deep_bucket" and a == 5:
            k = 12                      # more slots than the search's steps
        for b in sorted(rng.choice(1000, k, replace=False)):
            rows.append((a, int(b), (a + int(b)) % 41, len(rows) + 1))
    if case == "sparse_buckets":
        # a's span 30 times as wide: past four slots a row, and b's
        # values shared by more rows than a bucket may hold
        rows = [(a * 30, b % 50, c, w) for a, b, c, w in rows]
        rows = list({(a, b): (a, b, c, w) for a, b, c, w in rows}.values())
    return rows


def _fb_rows(ps):
    """The fact: every dimension row twice, its bucket under a `b` that
    row lacks (the span's lowest and highest among them: what an empty
    slot must not answer to), components out of range on either side
    and NULL."""
    amax = max(r[0] for r in ps)
    out = []
    for a, b, c, _w in ps:
        out += [(a, b, c), (a, b, c), (a, (b + 1) % 1000, c), (a, 0, c),
                (a, 999, c), (a, b, (c + 1) % 41)]
    out += [(0, 5, 1), (-5, 5, 1), (amax + 1, 5, 1), (10 ** 9, 5, 1),
            (7, 5, 1), (14, 0, 0), (1, -1, 1), (1, 1000, 1),
            (1, 10 ** 9, 1), (1, 5, -1), (1, 5, 41), (None, 5, 1),
            (1, None, 1), (1, 5, None), (None, None, None)]
    return out


def _bucket_tk(case="two_columns", key="a, b"):
    tk = TestKit()
    tk.must_exec("create table ps (a int, b int, c int, w int" +
                 (f", primary key ({key}))" if key else ")"))
    tk.must_exec("create table fb (k int primary key, a int, b int, "
                 "c int, q int)")
    ps = _ps_rows(case)
    tk.must_exec("insert into ps values " + ",".join(
        "(%d, %d, %d, %d)" % r for r in ps))
    fb = _fb_rows(ps)
    sql = lambda v: "null" if v is None else str(v)     # noqa: E731
    tk.must_exec("insert into fb values " + ",".join(
        "(%d, %s, %s, %s, %d)" % (k, sql(a), sql(b), sql(c), k % 97)
        for k, (a, b, c) in enumerate(fb)))
    return tk, ps, fb


def _plain_join(ps, fb, ncols):
    """count(*), sum(q), sum(w), min(w) of the inner join, in Python."""
    table = {r[:ncols]: r[3] for r in ps}
    hits = [(k % 97, table[r[:ncols]]) for k, r in enumerate(fb)
            if None not in r[:ncols] and r[:ncols] in table]
    return (len(hits), sum(q for q, _ in hits), sum(w for _, w in hits),
            min(w for _, w in hits))


def _probe_modes_of(tk, sql):
    before = {k: c.value for k, c in mu.FUSED_DIM_PROBE._children.items()}
    tk.domain.copr.use_device = True
    rows = tk.must_query(sql).rows
    grown = {k[1]: c.value - before.get(k, 0)
             for k, c in mu.FUSED_DIM_PROBE._children.items()
             if c.value != before.get(k, 0)}
    return rows, grown


# case -> (key columns, statement, mode, (bucket column, slots a bucket))
_BUCKET = {
    "two_columns": ("a, b", _BQ, "bucket", (0, 4)),
    # the order the key's columns are named in does not pick the column
    "two_columns_b_first": ("b, a", _BQ.replace(
        "fb.a = ps.a and fb.b = ps.b", "fb.b = ps.b and fb.a = ps.a"),
        "bucket", (1, 4)),
    "three_columns": ("a, b, c", _BQ3, "bucket", (0, 4)),
    # one `a` with 12 rows of 857 (the search has 10 steps), and no
    # other column whose table keeps the bounds
    "deep_bucket": ("a, b", _BQ, "search", None),
    # a's 8,970 values x 4 slots and b's 50 x 19: neither keeps them
    "sparse_buckets": ("a, b", _BQ, "search", None),
}


@pytest.mark.parametrize("case", sorted(_BUCKET))
def test_bucket_probe_equals_a_plain_join(kinds, case):
    key, sql, mode, bucket = _BUCKET[case]
    tk, ps, fb = _bucket_tk(case.replace("_b_first", ""), key)
    rows, grown = _probe_modes_of(tk, sql)
    assert tk.domain.last_fused_reason is None
    assert grown == {mode: 1}
    want = _plain_join(ps, fb, 3 if sql is _BQ3 else 2)
    assert tuple(int(v) for v in rows[0]) == want
    assert want[0] >= 2 * len(ps) and _host(tk, sql) == rows
    (_kind, _param, build, shapes), = kinds
    da, layout = shapes[2][0], build[0][6][0]
    assert layout.get("bucket") == bucket
    assert ("bt" in da, "sk" in da) == (mode == "bucket", mode == "search")
    if bucket is not None:
        # a row a bucket: its slots' other keys, then their positions;
        # -1 and the miss where a slot is empty
        bcol, slots = bucket
        meta = pl._dim_sort_meta(tk.domain.copr, build[0][0].dims[0],
                                 tk.domain.columnar.table(
                                     tk.domain.infoschema().table_by_name(
                                         "test", "ps")), None)
        rows_ = meta["probe"].table.reshape(-1, 2 * slots)
        bk, bp = rows_[:, :slots], rows_[:, slots:]
        assert len(rows_) == max(r[0] for r in ps)
        assert int((bk >= 0).sum()) == int((bp < meta["n"]).sum()) \
            == len(ps)
        assert (bk[bp == meta["n"]] == -1).all()
        assert rows_.dtype == np.int32 and da["bt"].dtype == np.int32
        assert da["bt"].shape[0] % (2 * slots) == 0     # whole rows
        assert meta["probe"].pack[2][bcol] == 0     # what the lane packs


def test_duplicate_composite_keys_are_still_refused():
    """No primary key and one (a, b) twice: not a unique build side,
    whatever table the keys would take; the statement is answered off
    the fused path."""
    tk, ps, fb = _bucket_tk(key=None)
    tk.must_exec("insert into ps values (%d, %d, 0, 9999)" % ps[3][:2])
    rows, grown = _probe_modes_of(tk, _BQ)
    assert grown == {}
    assert "duplicated" in tk.domain.last_fused_reason
    assert rows == _host(tk, _BQ)
    again = sum(r[:2] == ps[3][:2] for r in fb)
    assert int(rows[0][0]) == _plain_join(ps, fb, 2)[0] + again


def test_a_fifth_row_in_a_bucket_rebuilds_the_table_for_the_next_snapshot(
        kinds):
    """A commit gives one `a` a fifth `b`: the next statement's table
    has five slots a bucket (another program), a statement that reads
    as of before the commit keeps four and its answer."""
    from tidb_tpu.types.time_types import micros_to_str
    tk, ps, fb = _bucket_tk()
    base, _ = _probe_modes_of(tk, _BQ)
    assert kinds[-1][2][0][6][0]["bucket"] == (0, 4)
    time.sleep(0.05)
    mid = micros_to_str(int(time.time() * 1e6), 6)
    time.sleep(0.05)
    a = next(r[0] for r in ps if r[0] % 4 == 3)       # a full bucket
    b = next(b for b in (0, 999) if (a, b) not in {r[:2] for r in ps})
    tk.must_exec(f"insert into ps values ({a}, {b}, 0, 5000)")
    now, grown = _probe_modes_of(tk, _BQ)
    assert grown == {"bucket": 1}
    assert kinds[-1][2][0][6][0]["bucket"] == (0, 5)
    want = _plain_join(ps + [(a, b, 0, 5000)], fb, 2)
    assert tuple(int(v) for v in now[0]) == want and now != base
    asof = _BQ.replace("from fb, ps", f"from fb as of timestamp '{mid}', "
                       f"ps as of timestamp '{mid}'")
    old, grown = _probe_modes_of(tk, asof)
    assert old == base and grown == {"bucket": 1}
    assert kinds[-1][2][0][6][0]["bucket"] == (0, 4)
    assert _probe_modes_of(tk, _BQ)[0] == now


def _forget_key_tables(tk):
    """Drop the cached probe tables (`_dim_sort_meta`'s entries) and
    nothing a run has learned."""
    cache = tk.domain.copr._host_cache
    for k in [k for k in cache if isinstance(k, tuple) and
              ("dim" in k[2:3] or k[-1:] == ("dimcur",))]:
        del cache[k]


def _loops(build, shapes):
    """Loop and sort primitives of the body, and what it gathers from
    at fact width."""
    n = [0]

    def visit(e):
        n[0] += e.primitive.name in ("while", "scan", "sort")
    _walk(_body_jaxpr(build, shapes).jaxpr, visit)
    return n[0], _wide_census(build, shapes)


def test_census_q9_probes_partsupp_without_a_search(tk, runs_impl, kinds,
                                                    monkeypatch):
    """q9's program: partsupp's probe is ONE gather of its bucket's
    row of 32-bit words (four other keys, four positions), its payload
    gathered at the matching slot's position; no `valid[pos]` (the
    table holds visible rows alone and partsupp has no filter), no
    sorted keys, and fewer loops than the program that searches them."""
    build, shapes = _main_kernel(tk, kinds, _q("q9"))
    di, = [i for i, lay in enumerate(build[0][6]) if "bucket" in lay]
    assert build[0][6][di]["bucket"][1] == 4
    loops, census = _loops(build, shapes)
    mine = [(p, b) for p, b in census if p.startswith(f"[2][{di}]")]
    assert [(p.rsplit("[", 1)[1], b) for p, b in mine
            if "'cols'" not in p] == [("'bt']", 4)]
    assert [p for p, _ in mine if "'cols'" in p]        # ps_supplycost
    assert not [p for p, _ in census if "'sk'" in p or "'ord'" in p]
    monkeypatch.setattr(probe, "_bucket_table", lambda *a: None)
    _forget_key_tables(tk)
    searched, old = _loops(*_main_kernel(tk, kinds, _q("q9")))
    _forget_key_tables(tk)
    assert [p for p, _ in old if "'sk'" in p] and searched > loops


def _lowered_text(build, shapes):
    """The program's lowered text less its source locations
    (benchmarks/fold_probe_tpu.py --hlo's normalisation)."""
    import re
    a, k = build
    text = jax.jit(pl._make_pipeline_body(*a, **k)).lower(*shapes) \
        .as_text()
    return re.sub(r"loc\(.*?\)|#loc\d*( = .*)?", "", text)


@pytest.mark.parametrize("q", ["q3", "q5", "q10", "q18"])
def test_single_column_keys_keep_their_program(tk, runs_impl, kinds,
                                               monkeypatch, q):
    """The first-set join statements have no dimension on two columns:
    with the bucket form reachable or not, the host builds the same
    tables and the program's lowered text is the same, and nothing is
    counted `bucket`."""
    for _ in range(3):          # what a run learns (bucket, top-n cut)
        _rows, grown = _probe_modes_of(tk, _q(q))   # picks the next's
    assert "bucket" not in grown and "search" not in grown
    now = _lowered_text(*_main_kernel(tk, kinds, _q(q)))

    def unreachable(*a):
        raise AssertionError("a single-column key asked for buckets")
    monkeypatch.setattr(probe, "_bucket_table", unreachable)
    _forget_key_tables(tk)
    assert _lowered_text(*_main_kernel(tk, kinds, _q(q))) == now
    _forget_key_tables(tk)
