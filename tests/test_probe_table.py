"""copr/probe.py, the one owner of a probe table's form: every form's
device probe (`resolve` under jax.jit, over what `upload` filled), its
host probe where it has one and a plain Python dict agree, lane by lane;
and `signature()` moves exactly when the program's text must."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tidb_tpu.copr.dimfold as df
from tidb_tpu.chunk.device import shape_bucket
from tidb_tpu.copr import probe
from tidb_tpu.copr.probe import ProbeTable

COPR = types.SimpleNamespace(_dev_store=types.SimpleNamespace(budget=8 << 30))
K0, K1, PAY = 10, 11, 12        # column ids: the key's, a payload's


def _put(tag, arr, length, acap, fill=0, ts_keyed=False):
    out = np.full(acap, fill, dtype=arr.dtype)
    out[:length] = arr[:length]
    return jnp.asarray(out)


def _rows(form):
    """-> [key tuple] of the dimension's rows, unique."""
    if form.startswith("bucket"):
        # 1 to 4 rows an `a` of 1..300 (a seventh of them none), `b`
        # under 1000: no direct table of the packed span at this size
        rng = np.random.RandomState(45)
        rows = [(a, int(b)) for a in range(1, 301) if a % 7
                for b in sorted(rng.choice(1000, 1 + a % 4, replace=False))]
        return [r[::-1] for r in rows] if form == "bucket_col1" else rows
    if "sorted" in form:
        return [(k,) for k in range(100, 100 + 97 * 3000, 97)]  # sparse
    return [(k,) for k in range(50, 2050) if k % 5]             # dense


def _lanes(rows):
    """Probe keys (None: NULL): every row, and around them a miss
    inside the span, under `lo`, past the span, NULL and, for a key of
    two columns, either component out of range on either side."""
    los = [min(r[i] for r in rows) for i in range(len(rows[0]))]
    his = [max(r[i] for r in rows) for i in range(len(rows[0]))]
    have = set(rows)
    out = list(rows[::3]) + [rows[0], rows[-1]]
    if len(los) == 1:
        inside = next((k,) for k in range(los[0], his[0]) if (k,) not in have)
        out += [inside, (los[0] - 1,), (los[0] - 10 ** 6,), (his[0] + 1,),
                (his[0] + 10 ** 9,), (None,)]
    else:
        (a, b) = rows[5]
        inside = next((a, k) for k in range(los[1], his[1])
                      if (a, k) not in have)
        out += [inside, (a, los[1] - 1), (a, his[1] + 1), (los[0] - 1, b),
                (his[0] + 1, b), (his[0] + 10 ** 9, his[1] + 10 ** 9),
                (None, b), (a, None), (None, None)]
    return out


def _pack_lanes(lanes, pack):
    """(pv, kidx, pnm) as the pipeline's body forms them."""
    cols = [np.array([0 if r[i] is None else r[i] for r in lanes],
                     dtype=np.int64) for i in range(len(lanes[0]))]
    pnm = np.array([None in r for r in lanes])
    if pack is None:
        return cols[0], None, pnm
    los, spans, strides = pack
    pv, kidx = np.zeros(len(lanes), dtype=np.int64), []
    for c, lo, sp, st in zip(cols, los, spans, strides):
        idx = c - lo
        pnm = pnm | (idx < 0) | (idx >= sp)
        kidx.append(np.clip(idx, 0, sp - 1))
        pv = pv + kidx[-1] * st
    return pv, kidx, pnm


# case -> what differs from a plain table over all rows: `passes` (a
# fold's chain: which positions keep their hit), `wide` (the miss `n`
# past 31 bits), `exists`, `words` (a folded root's composed words)
_FORMS = {
    "direct_int32": {}, "direct_int64": {"wide": True},
    "direct_masked": {"passes": lambda p: p % 3 != 1},
    "bucket_col0": {}, "bucket_col1": {},
    "sorted": {}, "sorted_masked": {"passes": lambda p: p % 3 != 1},
    "exists_direct": {"exists": True}, "exists_sorted": {"exists": True},
    "exists_always_miss": {"exists": True, "none_pass": True},
    "words_direct": {"words": True, "passes": lambda p: p % 4 != 2},
    "words_sorted": {"words": True, "passes": lambda p: p % 4 != 2},
}


def _table(form, wide=False):
    rows = _rows(form)
    n = 1 << 31 if wide else len(rows)
    arrays = {K0 + i: (np.array([r[i] for r in rows], dtype=np.int64), None,
                       None) for i in range(len(rows[0]))}
    arrays[PAY] = (np.arange(len(rows), dtype=np.int64) * 7 - 3, None, None)
    table = ProbeTable.build(COPR, arrays, [K0 + i for i in
                                            range(len(rows[0]))],
                             np.arange(len(rows)), n)
    return rows, n, arrays, table


@pytest.mark.parametrize("form", sorted(_FORMS))
def test_every_form_resolves_as_a_dict_does(form):
    spec = _FORMS[form]
    rows, n, arrays, table = _table(form, spec.get("wide", False))
    passes = spec.get("passes") or (lambda p: True)
    want = {r: p for p, r in enumerate(rows) if passes(p)}
    masked = "passes" in spec
    if spec.get("exists"):
        keys = np.array([] if spec.get("none_pass") else
                        [r[0] for r in rows], dtype=np.int64)
        table = ProbeTable.build_exists(COPR, keys, n)
        want = {} if spec.get("none_pass") else {r: 0 for r in rows}
    elif masked:
        ok = np.array([passes(p) for p in range(len(rows))] + [False])
        src = table.positions
        table = table.with_positions(
            np.where(ok[np.minimum(src, n)], src, n).astype(src.dtype))
    assert table.form == ("sorted" if "sorted" in form else
                          "bucket" if "bucket" in form else "direct")
    if table.form == "bucket":
        assert table.bucket == (int(form[-1]), 4)
    if form.startswith("direct") or form == "exists_direct":
        assert table.table.dtype == (np.int64 if spec.get("wide")
                                     else np.int32)
    pack = None
    if spec.get("words"):
        fold = df.Fold(0, (), table.positions, {},
                       [{"n": n, "arrays": arrays}])
        pack = fold.packed((("pos",), ("col", 7, 0, PAY)))

    lanes = _lanes(rows)
    pv, kidx, pnm = _pack_lanes(lanes, table.pack)
    da = {"cols": {}}
    # (`valid` is as long as the dimension: not one of 2**31 rows)
    valid = None if table.exists or masked or spec.get("wide") \
        else np.ones(len(rows), bool)
    layout = table.upload(da, _put, shape_bucket(n), valid, pack)
    assert layout["form"] == table.form and \
        layout["exists"] == bool(spec.get("exists"))
    assert ("valid" in da) == (valid is not None)
    dcap = shape_bucket(n)

    @jax.jit
    def run(da, pv, kidx, pnm):
        got, hit = probe.resolve(da, layout, pv, kidx, pnm, n,
                                 table.n_sorted, dcap, masked)
        if pack is None:
            return got, hit, got
        fields = [df.unpack_field(got[wi], da["fshift"][fi],
                                  da["fmask"][fi], da["flo"][fi], dt)
                  for fi, (_k, _i, wi, dt) in enumerate(layout["pack"])]
        return fields[0], hit & (got[0] >= 0) & ~pnm, fields[1]
    pos, hit, payload = (np.asarray(x) for x in run(da, pv, kidx, pnm))

    exp_hit = np.array([r in want for r in lanes])
    exp_pos = np.array([want.get(r, 0) for r in lanes])
    assert exp_hit.sum() >= len(want) // 5 and (~exp_hit).sum() >= 5
    assert (hit == exp_hit).all(), [r for r, h, e in
                                    zip(lanes, hit, exp_hit) if h != e]
    assert (pos[exp_hit] == exp_pos[exp_hit]).all()
    assert (pos >= 0).all() and (pos < dcap).all()   # a miss gathers safely
    if pack is not None:
        assert (payload[exp_hit] == arrays[PAY][0][exp_pos[exp_hit]]).all()
    if table.form == "bucket":
        with pytest.raises(NotImplementedError, match="bucket"):
            table.host_probe(pv, pnm)
    else:
        hpos, hhit = table.host_probe(pv, pnm)
        assert (hhit == exp_hit).all()
        assert (hpos[exp_hit] == exp_pos[exp_hit]).all()
        assert (hpos >= 0).all() and (hpos < n).all()


def test_signature_moves_when_the_program_text_does():
    def direct(keys, n=None):
        keys = np.asarray(keys, dtype=np.int64)
        return ProbeTable.build(COPR, {K0: (keys, None, None)}, [K0],
                                np.arange(len(keys)), n or len(keys))
    a = direct(range(100, 200))
    # values alone: the same rows in another order, another `lo`, a
    # fold's positions in the table's place
    same = [direct(range(199, 99, -1)), direct(range(7, 107)),
            a.with_positions(np.where(a.table % 2, a.table, 100)
                             .astype(a.table.dtype))]
    assert {t.signature() for t in same} == {a.signature()}
    differ = [direct(range(100, 201)),                  # its length
              direct(range(100, 200), n=1 << 31),       # its type
              ProbeTable.build_exists(COPR, np.arange(100, 200), 100)]
    assert same[0].table.dtype != differ[1].table.dtype
    assert differ[2].table.shape == a.table.shape
    sigs = [a.signature()] + [t.signature() for t in differ]
    assert len(set(sigs)) == len(sigs)

    _rows0, _n, _arr, b = _table("bucket_col0")
    _rows1, _n, _arr, b1 = _table("bucket_col1")
    shuffled = ProbeTable("bucket", b.n, b.table[::-1].copy(),
                          n_sorted=b.n_sorted, pack=b.pack, bucket=b.bucket)
    assert shuffled.signature() == b.signature()
    assert b1.bucket != b.bucket and b1.signature() != b.signature()
    assert b.signature() != a.signature()

    s = direct(range(100, 100 + 97 * 3000, 97))
    s2 = direct(range(5, 5 + 89 * 3000, 89))
    assert s.form == "sorted" and s.signature() == s2.signature()
    assert s.signature() not in sigs
    assert ProbeTable.always_miss(1).signature() == \
        ProbeTable.always_miss(1).signature() != \
        ProbeTable.always_miss(1 << 31).signature()


@pytest.mark.parametrize("case, want", [
    ("folded", "folded"), ("sorted", "search"), ("bucket_col0", "bucket"),
    ("exists_direct", "exists"), ("exists_sorted", "search"),
    ("matdim", "matdim"), ("direct_int32", "direct")])
def test_the_counters_label(case, want):
    form = case if case in _FORMS else "direct_int32"
    rows, n, _arrays, table = _table(form)
    if case.startswith("exists"):
        table = ProbeTable.build_exists(
            COPR, np.array([r[0] for r in rows], dtype=np.int64), n)
    dim = types.SimpleNamespace(
        subplan=object() if case == "matdim" else None)
    assert table.label(dim, case == "folded") == want
    assert table.nbytes == table.table.nbytes + (
        table.keys.nbytes if table.form == "sorted" else 0)


def test_duplicate_or_null_keys_build_no_table():
    k = np.array([1, 2, 2, 4], dtype=np.int64)
    nul = np.array([False, True, False, False])
    at = np.arange(4)
    assert ProbeTable.build(COPR, {K0: (k, None, None)}, [K0], at, 4) is None
    assert ProbeTable.build(COPR, {K0: (at, nul, None)}, [K0], at, 4) is None
    assert ProbeTable.build(COPR, {K0: (k, None, None), K1: (at, nul, None)},
                            [K0, K1], at, 4) is None
    # rows the snapshot does not see are no duplicates
    got = ProbeTable.build(COPR, {K0: (k, None, None)}, [K0],
                           np.array([0, 2, 3]), 4)
    assert got.form == "direct" and list(got.table) == [0, 2, 4, 3]
