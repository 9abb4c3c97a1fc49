"""Folded dimensions of the fused pipeline (copr/pipeline.py).

A dimension that a fact column probes (a chain root: TPC-H's orders,
supplier) carries, in the table the fact lanes probe, everything that
is a function of its own row:

- the hit: its visibility at the snapshot, its own filters and, down
  the chain, the hit of every inner/semi dimension whose probe
  expression reads only its columns (customer under orders, nation
  under supplier, region under nation). A slot whose row fails any of
  them holds the miss sentinel `n`, so the kernel's hit test is the
  probe alone;
- the payload: what something downstream reads at fact width through
  the root's position -- the root's own columns, a descendant's column
  or join position, their null bits, the position itself when it is a
  group key -- is packed into the fields of one word a root row and
  composed with the probe table (`Fold.packed`): the kernel gathers
  the word once by key and shifts each field out. A root that reads
  nothing but its position keeps the plain table of positions.

A table a fact lane gathers from is as wide as what it holds
(`table_dtype`): int32 where a word's fields, or a table's positions
and its miss, fit 31 bits and the sign bit, int64 otherwise. The chip
gathers 32 bits a lane at a time, so an int64 table costs two gathers
a look-up and an int32 one; the program widens after the gather.

Which dimension folds under which is read from the plan's shape alone
(`fold_plan`); the tables are built once a snapshot on the host
(`bind_folds`), cached under a key that holds every table version of
the chain, the snapshot ts and every folded filter, and uploaded
through the resident store like any lut.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from ..expression import EvalCtx, eval_expr, eval_bool_mask
from ..expression.vec import materialize_nulls
from ..utils import metrics as _metrics


def _idxs(e):
    s = set()
    e.collect_columns(s)
    return s


def _cid_of(dag, sc):
    ci = dag.table_info.find_column(sc.name)
    return -1 if ci is None else ci.id


class FoldPlan:
    """The plan-shape half of the fold: per dimension, the dimension it
    is resolved under (`parent`), whether its mask folds into its own
    probe table (`masked`: a root the kernel probes and nothing else),
    and what to count (`outcomes`)."""

    __slots__ = ("parent", "kids", "masked", "outcomes")

    def __init__(self, ndims):
        self.parent = [None] * ndims
        self.kids = [[] for _ in range(ndims)]
        self.masked = [False] * ndims
        self.outcomes = [[] for _ in range(ndims)]

    def ancestors(self, di):
        di = self.parent[di]
        while di is not None:
            yield di
            di = self.parent[di]

    def descendants(self, di):
        for c in self.kids[di]:
            yield c
            yield from self.descendants(c)

    def sig(self):
        return tuple(self.parent), tuple(self.masked)


def fold_plan(plan) -> FoldPlan:
    """Decide from the plan's shape which dimensions fold. A child is an
    inner or semi dimension with a single key whose probe expression
    reads the columns of exactly one inner single-key dimension before
    it; everything else keeps its own probe. Of those, inner and
    not-yet-prefiltered semi dimensions with a single key fold their
    mask into their table; left, anti and composite-key dimensions keep
    today's program."""
    fp = getattr(plan, "_dim_fold", None)
    if fp is not None:
        return fp
    dims = list(plan.dims)
    fp = FoldPlan(len(dims))
    fact = {sc.col.idx for sc in plan.fact_dag.cols}
    owner = {}
    for di, dim in enumerate(dims):
        if dim.join_type in ("semi", "anti"):
            continue               # their columns never reach the pipeline
        for sc in dim.dag.cols:
            owner[sc.col.idx] = di if sc.col.idx not in owner else -1
    for di, dim in enumerate(dims):
        pidx = set()
        for _, pe in dim.all_keys():
            pidx |= _idxs(pe)
        owners = {owner.get(i) for i in pidx if i not in fact}
        chained = bool(pidx) and owners and not (pidx & fact)
        out = fp.outcomes[di]
        if dim.extra_keys:
            out.append("declined_composite_key")
            continue
        if dim.join_type in ("left", "anti"):
            if dim.join_type == "left" or chained:
                out.append("declined_" + dim.join_type)
            continue
        if chained and len(owners) == 1:
            (p,) = owners
            if p is None or p < 0 or p >= di:
                out.append("declined_unresolved_parent")
            elif dims[p].join_type != "inner" or dims[p].extra_keys:
                out.append("declined_parent_keeps_misses"
                           if dims[p].join_type != "inner"
                           else "declined_composite_parent")
            else:
                fp.parent[di] = p
                fp.kids[p].append(di)
                out.append("folded")
                continue
        elif owners:
            out.append("declined_multi_parent")
        # a root: prefiltered semi dimensions fold at meta time already
        if dim.join_type == "inner" or dim.subplan is not None:
            fp.masked[di] = True
            out.append("mask_folded")
    plan._dim_fold = fp         # plans are shared: publish it whole
    return fp


def pos_keys(fp, dset, keep=None):
    """The dimensions of `dset` whose join positions have to be kept as
    group keys: a dimension under another of the set is a function of
    that one's position and is decoded from it on the host (`keep`: one
    that stays a key of its own, the device top-n's ordering item)."""
    return sorted(di for di in dset
                  if di == keep or not any(a in dset
                                           for a in fp.ancestors(di)))


def needs(plan, fp, pos_grouped):
    """-> the column idxs the program reads at fact width: what post
    filters, aggregate arguments and the probes of the dimensions the
    kernel still probes read, and the group items unless the join
    positions stand for them."""
    read = set()
    for e in list(plan.post_filters) + [x for a in plan.aggs
                                        for x in a.args]:
        read |= _idxs(e)
    for di, dim in enumerate(plan.dims):
        if fp.parent[di] is None:
            for _, pe in dim.all_keys():
                read |= _idxs(pe)
    if not pos_grouped:
        for g in plan.group_items:
            read |= _idxs(g)
    return read


def pack_fields(plan, fp, root, need, pos_keyed):
    """What the program reads at fact width through `root`'s position,
    from the plan's shape: the position itself when it is a group key
    (`pos_keyed`: the lowering's position dimensions), an inner
    descendant's, and every column of `need` that is the root's or an
    inner descendant's -> tuple of ("pos",) | ("fpos", d) |
    ("col", idx, d, cid)."""
    fields = [("pos",)] if root in pos_keyed else []
    inner = [d for d in fp.descendants(root)
             if plan.dims[d].join_type == "inner"]
    fields += [("fpos", d) for d in inner if d in pos_keyed]
    if plan.dims[root].join_type == "inner":
        for d in [root] + inner:
            dag = plan.dims[d].dag
            fields += [("col", sc.col.idx, d, _cid_of(dag, sc))
                       for sc in dag.cols
                       if sc.col.idx in need and _cid_of(dag, sc) != -1]
    return tuple(fields)


def txn_dirty(ctx) -> bool:
    """Does the statement run inside a transaction with uncommitted
    writes? Such a statement's derived tables are neither cached nor
    served from a cache."""
    txn = getattr(getattr(ctx, "sess", None), "_txn", None)
    return txn is not None and not txn.committed and not txn.aborted \
        and txn.is_dirty()


def count(outcome):
    _metrics.DIM_FOLD.labels(outcome).inc()


def _host_cols(dim, meta):
    n = meta["n"]
    cols = {}
    for sc in dim.dag.cols:
        cid = _cid_of(dim.dag, sc)
        if cid == -1:
            continue
        d, nl, sd = meta["arrays"][cid]
        cols[sc.col.idx] = (d[:n], None if nl is None else nl[:n], sd)
    return cols


_WORD_BITS = 63
_NARROW_BITS = 31


def table_dtype(bits):
    """The physical type of a table a fact lane gathers from, by the
    bits of the largest value it holds: int32 where they leave the sign
    bit free (one 32-bit gather a look-up on the chip), else int64
    (two)."""
    return np.int32 if bits <= _NARROW_BITS else np.int64


def pos_dtype(n):
    """`table_dtype` of a table of positions under `n`, the miss."""
    return table_dtype(int(n).bit_length())


def miss(dtype):
    """A slot no row passes: the sign bit of word 0 alone, so
    `word >= 0` is the hit and every field of a miss reads its minimum
    (sign-extended, an int32 miss is an int64 one with no field bit)."""
    return np.iinfo(dtype).min


def pack_words(cols):
    """Pack arrays of one length into words, first fit in the order
    given into 63 bits: array i holds `value - lo[i]` in the bits its
    range needs, at `shift[i]` of word `word[i]`. Word 0 keeps its sign
    bit for the miss; a field that needs more than 63 bits (a double's
    pattern, a 64-bit range) is a later word of its own, as it is. A
    word whose fields end within 31 bits is held as int32, every other
    as int64 (`table_dtype`): the fit is never re-packed into narrower
    bins, which could only add words.
    -> (words, word, shift, mask, lo)."""
    ints, lo, bits = [], [], []
    for c in cols:
        if c.dtype.kind in "fu":
            c = c.view(f"i{c.dtype.itemsize}")      # its bit pattern
        mn, mx = (int(c.min()), int(c.max())) if len(c) else (0, 0)
        wide = (mx - mn) >> _WORD_BITS              # all 64 bits, as is
        ints.append(c.astype(np.int64))
        lo.append(0 if wide else mn)
        bits.append(64 if wide else (mx - mn).bit_length())
    used, word, shift = [0], [], []
    for b in bits:
        wi = next((i for i, u in enumerate(used) if u + b <= _WORD_BITS),
                  len(used))
        if wi == len(used):
            used.append(0)
        word.append(wi)
        shift.append(used[wi])
        used[wi] += b
    words = [np.zeros(len(cols[0]), dtype=np.int64) for _ in used]
    for c, mn, wi, sh in zip(ints, lo, word, shift):
        words[wi] |= (c - mn) << sh
    words = [w.astype(table_dtype(u), copy=False)
             for w, u in zip(words, used)]
    as64 = lambda xs: np.asarray(xs, dtype=np.int64)    # noqa: E731
    return words, tuple(word), as64(shift), \
        as64([-1 if b == 64 else (1 << b) - 1 for b in bits]), as64(lo)


def unpack_field(word, shift, mask, lo, dtype):
    """Field of a packed word (numpy on the host, jax.numpy in the
    kernel): the value `pack_words` was given, in its own dtype. The
    layout is int64's whatever holds the word: `shift`, `mask` and
    `lo` widen an int32 one."""
    dtype = np.dtype(dtype)
    v = ((word >> shift) & mask) + lo
    if dtype.kind == "f":
        return v.astype(f"i{dtype.itemsize}").view(dtype)
    return v.astype(dtype)


class Packed:
    """One root's composed probe words for one field set (`fields`,
    `pack_fields`' tuple): `tables[w]` is word w addressed as the probe
    table is (by key slot, or by sorted rank), `text[i]` = (kind, ident,
    word, dtype) names field i for the program, `shift` / `mask` / `lo`
    ride the call as operands, `sdicts[idx]` is a column's dictionary."""

    __slots__ = ("fields", "tables", "text", "shift", "mask", "lo",
                 "sdicts", "nbytes")

    def __init__(self, fields, tables, text, shift, mask, lo, sdicts):
        self.fields, self.tables, self.text = fields, tables, text
        self.shift, self.mask, self.lo = shift, mask, lo
        self.sdicts = sdicts
        self.nbytes = sum(t.nbytes for t in tables)


class Fold:
    """One root's folded tables over one snapshot: the probe table with
    the chain's hit folded in, each inner descendant's position at each
    of its ancestors' widths (`pos_at[(a, d)]`), and the composed words
    of each field set something reads through it (`packed`), built when
    first asked for."""

    def __init__(self, root, sig, table, pos_at, metas):
        self.root = root
        self.sig = sig
        self.table = table
        self.pos_at = pos_at
        self.n = metas[root]["n"]
        # the snapshot arrays of the root and of the descendants that
        # have a position at its width, and nothing else of the metas
        self._arrays = {d: metas[d]["arrays"] for a, d in pos_at
                        if a == root}
        self._arrays[root] = metas[root]["arrays"]
        self._packed = {}
        self.nbytes = table.nbytes + sum(a.nbytes for a in pos_at.values())

    def packed(self, fields):
        """The composed words of `fields` (`pack_fields`' tuple)
        -> Packed."""
        got = self._packed.get(fields)
        if got is None:
            got = self._packed[fields] = self._compose(fields)
            self.nbytes += got.nbytes
        return got

    def _compose(self, fields):
        n, cols, text, sdicts = self.n, [], [], {}
        for f in fields:
            if f[0] == "pos":
                cols.append(np.arange(n, dtype=np.int64))
                text.append(("pos", None, "int64"))
            elif f[0] == "fpos":
                cols.append(self.pos_at[(self.root, f[1])])
                text.append(("fpos", f[1], "int64"))
            else:
                _, idx, d, cid = f
                data, nulls, sdicts[idx] = self._arrays[d][cid]
                at = slice(n) if d == self.root else \
                    self.pos_at[(self.root, d)]
                cols.append(data[at])
                text.append(("col", idx, data.dtype.name))
                if nulls is not None:
                    cols.append(nulls[at])
                    text.append(("null", idx, "bool"))
        words, word, shift, mask, lo = pack_words(cols)
        # composed with the probe table: one fancy index a word at the
        # table's width, the sentinel slot n reading the miss
        at = np.minimum(self.table, n)
        tables = [np.append(w, w.dtype.type(0 if wi else miss(w.dtype)))[at]
                  for wi, w in enumerate(words)]
        text = tuple((k, ident, wi, dt)
                     for (k, ident, dt), wi in zip(text, word))
        return Packed(fields, tables, text, shift, mask, lo, sdicts)


def _build(fp, plan, metas, root):
    """Resolve the chain under `root` on the host, bottom-up at each
    dimension's own width."""
    dims = plan.dims
    pos_at = {}

    def resolve(di):
        meta = metas[di]
        n = meta["n"]
        passing = meta["valid"][:n].copy()
        cols = _host_cols(dims[di], meta)
        ectx = EvalCtx(np, n, cols, host=True)
        for f in dims[di].dag.filters:
            passing &= np.asarray(eval_bool_mask(ectx, f))
        for c in fp.kids[di]:
            pv, pnl, _ = eval_expr(ectx, dims[c].probe_expr)
            if np.isscalar(pv) or getattr(pv, "ndim", 1) == 0:
                pv = np.full(n, pv)
            pv = np.asarray(pv).astype(np.int64)
            pnm = np.asarray(materialize_nulls(ectx, pnl))
            cpos, chit = metas[c]["probe"].host_probe(pv, pnm)
            passing &= chit
            if metas[c]["probe"].exists:
                continue       # filters and visibility are in its table
            passing &= resolve(c)[cpos]
            if dims[c].join_type == "inner":
                pos_at[(di, c)] = cpos
                for d in fp.descendants(c):
                    below = pos_at.get((c, d))
                    if below is not None:
                        pos_at[(di, d)] = below[cpos]
        return passing

    passing = resolve(root)
    meta = metas[root]
    n = meta["n"]
    ok = np.append(passing, False)         # the sentinel n stays a miss
    src = meta["probe"].positions
    # (in the physical type of the table it stands in for)
    return np.where(ok[np.minimum(src, n)], src, n) \
        .astype(src.dtype, copy=False), pos_at


_MU = threading.Lock()


def _lru(copr):
    c = getattr(copr, "_fold_lru", None)
    if c is None:
        c = copr._fold_lru = OrderedDict()
    return c


def _chain_sig(fp, plan, metas, root, read_ts):
    parts = [read_ts]
    for di in [root] + list(fp.descendants(root)):
        dim, meta = plan.dims[di], metas[di]
        t = meta["tbl"]
        # expressions print the plan's column numbering: pin the stored
        # columns behind it too (the filters' through the table's uid
        # and the dag's column set, the probe's through its parent's)
        p = fp.parent[di]
        pidx = _idxs(dim.probe_expr)
        src = () if p is None else tuple(sorted(
            _cid_of(plan.dims[p].dag, sc) for sc in plan.dims[p].dag.cols
            if sc.col.idx in pidx))
        parts.append((
            di, p, src, t.uid, t.version, meta["n"], dim.join_type,
            _cid_of(dim.dag, dim.build_key), dim.probe_expr.fingerprint(),
            tuple(f.fingerprint() for f in dim.dag.filters),
            tuple((sc.col.idx, _cid_of(dim.dag, sc))
                  for sc in dim.dag.cols)))
    return tuple(parts)


def bind_folds(copr, plan, fp, metas, read_ts, ctx):
    """Build or find the folded tables of every root of `fp` and hand
    back the metas the upload and the kernel builder read: a root's
    copy carries its `Fold` (and the folded table in the positions' place),
    a folded child's copy names the root it went under.
    -> (metas, folds, builds)."""
    dirty = txn_dirty(ctx)
    out = list(metas)
    folds = builds = 0
    for di, outs in enumerate(fp.outcomes):
        for o in outs:
            count(o)
        folds += "folded" in outs or "mask_folded" in outs
    for root in range(len(metas)):
        if fp.parent[root] is not None or not fp.masked[root]:
            continue
        sig = _chain_sig(fp, plan, metas, root, read_ts)
        lru = _lru(copr)
        fold = None
        # a dirty transaction neither finds nor leaves a fold here (as
        # _materialized_dim_meta: a materialised child may have read its
        # uncommitted rows, under a uid nobody else will ever present)
        if not dirty:
            with _MU:
                fold = lru.get(sig)
                if fold is not None:
                    lru.move_to_end(sig)
        if fold is None:
            table, pos_at = _build(fp, plan, metas, root)
            fold = Fold(root, sig, table, pos_at, metas)
            builds += 1
            count("build")
            if not dirty:
                with _MU:
                    lru[sig] = fold
                    budget = copr._dev_store.budget // 4
                    while len(lru) > 1 and \
                            sum(f.nbytes for f in lru.values()) > budget:
                        lru.popitem(last=False)
        else:
            count("cache_hit")
        m = dict(metas[root])
        m["fold"] = fold
        m["probe"] = m["probe"].with_positions(fold.table)
        m["ukey"] = tuple(m.get("ukey", ())) + ("fold", fold.sig)
        out[root] = m
        for d in fp.descendants(root):
            c = dict(metas[d])
            c["folded_under"] = root
            out[d] = c
    return out, folds, builds
