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
- the payload: a descendant's column (or join position) that something
  downstream reads at fact width becomes a column of the root, at the
  root's width, read with one gather through the root's position.

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


def _host_probe(meta, pv, pnm):
    """The kernel's probe, in numpy: -> (position clipped into the
    dimension, hit)."""
    n = meta["n"]
    if meta["mode"] == "direct":
        lut = meta["lut"]
        idx = pv - meta["lo"]
        inb = (idx >= 0) & (idx < len(lut))
        raw = lut[np.clip(idx, 0, len(lut) - 1)]
        return np.minimum(raw, n - 1), inb & (raw < n) & ~pnm
    sk = meta["skeys"]
    loc = np.searchsorted(sk, pv)
    locc = np.minimum(loc, len(sk) - 1)
    hit = (loc < meta["n_sorted"]) & (sk[locc] == pv) & ~pnm
    return np.minimum(meta["order"][locc], n - 1), hit


class Fold:
    """One root's folded tables over one snapshot: the probe table with
    the chain's hit folded in, each inner descendant's position at each
    of its ancestors' widths (`pos_at[(a, d)]`), and the descendants'
    columns at the root's width, built when first asked for."""

    def __init__(self, root, sig, table, pos_at, metas):
        self.root = root
        self.sig = sig
        self.table = table
        self.pos_at = pos_at
        # the snapshot arrays of the descendants that have a position at
        # the root's width, and nothing else of the statement's metas
        self._arrays = {d: metas[d]["arrays"] for a, d in pos_at
                        if a == root}
        self._cols = {}
        self.nbytes = table.nbytes + sum(a.nbytes for a in pos_at.values())

    def col(self, d, cid):
        """Column `cid` of descendant `d` at the root's width
        -> (data, nulls, sdict)."""
        got = self._cols.get((d, cid))
        if got is None:
            data, nulls, sd = self._arrays[d][cid]
            pos = self.pos_at[(self.root, d)]
            got = (data[pos], None if nulls is None else nulls[pos], sd)
            self._cols[(d, cid)] = got
            self.nbytes += got[0].nbytes + \
                (0 if got[1] is None else got[1].nbytes)
        return got


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
            cpos, chit = _host_probe(metas[c], pv, pnm)
            passing &= chit
            if metas[c].get("pre"):
                continue       # filters and visibility are in its lut
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
    src = meta["lut"] if meta["mode"] == "direct" else meta["order"]
    return np.where(ok[np.minimum(src, n)], src, n), pos_at


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
    copy carries its `Fold` (and the folded table in the lut's place),
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
        m["lut" if m["mode"] == "direct" else "order"] = fold.table
        m["ukey"] = tuple(m.get("ukey", ())) + ("fold", fold.sig)
        out[root] = m
        for d in fp.descendants(root):
            c = dict(metas[d])
            c["folded_under"] = root
            out[d] = c
    return out, folds, builds
