"""Fused scan->join->agg device pipeline (reference: the operator chain
executor/join/hash_join_v2.go:608 build/probe + tipb partial agg,
re-designed TPU-first as ONE XLA program).

Design: the fact table streams through in static-shape partitions; each
dimension join is a probe of the dimension's table over its unique keys
(copr/probe.py; resident in HBM across queries, version-keyed) and a
gather of payload columns — no dynamic-shape compaction anywhere: rows
that fail a filter or miss a join simply clear a validity mask, and the
partial aggregation at the tail ignores them. This keeps every
intermediate at fact-partition cardinality, which is what lets XLA fuse
filter+join+agg into one kernel with zero host round-trips (the round-1
bottleneck: Q3/Q5 lost all join output to host numpy between operators).
"""
from __future__ import annotations

import re
import threading

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp

from ..expression import EvalCtx, eval_expr, eval_bool_mask
from ..expression.vec import materialize_nulls
from ..chunk.device import shape_bucket, shard_lanes
from . import agg_lowering as _al
from . import dimfold
from . import probe
from .probe import ProbeTable
from .agg_lowering import (PartialAggResult, capture_agg_dicts,
                           dense_strides, dense_agg_body, dense_agg_states,
                           sort_agg_body, runs_agg_core, onehot_agg_body,
                           onehot_states, compact_dense,
                           psum_dense_result, prefix_select)
from ..utils.fetch import prefetch, host_array, host_int
from ..utils import failpoint
from ..utils import jaxcfg
from ..utils import metrics as _metrics
from ..utils import phase
from ..utils import tracing as _tracing

_I64_MAX = np.iinfo(np.int64).max


class _AggShim:
    """Duck-typed dag for capture_agg_dicts/dense_strides/host_partial_agg."""

    def __init__(self, group_items, aggs):
        self.group_items = group_items
        self.aggs = aggs


_cid_of = dimfold._cid_of
_expr_idxs = dimfold._idxs


def _set_reason(copr, msg):
    """Record why the fused path declined, for EXPLAIN ANALYZE and
    scripts/diag_routing.py (reference: pkg/util/execdetails). Also
    counted by reason class (tidb_tpu_fused_decline_total) so fleet
    dashboards see decline-mix shifts without per-query EXPLAINs."""
    dom = getattr(copr, "domain", None)
    if dom is not None:
        dom.last_fused_reason = msg
    _metrics.FUSED_DECLINE.labels(_metrics.reason_code(msg)).inc()


def _dim_sort_meta(copr, dim, tbl, read_ts):
    """Host-side per-dimension prep: snapshot arrays + the join "hash
    table" for the build-key column (cached per table version) +
    uniqueness check. -> dict or None when ineligible.

    The table and its form are `probe.ProbeTable`'s, from the build
    keys; composite keys (dim.extra_keys, Q9 partsupp) pack into one
    int64 first, and the kernel's probe packs by the same layout."""
    col_ids = [cid for cid in (_cid_of(dim.dag, sc) for sc in dim.dag.cols)
               if cid != -1]
    arrays, valid = tbl.snapshot(col_ids, read_ts)
    n = len(valid)
    key_cids = [_cid_of(dim.dag, sc) for sc, _ in dim.all_keys()]
    if any(cid == -1 for cid in key_cids):
        _set_reason(copr, f"dim {dim.dag.table_info.name}: join key is "
                    "not a stored column")
        return None
    if n == 0:
        _set_reason(copr, f"dim {dim.dag.table_info.name}: no visible "
                    "rows at this snapshot")
        return None
    for cid in key_cids:
        kdata, _kn, ksdict = arrays[cid]
        if ksdict is not None or kdata.dtype.kind == "f":
            _set_reason(copr, f"dim {dim.dag.table_info.name}: join key "
                        "is not int64-comparable (string/float)")
            return None                  # int64-comparable keys only
    host_cache = copr._host_cache
    if dim.join_type in ("semi", "anti") and not dim.extra_keys:
        # SEMI/ANTI only test key EXISTENCE: fold the dim's filters on
        # the host and dedup, so duplicate keys and filtered dims (Q4's
        # EXISTS, Q22's NOT EXISTS over orders) still ride the fused
        # probe. The kernel then skips this dim's mask entirely (the
        # table `exists`).
        return _semi_prefiltered_meta(copr, dim, tbl, arrays, valid, n,
                                      key_cids[0], read_ts)
    # built over VALID rows only (old MVCC versions of an updated key
    # would otherwise look like duplicates); visibility depends on
    # read_ts, so it keys the cache; older versions are evicted
    ck = tuple(key_cids)
    hkey = (tbl.uid, ck, "dim", tbl.version, n, read_ts)
    table = host_cache.get(hkey)
    if table is None:
        host_cache.pop(host_cache.pop((tbl.uid, ck, "dimcur"), None), None)
        host_cache[(tbl.uid, ck, "dimcur")] = hkey
        # dup-key / null-key dims are rejected below on every use:
        # cache a tombstone (False), don't build the (possibly huge) table
        table = host_cache[hkey] = ProbeTable.build(
            copr, arrays, key_cids, np.nonzero(valid)[0], n) or False
    if table is False:
        _set_reason(copr, f"dim {dim.dag.table_info.name}: build keys "
                    "are duplicated or NULL (non-unique build side)")
        return None
    return {"probe": table, "arrays": arrays, "valid": valid, "n": n,
            "tbl": tbl}


_VOLATILE_RE = re.compile(
    r"rand\(|now\(|current_|sysdate\(|uuid|connection_id\(|sleep\(|"
    r"last_insert_id\(|benchmark\(|@", re.IGNORECASE)


# node types whose semantic content is FULLY captured by explain_info
# plus the per-type extras appended in _plan_fp below. Any other node
# kind refuses fingerprinting (-> no caching) rather than risk two
# different subplans aliasing one cache entry.
_FP_SAFE_NODES = frozenset([
    "PhysTableReader", "PhysFusedPipeline", "PhysHashAgg",
    "PhysHashJoin", "PhysMergeJoin", "PhysSelection", "PhysProjection",
    "PhysShell", "PhysSort", "PhysTopN", "PhysLimit", "PhysUnion",
    "PhysDual", "PhysIndexRange", "PhysIndexMerge", "PhysPointGet",
    "PhysBatchPointGet", "PhysIndexLookupJoin",
    # fragment boundaries are pure pass-throughs: Sender prints
    # type/fragment/keys in explain_info, Receiver's content is its child
    "PhysExchangeSender", "PhysExchangeReceiver",
])


def _plan_fp(plan):
    """Structural fingerprint of a physical plan: node type +
    explain_info (filters/aggs/keys print with literal values) + output
    schema, recursively; -> None when any node's content can't be fully
    pinned. Keys the materialized-dim cache, so under-discrimination
    here would serve one subquery's rows to a different subquery —
    node types append every field their explain_info omits."""
    tname = type(plan).__name__
    if tname not in _FP_SAFE_NODES:
        return None
    parts = [tname, plan.explain_info(),
             ",".join(sc.name or "" for sc in plan.schema.cols)]
    oc = getattr(plan, "other_conds", None)
    if oc:
        parts.append("oc:" + ";".join(map(repr, oc)))
    if getattr(plan, "null_aware", False):
        parts.append("naaj")       # NOT IN vs NOT EXISTS anti semantics
    # explain_info gaps, per node kind:
    if tname == "PhysBatchPointGet":       # prints only len(handles)
        parts.append("h:" + ";".join(map(repr, plan.handles)))
    elif tname == "PhysIndexRange":        # omits residual conjuncts
        parts.append("res:" + ";".join(map(repr, plan.residual)))
    elif tname == "PhysIndexMerge":        # omits ranges + residual
        parts.append("br:" + ";".join(
            f"{ix.name}[{lo!r},{hi!r},{li},{hi_i}]"
            for ix, lo, hi, li, hi_i in plan.branches))
        parts.append("res:" + ";".join(map(repr, plan.residual)))
    elif tname == "PhysIndexLookupJoin":   # omits inner residuals
        parts.append("inres:" + ";".join(map(repr, plan.inner_dag.filters +
                                             plan.inner_dag.host_filters)))
        parts.append("incols:" + ",".join(sc.name or ""
                                          for sc in plan.inner_dag.cols))
    elif tname == "PhysHashAgg":
        parts.append("agg:" + ";".join(
            f"{a.name}/{getattr(a, 'distinct', False)}" for a in plan.aggs))
    elif tname == "PhysTableReader":       # omits limit/topn pushdowns
        parts.append(f"lim:{plan.dag.limit},topn:{plan.dag.topn!r},"
                     f"psel:{plan.dag.part_sel!r}")
    elif tname == "PhysFusedPipeline":     # omits fact filters/pushdowns
        parts.append("ff:" + ";".join(map(repr, plan.fact_dag.filters +
                                          plan.fact_dag.host_filters)))
        parts.append(f"lim:{plan.fact_dag.limit},"
                     f"topn:{plan.fact_dag.topn!r},"
                     f"ts:{plan.topn_spec!r}")
    dims = getattr(plan, "dims", None)
    if dims:
        for d in dims:
            parts.append(f"jt:{d.join_type}")
            parts.append(";".join(map(repr, d.dag.filters + d.dag.host_filters)))
            if d.subplan is not None:
                sub = _plan_fp(d.subplan)
                if sub is None:
                    return None
                parts.append(sub)
    fb = getattr(plan, "fallback", None)
    if fb is not None and type(fb).__name__ not in _FP_SAFE_NODES:
        return None
    for c in plan.children:
        sub = _plan_fp(c)
        if sub is None:
            return None
        parts.append(sub)
    return "|".join(parts)


def _plan_base_tables(engine, plan, out=None):
    """Collect the ColumnarTables a plan reads. -> list or None when any
    referenced table can't be pinned (unknown id, partitioned) — the
    caller then skips caching rather than risk a stale reuse."""
    if out is None:
        out = []
    infos = []
    for attr in ("dag", "fact_dag", "inner_dag"):
        dag = getattr(plan, attr, None)
        if dag is not None and getattr(dag, "table_info", None) is not None:
            infos.append(dag.table_info)
    ti = getattr(plan, "table_info", None)
    if ti is not None:
        infos.append(ti)
    for d in getattr(plan, "dims", None) or ():
        if d.dag is not None and d.dag.table_info is not None:
            infos.append(d.dag.table_info)
        if d.subplan is not None and \
                _plan_base_tables(engine, d.subplan, out) is None:
            return None
    for info in infos:
        if getattr(info, "partitions", None):
            return None
        tbl = engine.tables.get(info.id)
        if tbl is None:
            return None
        out.append(tbl)
    for c in plan.children:
        if _plan_base_tables(engine, c, out) is None:
            return None
    return out


_MATDIM_MAX_BYTES = 1 << 29     # 512MB of cached subquery results


def _matdim_cache(copr):
    """Per-copr LRU for materialized-dim results, byte-bounded — unlike
    the metadata entries in _host_cache, these hold full result arrays
    (the device pool analog: _dev_put charges an HBM budget)."""
    c = getattr(copr, "_matdim_lru", None)
    if c is None:
        from collections import OrderedDict
        c = copr._matdim_lru = OrderedDict()
        copr._matdim_bytes = 0
    return c


def _matdim_nbytes(out):
    total = 0
    for d, nl, _sd in out["arrays"].values():
        total += getattr(d, "nbytes", 0)
        total += getattr(nl, "nbytes", 0) if nl is not None else 0
    return total + out["probe"].nbytes


_MAT_SEQ = [0]
_MAT_SEQ_MU = threading.Lock()  # materializations on any conn thread


class _MatTbl:
    """Shim standing in for a ColumnarTable for materialized dims: only
    the attributes the upload/caching paths read. A fresh uid per
    materialization means device uploads never alias across queries
    (the HBM pool evicts LRU)."""

    __slots__ = ("uid", "version", "n", "dicts")

    def __init__(self, n):
        with _MAT_SEQ_MU:
            _MAT_SEQ[0] += 1
            self.uid = ("mat", _MAT_SEQ[0])
        self.version = 0
        self.n = n
        self.dicts = {}


def _materialized_dim_meta(copr, ctx, dim, read_ts):
    """Span `matdim` and tidb_tpu_matdim_total{outcome} round
    `_matdim_meta`: `hit` where the cache keyed on the subplan's
    fingerprint and its base tables' versions answered, `build` where
    the subplan ran (`groups`: the dimension's rows; `rows`: its base
    tables')."""
    with _tracing.span("matdim") as sp:
        seen = {}
        meta = _matdim_meta(copr, ctx, dim, read_ts, seen)
        outcome = seen.get("outcome", "build")
        _metrics.MATDIM.labels(outcome).inc()
        if sp is not None:
            sp.attrs["outcome"] = outcome
            sp.attrs["groups"] = 0 if meta is None else meta["n"]
            sp.attrs["rows"] = seen.get("rows", 0)
    return meta


def _matdim_meta(copr, ctx, dim, read_ts, seen):
    """Execute dim.subplan (Q17's decorrelated per-key aggregate, Q18's
    grouped IN-subquery) and shape its output like a dim table: arrays
    keyed by output POSITION, every row valid, group keys unique by
    construction (still verified). -> meta dict or None; `seen` takes
    `outcome` "hit" on a cache hit and the base tables' `rows`."""
    if ctx is None:
        _set_reason(copr, "materialized dim: no execution context")
        return None
    # cache across queries/snapshots: subplans are deterministic over
    # their base-table contents, so (structural fingerprint, base-table
    # versions) pins the result; reuse is sound when no base row was
    # committed after either snapshot (max_commit_ts <= both read_ts).
    # q21/q18-class queries re-run their decorrelated subqueries
    # verbatim every execution — this turns those from the dominant
    # per-run cost into a dict hit.
    # an active dirty transaction can see uncommitted rows through the
    # subplan's scans (UnionScan merge) without bumping any table
    # version — both caching such a result and serving a committed-data
    # result to the writer would be wrong, so dirty sessions bypass the
    # cache entirely in both directions
    dirty = dimfold.txn_dirty(ctx)
    ck = base = None
    fp = None if dirty else _plan_fp(dim.subplan)
    if fp is not None and not _VOLATILE_RE.search(fp):
        base = _plan_base_tables(copr.engine, dim.subplan)
    if base:
        try:
            tz = (str(ctx.sv.get("time_zone")), str(ctx.sv.get("sql_mode")))
        except Exception:               # noqa: BLE001
            tz = ()
        ck = ("matdim", fp, tz)
        vers = tuple((t.uid, t.version) for t in base)
        maxts = max(t.max_commit_ts for t in base)
        seen["rows"] = sum(t.n for t in base)
        lru = _matdim_cache(copr)
        ent = lru.get(ck)
        if ent is not None:
            evers, ets, cached, _nb = ent
            # read_ts None = latest snapshot (sees every committed row)
            if evers == vers and (ets is None or maxts <= ets) and \
                    (read_ts is None or maxts <= read_ts):
                lru.move_to_end(ck)
                seen["outcome"] = "hit"
                return cached
    from ..executor.builder import build_executor
    ex = build_executor(ctx, dim.subplan)
    ex.open()
    chunks = ex.all_chunks()
    ex.close()
    ncols = len(dim.dag.cols)
    n = sum(len(ch) for ch in chunks)
    if n == 0:
        _set_reason(copr, "materialized dim: subplan produced no rows")
        return None                   # caller's empty-dim handling differs
    arrays = {}
    for i in range(ncols):
        parts = [ch.columns[i] for ch in chunks]
        data = np.concatenate([np.asarray(p.data) for p in parts])
        if data.dtype.kind not in "iufb":
            _set_reason(copr, "materialized dim: non-numeric column")
            return None               # object arrays can't ride the kernel
        sdicts = {id(p.dict) for p in parts if p.dict is not None}
        if len(sdicts) > 1:
            _set_reason(copr, "materialized dim: inconsistent dicts")
            return None               # inconsistent dicts across chunks
        sdict = next((p.dict for p in parts if p.dict is not None), None)
        nulls = None
        if any(p.nulls is not None for p in parts):
            nulls = np.concatenate(
                [p.nulls if p.nulls is not None
                 else np.zeros(len(p), dtype=bool) for p in parts])
        arrays[i] = (data, nulls, sdict)
    key_cids = [_cid_of(dim.dag, sc) for sc, _ in dim.all_keys()]
    if any(cid == -1 for cid in key_cids):
        _set_reason(copr, "materialized dim: join key not in output")
        return None
    for cid in key_cids:
        kdata, _kn, ksdict = arrays[cid]
        if ksdict is not None or kdata.dtype.kind == "f":
            _set_reason(copr, "materialized dim: non-int64 join key")
            return None
    table = ProbeTable.build(copr, arrays, key_cids, np.arange(n), n)
    if table is None:
        _set_reason(copr, "materialized dim: non-unique or NULL keys")
        return None
    out = dict(probe=table, arrays=arrays, valid=np.ones(n, dtype=bool),
               n=n, tbl=_MatTbl(n),
               dictsig=tuple(sorted(
                   (i, len(sd.values)) for i, (_d, _nl, sd)
                   in arrays.items() if sd is not None)))
    if ck is not None:
        lru = _matdim_cache(copr)
        nb = _matdim_nbytes(out)
        old = lru.pop(ck, None)
        if old is not None:
            copr._matdim_bytes -= old[3]
        lru[ck] = (vers, read_ts, out, nb)
        copr._matdim_bytes += nb
        while copr._matdim_bytes > _MATDIM_MAX_BYTES and len(lru) > 1:
            _k, (_v, _t, _o, onb) = lru.popitem(last=False)
            copr._matdim_bytes -= onb
    return out


def _semi_prefiltered_meta(copr, dim, tbl, arrays, valid, n, key_cid,
                           read_ts):
    fps = tuple(f.fingerprint() for f in dim.dag.filters)
    hkey = (tbl.uid, key_cid, "semidim", tbl.version, n, read_ts, fps)
    cache = copr._host_cache
    meta = cache.get(hkey)
    if meta is None:
        cache.pop(cache.pop((tbl.uid, key_cid, "semicur"), None), None)
        cache[(tbl.uid, key_cid, "semicur")] = hkey
        mask = valid.copy()
        ectx = EvalCtx(np, n, dimfold._host_cols(
            dim, {"n": n, "arrays": arrays}), host=True)
        for f in dim.dag.filters:
            mask &= np.asarray(eval_bool_mask(ectx, f))
        kdata, knulls, _ = arrays[key_cid]
        if knulls is not None:
            mask &= ~knulls[:n]
        meta = cache[hkey] = ProbeTable.build_exists(
            copr, np.unique(kdata[:n][mask]), n)
    return {"probe": meta, "arrays": arrays, "valid": valid, "n": n,
            "tbl": tbl, "ukey": ("pre",) + fps}


def _upload_dim(copr, dim, meta, cap, read_ts, mesh=None, want=None,
                pack=None):
    """Pad + upload dim arrays through the HBM buffer pool; -> pytree of
    device arrays for the kernel plus (has_nulls, sdict) layout info.
    With a mesh, every array replicates to all devices (the Broadcast
    exchange of the dim fragment).

    A folded dimension (copr/dimfold.py) uploads what its program
    reads and no more: `want` names the columns of its own that go up
    at its width (None: all of them, a dimension that folds nothing;
    of a folded one only the device top-n's ordering column), and no
    `valid` (its probe table holds the hit); a dimension resolved under
    a root takes no probe table at all; `pack`: the composed words of a
    root through whose position something but the position is read."""
    tbl = meta["tbl"]
    n = meta["n"]
    ver = tbl.version
    ck = () if mesh is None else ("bcast", mesh.devices.size)
    mk = ck + tuple(meta.get("ukey", ()))
    folded = meta.get("fold") is not None
    probed = meta.get("folded_under") is None
    # plain dim column data is append-only table state: it rides the
    # delta-maintained append seam (copr/delta.py) when the meta wraps
    # a REAL columnar table — materialized-dim shims (_MatTbl) and the
    # fabricated empty-dim placeholder arrays must not (their arrays
    # are not the table's columns)
    appendable = hasattr(tbl, "gc_epoch") and not meta.get("synthetic")

    def put(tag, arr, length, acap, fill=0, ts_keyed=False):
        # plain column data depends only on the table version; only the
        # MVCC-derived arrays (valid mask, lut/sort built over the valid
        # set, a fold's tables) vary with the snapshot ts and carry the
        # meta's `ukey` — keying data by either would re-upload every
        # dim column once per transaction or per folded filter.
        # _dev_put reads the pad capacity from key[-1]: acap stays LAST.
        key = (tbl.uid, tag, ver, read_ts if ts_keyed else None,
               length) + (mk if ts_keyed else ck) + (acap,)
        if mesh is None:
            return copr._dev_put(key, arr, pad_fill=fill,
                                 uid=tbl.uid, version=ver)
        return copr._dev_put_replicated(key, arr, mesh, acap, pad_fill=fill,
                                        uid=tbl.uid, version=ver)

    def put_col(cid, kind, arr, acap, fill=0):
        # append seam for raw dim columns: the whole column [0, n)
        # padded to acap, tail-patched under appends instead of
        # re-uploaded on every dim-table version bump
        from .delta import append_key
        key = append_key(tbl.uid, ("dim",) + ck, cid, kind,
                         tbl.gc_epoch, (), acap)
        return copr._dev_put_append(
            key, arr, n, acap, tbl.uid, ver, tbl.gc_epoch, 0, None,
            pad_fill=fill, mesh=mesh,
            spec="local" if mesh is None else "replicated")

    pre = meta["probe"].exists
    args, layout = {"cols": {}}, {}
    if probed:
        layout = meta["probe"].upload(
            args, put, cap, None if pre or folded else meta["valid"], pack)
    if not pre:
        for sc in dim.dag.cols:
            cid = _cid_of(dim.dag, sc)
            if cid == -1 or (want is not None and sc.col.idx not in want):
                continue
            data, nulls, sdict = meta["arrays"][cid]
            if appendable:
                jd = put_col(cid, "d", data, cap)
                jn = None
                if nulls is not None:
                    jn = put_col(cid, "n", nulls, cap, fill=True)
            else:
                jd = put(("fp", cid), data, n, cap)
                jn = None
                if nulls is not None:
                    jn = put(("fpn", cid), nulls, n, cap, fill=True)
            args["cols"][sc.col.idx] = (jd, jn)
            layout[sc.col.idx] = (nulls is not None, sdict)
    return args, layout


def _topn_group_col(plan):
    """(dimension, column idx) the device top-n reads at bucket width
    when it orders by a group item of a position-grouped plan (the `gm`
    of `_make_pipeline_body`) -> tuple or None."""
    spec = getattr(plan, "topn_spec", None)
    if spec is None or spec[0] != "group" or spec[1] >= len(plan.group_items):
        return None
    gm = _pos_group_items(plan)
    if gm is None:
        return None
    kind, di, _c = gm[0][spec[1]]
    return di, (plan.group_items[spec[1]].idx if kind == "dimcol"
                else plan.dims[di].build_key.col.idx)


def _upload_dims(copr, plan, fp, dim_metas, dim_caps, read_ts, mesh,
                 pos_grouped):
    """Upload every dimension for one lowering of the statement:
    `pos_grouped` says whether the join positions stand for the group
    items ("posdense", "posruns"), which decides what a folded root has
    to carry. -> (dim_args, dim_layouts, the roots' pack outcomes and
    a "word32" a table bound for a 32-bit gather, a word or a table of
    positions, for `tidb_tpu_dim_fold_total`)."""
    need, need_pos, tcol = None, (), None
    if fp is not None:
        need = dimfold.needs(plan, fp, pos_grouped)
        tcol = _topn_group_col(plan)
        if pos_grouped:
            need_pos = _pos_group_items(plan)[1]
    dim_args, dim_layouts, outcomes = [], [], []
    for di, (dim, meta, dcap) in enumerate(zip(plan.dims, dim_metas,
                                               dim_caps)):
        want, pack = None, None
        if fp is not None and (fp.masked[di] or fp.parent[di] is not None):
            want = {tcol[1]} if tcol is not None and tcol[0] == di else ()
        if fp is not None and fp.masked[di]:
            # what the program reads through this root's position: all
            # of it composed with the probe table, unless that is the
            # position alone (the table of positions is that word)
            fields = dimfold.pack_fields(plan, fp, di, need, need_pos)
            if set(fields) - {("pos",)}:
                pack = meta["fold"].packed(fields)
                outcomes.append("packed")
                if len(pack.tables) > 1:
                    outcomes.append("packed_spill")
        da, layout = _upload_dim(copr, dim, meta, dcap, read_ts, mesh,
                                 want, pack)
        outcomes += ["word32"] * layout.get("words", ()).count("int32")
        dim_args.append(da)
        dim_layouts.append(layout)
    return dim_args, dim_layouts, outcomes


def _fused_topn_state(plan, fact_tbl, state, kd, sd):
    """Validate the planner's topn_spec against runtime state ->
    spec tuple or None. Device-side top-k over per-run partials is
    exact only when every group lives in at most one partial per
    partition, which requires:
    - an ANCHOR group item: a fact column (or dim probe key) whose
      storage order is verified monotone (ColumnarTable.is_clustered) —
      equal keys adjacent, at most ONE group split per partition edge;
    - every other group item a function of columns reachable from the
      anchor through inner/left unique-key dims (constant within a run);
    - an integer, non-dict primary metric (exact comparisons between
      the kernel's top-k and the host safety check — float metrics
      would risk ulp-level disagreement at the cut boundary)."""
    spec = getattr(plan, "topn_spec", None)
    if spec is None or state.topn_off:
        return None
    kind, ai, desc, k_total = spec
    from ..expression import Column
    from ..types.field_type import TypeClass
    if kind == "agg":
        if ai >= len(plan.aggs):
            return None
        a = plan.aggs[ai]
        if a.name not in ("sum", "count", "min", "max"):
            return None
        if a.args:
            if a.args[0].ft.tclass == TypeClass.FLOAT or sd[ai] is not None:
                return None
    else:
        if ai >= len(plan.group_items):
            return None
        if kd[ai] is not None or \
                plan.group_items[ai].ft.tclass == TypeClass.FLOAT:
            return None
    cid_by_idx = {}
    for sc in plan.fact_dag.cols:
        cid = _cid_of(plan.fact_dag, sc)
        if cid != -1:
            cid_by_idx[sc.col.idx] = cid
    anchor = None
    for g in plan.group_items:
        if isinstance(g, Column) and g.idx in cid_by_idx and \
                fact_tbl.is_clustered(cid_by_idx[g.idx]):
            anchor = g.idx
            break
    if anchor is None:
        return None
    closure = {anchor}
    for _ in range(len(plan.dims) + 1):
        grew = False
        for dim in plan.dims:
            if dim.join_type in ("semi", "anti"):
                continue
            pidx = set()
            for _, pe in dim.all_keys():
                pidx |= _expr_idxs(pe)
            if pidx and pidx <= closure:
                for sc in dim.dag.cols:
                    if sc.col.idx not in closure:
                        closure.add(sc.col.idx)
                        grew = True
        if not grew:
            break
    for g in plan.group_items:
        gi = _expr_idxs(g)
        if not gi or not (gi <= closure):
            return None
    return spec


def _topn_metric_host(spec, aggs, keys, key_nulls, states):
    """Numpy mirror of the kernel's transformed metric (larger = better)
    for the tie-boundary safety check; must stay formula-identical to
    _topn_select."""
    kind, ai, desc, _k = spec
    if kind == "group":
        v = np.asarray(keys[ai]).astype(np.int64)
        nul = np.asarray(key_nulls[ai])
    else:
        st = states[ai]
        v = np.asarray(st[0]).astype(np.int64)
        nul = (np.asarray(st[-1]) == 0) if aggs[ai].name != "count" \
            else np.zeros(len(v), dtype=bool)
    m = v if desc else ~v      # ~v = -v-1: wrap-free order reversal
    # reserve the sentinel ranges: +-(I64_MAX-1).. are taken by the
    # null/empty/forced-boundary markers below and in _topn_select; a
    # metric at int64 extremes clamps, the resulting tie degrades into
    # the coverage check's safe (off) verdict rather than colliding
    m = np.clip(m, -_I64_MAX + 2, _I64_MAX - 2)
    # MySQL null ordering: first on ASC (best), last on DESC (worst)
    return np.where(nul, (-_I64_MAX) if desc else (_I64_MAX - 1), m)


def _topn_select(res, aggs, topn, bucket, group_metric=None):
    """In-kernel candidate selection over the partial-group arrays:
    transformed int64 metric (larger = better), empty slots forced last,
    the partition-boundary groups (run 0 and run ngroups-1, whose
    totals may continue in the neighbouring partition) forced FIRST so
    the host merge always sees both halves. Returns the res contract
    with arrays trimmed to kprime rows plus the selected run ids.
    group_metric: (values, nulls) of the ordering group item where
    res["keys"] holds join positions and not the items ("posruns")."""
    kind, ai, desc, kprime = topn
    ng = res["ngroups"]
    if kind == "group":
        v, nul = group_metric if group_metric is not None else \
            (res["keys"][ai], res["key_nulls"][ai])
        v = v.astype(jnp.int64)
    else:
        st = res["states"][ai]
        v = st[0].astype(jnp.int64)
        nul = (st[-1] == 0) if aggs[ai].name != "count" \
            else jnp.zeros(v.shape, dtype=bool)
    m = v if desc else ~v      # ~v = -v-1: wrap-free order reversal
    m = jnp.clip(m, -_I64_MAX + 2, _I64_MAX - 2)   # keep sentinels unique
    m = jnp.where(nul, (-_I64_MAX) if desc else (_I64_MAX - 1), m)
    iota = jnp.arange(bucket)
    m = jnp.where(iota < ng, m, -_I64_MAX - 1)
    m = jnp.where((iota == 0) | (iota == ng - 1), _I64_MAX, m)
    _, sel = jax.lax.top_k(m, kprime)
    out = {"ngroups": ng, "sel": sel,
           "keys": [k[sel] for k in res["keys"]],
           "key_nulls": [kn[sel] for kn in res["key_nulls"]],
           "states": [[s[sel] for s in st] for st in res["states"]]}
    if "nvalid" in res:
        out["nvalid"] = res["nvalid"]
    return out


def _pos_group_items(plan):
    """Group-by-FK detection: when every group item is either a column of
    an (inner, unique) dimension or the probe key of one, the join
    POSITION already identifies the group — aggregation becomes a direct
    scatter-add into dim-position space, no sort, no key packing.
    (Q3's group (l_orderkey, o_orderdate, o_shippriority) is position-
    in-orders; the reference reaches the same cardinality through its
    hash table, we get it free from the join.)
    -> (group_map, pos_dims) or None; group_map[i] = (kind, di, cid):
    group item i is column `cid` of dimension `di` ("dimcol") or equals
    its build key on every hit ("probekey")."""
    from ..expression import Column
    group_map = []
    for g in plan.group_items:
        m = None
        for di, dim in enumerate(plan.dims):
            if dim.join_type != "inner":
                continue       # left-dim pos is garbage on misses
            if isinstance(g, Column):
                for sc in dim.dag.cols:
                    if sc.col.idx == g.idx:
                        m = ("dimcol", di, _cid_of(dim.dag, sc))
                        break
            if m is None and \
                    g.fingerprint() == dim.probe_expr.fingerprint():
                m = ("probekey", di, _cid_of(dim.dag, dim.build_key))
            if m is not None:
                break
        if m is None:
            return None
        group_map.append(m)
    if not group_map:
        return None
    # a dimension folded under another of the set is a function of that
    # one's position (q10's nation under customer): decoded from it on
    # the host, not kept as a key of its own
    spec = getattr(plan, "topn_spec", None)
    keep = group_map[spec[1]][1] if spec is not None and \
        spec[0] == "group" and spec[1] < len(group_map) else None
    return group_map, dimfold.pos_keys(dimfold.fold_plan(plan),
                                       {di for _, di, _ in group_map}, keep)


def _ident_items(plan):
    """The group items that identify the group of a fused statement's
    partials (`PartialAggResult.ident`): for every dimension whose join
    position is a key of `_pos_group_items`, the item that is unique a
    position of it — its probe key, or its build key as a column. The
    other items are columns at those positions. Holds where the fused
    statement ran: build keys verified unique and non-NULL, inner joins.
    -> tuple of indices into the group items, or None when a dimension
    has no such item (`group by c_name` alone) or joins on several
    columns (its build key alone is not unique)."""
    gm = _pos_group_items(plan)
    if gm is None:
        return None
    group_map, pos_dims = gm
    ident = []
    for di in pos_dims:
        dim = plan.dims[di]
        key_cid = _cid_of(dim.dag, dim.build_key)
        at = next((i for i, (_kind, d, cid) in enumerate(group_map)
                   if d == di and cid == key_cid), None)
        if at is None or key_cid == -1 or dim.extra_keys:
            return None
        ident.append(at)
    return tuple(ident)


def _pos_group_map(plan, dim_metas):
    """_pos_group_items plus the size of the position domain, which
    picks the lowering: slots packed into one array ("posdense") or the
    positions kept as separate run keys ("posruns").
    -> (group_map, pos_dims, nslots) or None."""
    gm = _pos_group_items(plan)
    if gm is None:
        return None
    nslots = 1
    for di in gm[1]:
        nslots *= dim_metas[di]["n"]
    return gm + (nslots,)


def _decode_pos_keys(group_map, poses, dim_metas):
    """Join positions -> the group items' values, on the host: item i is
    column `cid` of dimension `di` at that dimension's position. Shared
    by both position-grouped kinds. poses: {di: int array}.
    -> (keys, key_nulls, key_dicts)."""
    keys, key_nulls, key_dicts = [], [], []
    for kind, di, cid in group_map:
        pos = poses.get(di)
        if pos is None:
            # folded under a dimension whose position is a key
            fold = dim_metas[dim_metas[di]["folded_under"]]["fold"]
            pos = next(fold.pos_at[(a, di)][poses[a]] for a in poses
                       if (a, di) in fold.pos_at)
        data, nulls, sdict = dim_metas[di]["arrays"][cid]
        keys.append(data[pos].astype(np.int64))
        key_nulls.append(nulls[pos] if (kind == "dimcol" and
                                        nulls is not None)
                         else np.zeros(len(pos), dtype=bool))
        key_dicts.append(sdict)
    return keys, key_nulls, key_dicts


def _compact_pos_dense(plan, res, group_map, pos_dims, dim_metas, sd):
    """Decode dim positions back into group-key values (host side)."""
    prefetch(res)
    present = host_array(res["present"])
    slots = np.nonzero(present > 0)[0]
    rem = slots.copy()
    poses = {}
    for di in reversed(pos_dims):
        dn = dim_metas[di]["n"]
        poses[di] = rem % dn
        rem = rem // dn
    keys, key_nulls, key_dicts = _decode_pos_keys(group_map, poses,
                                                  dim_metas)
    states = [[host_array(s)[slots] for s in st] for st in res["states"]]
    return PartialAggResult(ngroups=len(slots), keys=keys,
                            key_nulls=key_nulls, states=states,
                            key_dicts=key_dicts, state_dicts=sd)


def _make_pipeline_body(plan, fact_cap, fact_sdicts, dim_caps, dim_ns,
                        dim_sns, dim_layouts, agg_kind, agg_param,
                        ecap=None, want_fnvalid=False, fold=None):
    """The traced pipeline: filter fact -> dim probes/gathers -> residual
    filters -> partial agg. fact_cap is the (local, for MPP shards) fact
    partition capacity; dim_ns = full dim row counts, dim_sns = valid
    sorted-key counts for searchsorted bounds.

    ecap: early-compaction capacity. Selective fact filters (the
    q14/q19 class: a date-range predicate keeps ~1% of lineitem) make
    every downstream probe gather and agg pass pay full-partition cost
    for mostly-dead lanes. With ecap set, survivors of the FACT-local
    filters are gathered into an ecap-row buffer (an int32 prefix count,
    `prefix_select` for the k-th survivor's lane and gathers — the
    scatter-free kernel policy) and the joins/post
    filters/aggregation run at ecap instead of fact_cap. The caller
    learns ecap per query shape and verifies fnvalid <= ecap (overflow
    regrows the bucket and reruns — the group_bucket retry pattern).
    want_fnvalid: single-chip callers get res["fnvalid"] (the
    fact-filter survivor count) for that policy; the MPP wrapper keeps
    the result pytree unchanged.

    fold: the plan's dimfold.FoldPlan when any dimension folds. A
    dimension resolved under another is not probed here at all; a root
    whose mask is in its probe table gathers no `valid[pos]`, and what
    is read through its position (`dim_layouts[i]["pack"]`) comes out
    of the words its key addresses: one gather a word. None: today's
    program for every dimension."""
    fact_filters = list(plan.fact_dag.filters)
    dims = list(plan.dims)
    post = list(plan.post_filters)
    group_items = list(plan.group_items)
    aggs = list(plan.aggs)
    posruns = agg_kind == "posruns"
    group_only = frozenset()
    if posruns:
        # the positions are the group keys: a dimension column that
        # nothing but the group items reads is decoded from them on the
        # host and never gathered at fact width
        group_map, _pd = _pos_group_items(plan)
        read = set()
        for e in post + [arg for a in aggs for arg in a.args]:
            read |= _expr_idxs(e)
        for dim in dims:
            for _, pe in dim.all_keys():
                read |= _expr_idxs(pe)
        group_only = frozenset(g.idx for g, (kind, _di, _c) in
                               zip(group_items, group_map)
                               if kind == "dimcol") - read

    def body(fjc, fvv, dargs):
        cap = fact_cap
        cols = {k: (d, nl, fact_sdicts[k]) for k, (d, nl) in fjc.items()}
        ctx = EvalCtx(jnp, cap, cols, host=False)
        mask = fvv
        # the stages' named scopes cost nothing at run time: they put
        # the stage into each HLO instruction's metadata, for whoever
        # reads a profile of a `jit_tidb_fused_<kind>` program
        with jax.named_scope("scan_filter"):
            for f in fact_filters:
                mask = mask & eval_bool_mask(ctx, f)
        if ecap is not None:
            with jax.named_scope("compact"):
                src, fnvalid = prefix_select(
                    mask, jnp.arange(1, ecap + 1), "early_compact")
                src = jnp.minimum(src, cap - 1)
                cols = {k: (d[src], None if nl is None else nl[src], sd)
                        for k, (d, nl, sd) in cols.items()}
                cap = ecap
                mask = jnp.arange(ecap, dtype=jnp.int64) < fnvalid
                ctx = EvalCtx(jnp, cap, cols, host=False)
        elif want_fnvalid:
            fnvalid = jnp.sum(mask.astype(jnp.int64))
        dim_pos = {}
        for dim_i, (dim, da, dcap, dn, dsn, layout) in enumerate(
                zip(dims, dargs, dim_caps, dim_ns, dim_sns, dim_layouts)):
            if fold is not None and fold.parent[dim_i] is not None:
                continue               # resolved at its root's width
            masked = fold is not None and fold.masked[dim_i]
            with jax.named_scope("dim_probe"):
                if layout["exists"] or masked or (
                        layout.get("visible") and not dim.dag.filters):
                    # nothing to read at the position: its filters and
                    # visibility are in the table (`exists`, a folded
                    # root), or a hit is a visible row and none filters
                    dmask = None
                else:
                    dcols = {}
                    for idx, (jd, jn) in da["cols"].items():
                        dcols[idx] = (jd, jn, layout[idx][1])
                    dctx = EvalCtx(jnp, dcap, dcols, host=False)
                    dmask = da["valid"]
                    for f in dim.dag.filters:
                        dmask = dmask & eval_bool_mask(dctx, f)
                if dim.extra_keys:
                    # composite key: pack probes with the build-side layout;
                    # out-of-range components force a miss (a clipped index
                    # could otherwise alias a live packed key)
                    pv = jnp.zeros(cap, dtype=jnp.int64)
                    pnm = jnp.zeros(cap, dtype=bool)
                    inb_pack = jnp.ones(cap, dtype=bool)
                    kidx = []
                    for ki, (_, pe) in enumerate(dim.all_keys()):
                        v, nl, _ = eval_expr(ctx, pe)
                        if np.isscalar(v) or getattr(v, "ndim", 1) == 0:
                            v = jnp.full(cap, v)
                        v = v.astype(jnp.int64)
                        pnm = pnm | materialize_nulls(ctx, nl)
                        idx = v - da["plo"][ki]
                        inb_pack = inb_pack & (idx >= 0) & \
                            (idx < da["pspan"][ki])
                        idx = jnp.clip(idx, 0, da["pspan"][ki] - 1)
                        kidx.append(idx)
                        pv = pv + idx * da["pstride"][ki]
                    pnm = pnm | ~inb_pack
                else:
                    pv, pnl, _ = eval_expr(ctx, dim.probe_expr)
                    if np.isscalar(pv) or getattr(pv, "ndim", 1) == 0:
                        pv = jnp.full(cap, pv)
                    pv = pv.astype(jnp.int64)
                    pnm = materialize_nulls(ctx, pnl)
                    kidx = None
                pos, hit = probe.resolve(da, layout, pv, kidx, pnm, dn, dsn,
                                         dcap, masked)
                pk = layout.get("pack")
                if pk is not None:
                    # a folded root's composed words came back in the
                    # position's place: every field a shift and a mask
                    # of one; the sign bit of word 0 is the miss
                    words = pos
                    mask = mask & hit & (words[0] >= 0) & ~pnm
                    got = {(kind, ident): dimfold.unpack_field(
                        words[wi], da["fshift"][fi], da["fmask"][fi],
                        da["flo"][fi], dt)
                        for fi, (kind, ident, wi, dt) in enumerate(pk)}
                    for (kind, ident), v in got.items():
                        if kind == "col":
                            cols[ident] = (v, got.get(("null", ident)),
                                           layout[ident][1])
                        elif kind != "null":
                            dim_pos[dim_i if kind == "pos" else ident] = v
                    ctx = EvalCtx(jnp, cap, cols, host=False)
                    continue
                if dmask is not None:
                    hit = hit & dmask[pos]
                if dim.join_type == "left":
                    # preserved side: misses keep the row, payload is NULL
                    for idx, (jd, jn) in da["cols"].items():
                        g = jd[pos]
                        gn = ~hit if jn is None else (~hit | jn[pos])
                        cols[idx] = (g, gn, layout[idx][1])
                elif dim.join_type == "anti":
                    # NOT EXISTS: keep only rows with NO match (NULL probe
                    # keys never match, so they survive — EXISTS-derived
                    # anti semantics; null-aware NOT IN never plans here)
                    mask = mask & ~hit
                else:
                    mask = mask & hit
                    if dim.join_type != "semi":
                        for idx, (jd, jn) in da["cols"].items():
                            # (a folded root's are the top-n's, read
                            # at bucket width)
                            if idx in group_only or masked:
                                continue
                            g = jd[pos]
                            gn = jn[pos] if jn is not None else None
                            cols[idx] = (g, gn, layout[idx][1])
                dim_pos[dim_i] = jnp.minimum(pos, dn - 1)
                ctx = EvalCtx(jnp, cap, cols, host=False)
        with jax.named_scope("scan_filter"):
            for f in post:
                mask = mask & eval_bool_mask(ctx, f)
        if agg_kind == "posdense":
            pos_dims, nslots = agg_param
            with jax.named_scope("group_agg"):
                slot = jnp.zeros(cap, dtype=jnp.int64)
                for di in pos_dims:
                    slot = slot * dim_ns[di] + dim_pos[di]
                slot = jnp.where(mask, slot, nslots)
                res = dense_agg_states(ctx, mask, aggs, slot, nslots,
                                       cap)
            if ecap is not None or want_fnvalid:
                res["fnvalid"] = fnvalid
            return res
        if agg_kind == "dense":
            with jax.named_scope("group_agg"):
                res = dense_agg_body(ctx, mask, group_items, aggs,
                                     agg_param, cap)
            if ecap is not None or want_fnvalid:
                res["fnvalid"] = fnvalid
            return res
        if agg_kind == "onehot":
            sargs = dargs[len(dims)]
            with jax.named_scope("group_agg"):
                res = onehot_agg_body(ctx, mask, group_items, aggs,
                                      cap, *agg_param, sargs)
                res["nvalid"] = jnp.sum(mask.astype(jnp.int64))
            if ecap is not None or want_fnvalid:
                res["fnvalid"] = fnvalid
            return res
        # "sort" and "posruns" share everything but the run keys:
        # agg_param[1] is the segment impl or the position dims
        gb, agg_impl, topn, ccap = agg_param
        pos_dims = agg_impl if posruns else ()
        pkeys = [dim_pos[di] for di in pos_dims]
        actx, amask, acap = ctx, mask, cap
        if ccap is None:
            with jax.named_scope("compact"):
                csum = jnp.cumsum(mask.astype(jnp.int64))
                nvalid = csum[cap - 1]
        else:
            # compact-then-aggregate (selective pipelines, the
            # Q18/Q21 class): the sort-based agg pays O(cap log cap)
            # on the FULL padded partition even when a semi/anti dim
            # kills almost every row. Gather the survivors into a
            # small learned-capacity buffer first — an int32 prefix
            # count, the k-th survivor's lane by rows of block ends
            # (prefix_select) and gathers only (the scatter-free
            # kernel policy) — and aggregate that. The caller verifies
            # nvalid <= ccap (an overflow regrows the bucket and
            # reruns, the group_bucket retry pattern).
            with jax.named_scope("compact"):
                src, nvalid = prefix_select(
                    mask, jnp.arange(1, ccap + 1), "late_compact")
                src = jnp.minimum(src, cap - 1)
                ok = jnp.arange(ccap, dtype=jnp.int64) < nvalid
                ccols = {}
                for cidx, (d, nl, sd) in cols.items():
                    ccols[cidx] = (d[src],
                                   None if nl is None else nl[src], sd)
                pkeys = [k[src] for k in pkeys]
                actx, amask, acap = EvalCtx(jnp, ccap, ccols,
                                            host=False), ok, ccap
        with jax.named_scope("group_agg"):
            if posruns:
                # masked lanes carry whatever position the probe left
                # them: the core drops wholly masked runs, and a run
                # they split is two partials the host merge adds up
                res = runs_agg_core(pkeys, None, amask, actx, aggs,
                                    acap, gb)
            else:
                res = sort_agg_body(actx, amask, group_items, aggs, acap,
                                    gb, impl=agg_impl)
        res["nvalid"] = nvalid
        if topn is not None:
            with jax.named_scope("topn"):
                gm = None
                if posruns and topn[0] == "group":
                    # the ordering item's values, at bucket width
                    kind, di, _c = group_map[topn[1]]
                    at = res["keys"][pos_dims.index(di)]
                    jd, jn = dargs[di]["cols"][
                        group_items[topn[1]].idx if kind == "dimcol"
                        else dims[di].build_key.col.idx]
                    gm = (jd[at], jnp.zeros(gb, dtype=bool)
                          if jn is None or kind != "dimcol" else jn[at])
                res = _topn_select(res, aggs, topn, gb, gm)
        if ecap is not None or want_fnvalid:
            res["fnvalid"] = fnvalid
        return res
    return body


def _build_fused_kernel(plan, fact_cap, fact_sdicts, dim_caps, dim_ns,
                        dim_sns, dim_layouts, agg_kind, agg_param,
                        ecap=None, fold=None):
    body = _make_pipeline_body(plan, fact_cap, fact_sdicts, dim_caps,
                               dim_ns, dim_sns, dim_layouts, agg_kind,
                               agg_param, ecap=ecap, want_fnvalid=True,
                               fold=fold)
    # donate the fact validity mask: per-dispatch scratch rebuilt by
    # _pad_upload every call; dim args and fact columns ride the
    # resident pool and must never be donated
    dn = jaxcfg.donation_argnums(1)
    jaxcfg.name_program(body, "fused_" + agg_kind)
    return jaxcfg.guard_donation(jax.jit(body, donate_argnums=dn), dn)


def _build_fused_kernel_mpp(plan, local_cap, fact_sdicts, dim_caps,
                            dim_ns, dim_sns, dim_layouts, agg_kind,
                            agg_param, mesh, fold=None):
    """The fused pipeline as ONE shard_map program: fact shards ride the
    'dp' mesh axis (PassThrough exchange from the scan), dims are
    replicated (Broadcast exchange), and the partial aggregation merges
    across shards — psum/pmin/pmax allreduces for dense layouts, stacked
    per-shard partials (host merge) for the general sort layout."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    body = _make_pipeline_body(plan, local_cap, fact_sdicts, dim_caps,
                               dim_ns, dim_sns, dim_layouts, agg_kind,
                               agg_param, fold=fold)
    aggs = list(plan.aggs)
    dense = agg_kind in ("dense", "posdense")

    def frag(fjc, fvv, dargs):
        res = body(fjc, fvv, dargs)
        if dense:
            return psum_dense_result(res, aggs, "dp")
        # sort layout: per-shard partials, stacked along the mesh axis
        res["ngroups"] = res["ngroups"][None]
        if "nvalid" in res:
            res["nvalid"] = res["nvalid"][None]
        return res

    if dense:
        out_spec = P()
    else:
        out_spec = P("dp")
    jaxcfg.name_program(frag, "mpp_fused_" + agg_kind)
    fn = shard_map(frag, mesh=mesh, in_specs=(P("dp"), P("dp"), P()),
                   out_specs=out_spec, check_vma=False)
    return jax.jit(fn)


def _delta_partition(plan, fact_tbl, fact_arrays, delta_rows):
    """Shape a transaction's uncommitted INSERT rows like one more fact
    partition (reference UnionScan's txn-buffer merge, re-designed as a
    device overlay): {plan col idx -> (data, nulls, sdict)} + valid.
    Null-array presence mirrors the committed snapshot so the kernel's
    pytree (and its compiled program) is unchanged."""
    n = len(delta_rows)
    handles = np.array([h for h, _ in delta_rows], dtype=np.int64)
    info = fact_tbl.table_info
    off_of = {ci.id: off for off, ci in enumerate(info.columns)}
    cols = {}
    for sc in plan.fact_dag.cols:
        cid = _cid_of(plan.fact_dag, sc)
        if cid == -1:
            cols[sc.col.idx] = (handles, None, None)
            continue
        snap_data, snap_nulls, sdict = fact_arrays[cid]
        off = off_of[cid]
        data = np.zeros(n, dtype=snap_data.dtype)
        nulls = np.zeros(n, dtype=bool)
        for r, (_h, datums) in enumerate(delta_rows):
            d = datums[off] if off < len(datums) else None
            if d is None or d.is_null:
                nulls[r] = True
                continue
            if sdict is not None:
                v = d.val
                data[r] = sdict.encode_one(
                    v if isinstance(v, str) else str(v))
            elif data.dtype == np.float64:
                data[r] = float(d.val)
            elif data.dtype == object:
                data[r] = d.val
            else:
                v = int(d.val)
                if v > 0x7FFFFFFFFFFFFFFF:
                    v -= 1 << 64
                data[r] = v
        nl = nulls if snap_nulls is not None else (
            None if not nulls.any() else nulls)
        cols[sc.col.idx] = (data, nl, sdict)
    return cols, np.ones(n, dtype=bool)


def _delta_in_span(shim, sizes, delta_part):
    """Do the delta rows' group keys fall inside the dense layout's
    span? Evaluated on host over the (tiny) delta partition: group item
    i must land in [off, off + size - 2] (dense_agg_body maps value d
    to code d - off + 1, clipped to size - 1; NULLs take slot 0).
    Group items referencing DIM columns can't be checked here — the
    delta probes dims inside the kernel — so only fact-only group
    expressions qualify; anything else keeps the sort lowering."""
    dcols, dv = delta_part
    nd = len(dv)
    if nd == 0:
        return True
    ctx = EvalCtx(np, nd, dcols, host=True)
    for g, (size, off) in zip(shim.group_items, sizes):
        refs = set()
        g.collect_columns(refs)
        if not refs <= set(dcols):
            return False
        try:
            d, nl, sdict = eval_expr(ctx, g)
        except Exception:               # noqa: BLE001
            return False
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = np.full(nd, d)
        d = np.asarray(d)
        if d.dtype.kind not in "iu":
            return False
        nm = np.asarray(materialize_nulls(ctx, nl))
        live = d[~nm] if nm.any() else d
        if len(live) and (int(live.min()) < off or
                          int(live.max()) > off + size - 2):
            return False
    return True


def _oh_learn_table(state, plan, oh_learn, rows=0, version=None):
    """Build the one-hot slot table from a completed sorted/runs
    execution's partials: union the per-partition group keys, pack them
    with host-chosen offsets/spans (the kernel range-checks each code,
    so any later out-of-span value is a miss, never an alias), and
    store the sorted packed table + per-slot key columns.

    ``rows``/``version`` record the fact coverage watermark (the
    version read BEFORE the snapshot, the snapshot's row count): the
    bind-time delta fold (_oh_fold_delta) extends the table from rows
    [rows, n) instead of letting an appended key force a
    miss-pop-relearn — the version-advance/delta contract the vector
    index follows (ROADMAP item #5 learned-structure tail)."""
    K = len(plan.group_items)
    kcols = [np.concatenate([e[0][i] for e in oh_learn])
             for i in range(K)]
    knulls = [np.concatenate([e[1][i] for e in oh_learn])
              for i in range(K)]
    # derive spans and REJECT before packing: a full-range key column
    # would otherwise overflow the int64 pack multiply (the kernel has
    # the same <61-bit bound, so such shapes can never one-hot anyway)
    los, spans = [], []
    total_bits = 0.0
    for i in range(K):
        vals = kcols[i]
        if vals.dtype.kind not in "iu":
            state.onehot = False
            return
        nn = vals[~knulls[i]]
        lo = int(nn.min()) if len(nn) else 0
        hi = int(nn.max()) if len(nn) else 0
        if vals.dtype.kind == "u" and (lo > _I64_MAX or hi > _I64_MAX):
            # uint64 keys above int63: np.asarray(los, int64) below
            # would raise an uncaught OverflowError, and the kernel's
            # int64 packing could never represent them anyway — pin the
            # shape off the one-hot path like non-integer dtypes
            state.onehot = False
            return
        span = hi - lo + 2
        total_bits += np.log2(max(span, 1))
        los.append(lo)
        spans.append(span)
    if total_bits >= 61.0:
        state.onehot = False
        return
    packed = np.zeros(len(kcols[0]), dtype=np.int64)
    for i in range(K):
        code = np.where(knulls[i], 0,
                        kcols[i].astype(np.int64) - los[i] + 1)
        packed = packed * spans[i] + code
    uniq, idx = np.unique(packed, return_index=True)
    nslots = len(uniq)
    if not _al.onehot_fits(nslots):
        state.onehot = False
        return
    scap = 128
    while scap < nslots:
        scap <<= 1
    skeys = np.full(scap, _I64_MAX, dtype=np.int64)
    skeys[:nslots] = uniq
    state.onehot = {
        "skeys": skeys, "los": np.asarray(los, dtype=np.int64),
        "spans": np.asarray(spans, dtype=np.int64),
        "nslots": nslots, "scap": scap,
        "key_vals": [kcols[i][idx] for i in range(K)],
        "key_nulls": [knulls[i][idx] for i in range(K)],
        "rows": rows, "version": version,
    }


def _oh_tail_keys(copr, plan, fact_arrays, lo, hi):
    """Group-key columns of fact rows [lo, hi) evaluated on host —
    the delta fold's input. None when a group item reaches beyond the
    fact columns (dim-joined keys: the fold cannot see those rows'
    join results; the dispatch-time miss path still covers them)."""
    cols = {}
    for sc in plan.fact_dag.cols:
        cid = _cid_of(plan.fact_dag, sc)
        if cid == -1:
            continue
        data, nulls, sdict = fact_arrays[cid]
        cols[sc.col.idx] = (data[lo:hi],
                            None if nulls is None else nulls[lo:hi],
                            sdict)
    m = hi - lo
    ectx = EvalCtx(np, m, cols, host=True)
    kcols, knulls = [], []
    try:
        for g in plan.group_items:
            d, nl, _sd = eval_expr(ectx, g)
            if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
                d = np.full(m, d)
            d = np.asarray(d)
            if d.dtype.kind not in "iu":
                return None
            kcols.append(d.astype(np.int64))
            knulls.append(np.asarray(materialize_nulls(ectx, nl)))
    except Exception:                       # noqa: BLE001
        return None
    return kcols, knulls


def _oh_fold_delta(copr, state, plan, fact_arrays, n, version):
    """Version-advance/delta maintenance of a learned one-hot slot
    table: fold the keys of appended fact rows [rows, n) into the
    table at bind time — new in-span keys become new slots (the
    kernel reuses the same scap program; nslots is a device operand)
    — instead of rebuilding the whole table from a sorted re-execution
    on the first dispatch-time miss. Out-of-span keys or slot-count
    overflow still pop for a relearn (metered fused_onehot_rebuild);
    an append of existing keys is a pure watermark advance."""
    OH = state.onehot
    if not isinstance(OH, dict):
        return
    rows = OH.get("rows", 0)
    # ``version``/``n`` are the caller's pre-snapshot version and the
    # snapshot's row count — the fold must never claim rows past the
    # arrays it actually reads
    if OH.get("version") == version:
        return
    dom = getattr(copr, "domain", None)
    if n <= rows:
        # delete/update tombstones (or a shorter snapshot): slots are
        # unaffected — zero-count slots drop at decode time
        OH["version"] = version
        return
    tail = _oh_tail_keys(copr, plan, fact_arrays, rows, n)
    if tail is None:
        return                  # dim-joined keys: miss path owns this
    kcols, knulls = tail
    K = len(plan.group_items)
    los, spans = OH["los"], OH["spans"]
    packed = np.zeros(n - rows, dtype=np.int64)
    for i in range(K):
        v = kcols[i]
        nm = knulls[i]
        live = ~nm
        if live.any() and (int(v[live].min()) < int(los[i]) or
                           int(v[live].max()) > int(los[i]) +
                           int(spans[i]) - 2):
            # outside the learned span: the packing cannot represent
            # it — relearn from scratch (the only rebuild left)
            del state.onehot
            if dom is not None:
                dom.inc_metric("fused_onehot_rebuild")
            return
        code = np.where(nm, 0, v - int(los[i]) + 1)
        packed = packed * int(spans[i]) + code
    nslots = OH["nslots"]
    old_keys = OH["skeys"][:nslots]
    uniq, first = np.unique(packed, return_index=True)
    fresh = ~np.isin(uniq, old_keys)
    if not fresh.any():
        OH["rows"], OH["version"] = n, version
        return
    merged = np.concatenate([old_keys, uniq[fresh]])
    order = np.argsort(merged, kind="stable")
    nnew = len(merged)
    if not _al.onehot_fits(nnew):
        state.onehot = False        # pin off like the learn path
        if dom is not None:
            dom.inc_metric("fused_onehot_rebuild")
        return
    scap = OH["scap"]
    while scap < nnew:
        scap <<= 1
    skeys = np.full(scap, _I64_MAX, dtype=np.int64)
    skeys[:nnew] = merged[order]
    fidx = first[fresh]
    key_vals, key_nulls = [], []
    for i in range(K):
        kv = np.concatenate([OH["key_vals"][i],
                             kcols[i][fidx].astype(
                                 OH["key_vals"][i].dtype, copy=False)])
        kn = np.concatenate([OH["key_nulls"][i], knulls[i][fidx]])
        key_vals.append(kv[order])
        key_nulls.append(kn[order])
    # replace the dict wholesale: in-flight dispatches carry their own
    # table reference (oh_table in the dispatch state) and stay
    # consistent; the next dispatch binds the extended one
    state.onehot = {
        "skeys": skeys, "los": los, "spans": spans,
        "nslots": nnew, "scap": scap,
        "key_vals": key_vals, "key_nulls": key_nulls,
        "rows": n, "version": version,
    }
    if dom is not None:
        dom.inc_metric("fused_onehot_delta_fold")


def _bind_tables(copr, plan, read_ts, ctx, fp=None, sp=None):
    """The statement's first `bind`: fold committed deltas into the
    resident buffers of the fact table and every dimension, build or
    find each dimension's metadata — and, over it, the folded tables of
    the plan's chains (`fp`, dimfold.py; `sp`, the open span, takes
    their count) — snapshot the fact columns.
    -> (fact_tbl, dim_metas, fact_version, fact_arrays, fact_valid), or
    the answer itself ([] for no rows, None for runtime-ineligible)."""
    engine = copr.engine
    fact_tbl = engine.table(plan.fact_dag.table_info)
    # incremental HTAP: fold committed deltas into resident buffers
    # FIRST (patched entries advance their version and survive), then
    # sweep what stayed stale (derived entries, unpatchable buffers) —
    # copr/delta.py; this used to be a full drop-and-reupload per
    # DML commit
    copr.delta.refresh(fact_tbl, ctx)
    copr._dev_store.invalidate(fact_tbl.uid, fact_tbl.version)
    dim_metas = []
    for di, dim in enumerate(plan.dims):
        if dim.subplan is not None:
            meta = _materialized_dim_meta(copr, ctx, dim, read_ts)
            if meta is None:
                return None
            dim_metas.append(meta)
            continue
        tbl = engine.table(dim.dag.table_info)
        copr.delta.refresh(tbl, ctx)
        copr._dev_store.invalidate(tbl.uid, tbl.version)
        if tbl.n == 0:
            if dim.join_type in ("inner", "semi"):
                return []         # inner/semi with empty dim: no rows
            # LEFT/ANTI over an empty dim preserve the fact side (NULL
            # payload / all-miss): a 1-row always-miss dim keeps every
            # shape static
            arrays = {}
            for sc in dim.dag.cols:
                cid = _cid_of(dim.dag, sc)
                if cid == -1:
                    continue
                arrays[cid] = (np.zeros(1, dtype=tbl.data[cid].dtype),
                               None, tbl.dicts.get(cid))
            dim_metas.append({
                "arrays": arrays, "valid": np.zeros(1, dtype=bool),
                "n": 1, "tbl": tbl, "probe": ProbeTable.always_miss(1),
                # arrays are fabricated 1-row placeholders, NOT the
                # table's append-only columns: they must never enter
                # the delta-maintained append seam under this uid
                "synthetic": True})
            continue
        meta = _dim_sort_meta(copr, dim, tbl, read_ts)
        if meta is None:
            if fp is not None and fp.parent[di] is not None:
                # duplicated, NULL or non-integer build keys: no join
                # position to resolve at the parent's width (nor a
                # fused statement at all)
                dimfold.count("declined_child_ineligible")
            return None
        dim_metas.append(meta)
    if fp is not None:
        dim_metas, folds, builds = dimfold.bind_folds(
            copr, plan, fp, dim_metas, read_ts, ctx)
        if sp is not None:
            sp.attrs["folds"] = folds
            sp.attrs["fold_builds"] = builds

    # version BEFORE the snapshot (delta.refresh rationale): the one-hot
    # coverage watermark must never claim rows it did not see
    fact_version = fact_tbl.version
    fact_arrays, fact_valid = fact_tbl.snapshot(
        [cid for cid in (_cid_of(plan.fact_dag, sc)
                         for sc in plan.fact_dag.cols) if cid != -1],
        read_ts)
    return fact_tbl, dim_metas, fact_version, fact_arrays, fact_valid


def fused_partials(copr, plan, read_ts, mesh=None,
                   bcast_threshold=1 << 20, ctx=None, delta_rows=None,
                   dead_handles=None):
    """Execute a PhysFusedPipeline -> [PartialAggResult] (one per fact
    partition; one per mesh shard for the MPP sort layout), or None when
    runtime-ineligible (caller falls back to the conventional subtree).
    With a mesh, the whole pipeline runs as one shard_map program: fact
    sharded over 'dp', dims broadcast, aggregation allreduced."""
    fp = dimfold.fold_plan(plan) if plan.dims else None
    with phase.bind_span() as sp:
        bound = _bind_tables(copr, plan, read_ts, ctx, fp, sp)
    if not isinstance(bound, tuple):
        return bound
    if fp is not None and not any(fp.masked):
        fp = None           # nothing folds: today's program and operands
    fact_tbl, dim_metas, fact_version, fact_arrays, fact_valid = bound
    n = len(fact_valid)
    if n == 0 and not delta_rows:
        return []
    handles = fact_tbl.handle_array()
    if len(handles) > n:
        handles = handles[:n]
    if dead_handles:
        # txn updated/deleted committed fact rows: mask their old
        # versions out of the base snapshot (new versions, if any,
        # arrive via the delta partition). & makes a fresh array —
        # the snapshot's validity may be cached/shared.
        fact_valid = fact_valid & ~np.isin(
            handles, np.asarray(dead_handles, dtype=np.int64))

    if mesh is not None:
        # a build side too large to replicate routes through the HASH
        # exchange (all_to_all shuffle) instead of Broadcast
        sh = _try_fused_shuffle(copr, plan, mesh, dim_metas, fact_tbl,
                                fact_arrays, fact_valid, n, handles,
                                bcast_threshold, ectx=ctx)
        if sh is not None:
            return sh

    dim_caps = [shape_bucket(m["n"]) for m in dim_metas]
    dim_ns = [m["n"] for m in dim_metas]
    dim_sns = [m["probe"].n_sorted for m in dim_metas]
    dim_up = {}

    def _dims_for(pos_grouped):
        """The dimensions on the device, uploaded once a statement
        (shared across fact partitions) -> (dim_args, dim_layouts).
        What a folded root carries depends on whether the join
        positions stand for the group items, so a statement that
        changes its lowering between row blocks uploads twice."""
        pos_grouped = pos_grouped and fp is not None
        if pos_grouped not in dim_up:
            with phase.bind_span() if plan.dims else _tracing.NO_SPAN \
                    as sp:
                *up, outcomes = _upload_dims(
                    copr, plan, fp, dim_metas, dim_caps, read_ts, mesh,
                    pos_grouped)
                for o in outcomes:
                    dimfold.count(o)
                if sp is not None and fp is not None:
                    sp.attrs["packed_roots"] = outcomes.count("packed")
                    sp.attrs["word32"] = outcomes.count("word32")
                if not dim_up:      # once a statement, not a lowering
                    # what resolves each dimension at fact width
                    probes = [m["probe"].label(d, "folded_under" in m)
                              for d, m in zip(plan.dims, dim_metas)]
                    for d, mode in zip(plan.dims, probes):
                        _metrics.FUSED_DIM_PROBE.labels(d.join_type,
                                                        mode).inc()
                    if sp is not None:
                        sp.attrs["probes"] = "+".join(probes)
                dim_up[pos_grouped] = up
        return dim_up[pos_grouped]

    # 1-row host ctx over ALL pipeline columns: learn output dicts and
    # whether a dense group layout applies (dict-coded keys only here —
    # int min/max dense detection would need a host pass over gathered
    # values, which the fused path deliberately avoids)
    one = {}
    for sc in plan.fact_dag.cols:
        cid = _cid_of(plan.fact_dag, sc)
        if cid == -1:
            one[sc.col.idx] = (handles[:1] if len(handles)
                               else np.zeros(1, np.int64), None, None)
        else:
            data, nulls, sdict = fact_arrays[cid]
            one[sc.col.idx] = (data[:1] if len(data)
                               else np.zeros(1, data.dtype), None, sdict)
    for dim, meta in zip(plan.dims, dim_metas):
        if dim.join_type in ("semi", "anti"):
            continue
        for sc in dim.dag.cols:
            cid = _cid_of(dim.dag, sc)
            if cid == -1:
                continue
            data, nulls, sdict = meta["arrays"][cid]
            one[sc.col.idx] = (data[:1] if len(data)
                               else np.zeros(1, data.dtype), None, sdict)
    # the delta partition builds BEFORE layout decisions: its dict
    # encodes extend the shared dicts, so dict-derived dense sizes
    # already cover delta codes (the HTAP overlay must not lose the
    # dense lowering for every in-span write)
    delta_part = None
    if delta_rows:
        delta_part = _delta_partition(plan, fact_tbl, fact_arrays,
                                      delta_rows)
    shim = _AggShim(plan.group_items, plan.aggs)
    kd, sd = capture_agg_dicts(shim, one)
    # what earlier runs taught about this (table, gc epoch, group items,
    # aggregates) shape: bucket, impl pin, compaction, top-n, one-hot
    st = _al.ShapeState(copr, fact_tbl, plan.group_items, plan.aggs)

    fcols = None

    def _dense_sizes():
        """The dense layout of the group items, or None. Asked for only
        when no position domain stands."""
        nonlocal fcols
        fact_idxs = {sc.col.idx for sc in plan.fact_dag.cols}
        if n and (not plan.dims or all(
                dimfold._idxs(g) <= fact_idxs
                for g in plan.group_items)):
            # group items that read the fact's own columns alone (a
            # zero-dim pipeline: q15's GROUP BY l_suppkey; Q13's
            # customers under their counted orders): int group keys can
            # dense-detect via a host min/max pass over the fact arrays,
            # exactly like the copr reader path — without this they
            # fall to the sort lowering. Items that read a dimension
            # would need a host pass over gathered values, which the
            # fused path deliberately avoids
            fcols = {}
            for sc in plan.fact_dag.cols:
                cid = _cid_of(plan.fact_dag, sc)
                fcols[sc.col.idx] = (handles, None, None) if cid == -1 \
                    else fact_arrays[cid]
        sizes = dense_strides(shim, kd, fcols, n)
        if sizes is not None and delta_part is not None and \
                not _delta_in_span(shim, sizes, delta_part):
            # dense layouts clip group codes to the derived span: a
            # delta key OUTSIDE it would silently merge into a boundary
            # group — those executions take the exact sort lowering
            return None
        return sizes

    low = _al.Lowering(
        st, _pos_group_map(plan, dim_metas), _dense_sizes,
        site="fused" if mesh is None else "fused_mpp",
        dims=bool(plan.dims),
        topn=None if mesh is not None else
        _fused_topn_state(plan, fact_tbl, st, kd, sd),
        unclustered=lambda: fcols is not None and
        _al.host_unclustered(shim, fcols, n))

    fact_sdicts = {k: v[2] for k, v in one.items()
                   if k in {sc.col.idx for sc in plan.fact_dag.cols}}
    out = []
    step = copr.device_rows
    # one-hot MXU lowering: a host-learned slot table replaces the
    # device argsort for small group domains (onehot_agg_body). Learned
    # from the first sorted/runs execution, invalidated by misses
    # (new/changed keys) at consume time. Fold appended rows' keys into
    # a learned table BEFORE any dispatch binds it: an in-bucket append
    # must extend slots, not force a dispatch-time miss-pop-relearn
    _oh_fold_delta(copr, st, plan, fact_arrays, n, fact_version)
    oh_learn = []
    oh_parts = []
    oh_elig = low.onehot_learnable(plan.group_items, plan.aggs, one,
                                   delta_rows)
    if mesh is not None:
        dim_args, dim_layouts = _dims_for(low.pos is not None)
        return _run_fused_mpp(
            copr, plan, mesh, fact_tbl, fact_arrays, fact_valid, n,
            handles, dim_args, dim_metas, dim_caps, dim_ns, dim_sns,
            dim_layouts, fact_sdicts, low, shim, kd, sd, read_ts, fp)
    # which group items identify a "sort" / "posruns" partial's groups
    ident = _ident_items(plan)
    # the lowering the first row block will take: its operands go up
    # before the loop, in a `bind` of the statement's own
    _dims_for(low.choose(0)[0] in ("posdense", "posruns"))
    # row blocks of this run: the fact's, plus the transaction's own
    # rows as one more; the `dispatch`/`consume` spans carry the numbers
    parts = -(-n // step) + (delta_part is not None)

    def _partitions():
        for start in range(0, n, step):
            sl = slice(start, min(start + step, n))
            pm = sl.stop - sl.start
            with phase.bind_span():
                pcols = copr._bind_cols(plan.fact_dag, fact_tbl,
                                        fact_arrays, sl, handles,
                                        cacheable=(n == fact_tbl.n),
                                        valid=fact_valid)
            # capture this partition's device-cache keys: the pipelined
            # loop dispatches the NEXT partition (overwriting
            # copr._bind_keys) before this one's consume-time retries
            yield pcols, fact_valid[sl], pm, dict(copr._bind_keys)
        if delta_part is not None:
            # the transaction's uncommitted inserts as one more fact
            # partition through the SAME kernel (device UnionScan);
            # empty bind keys: never device-cache dirty rows
            dcols, dv = delta_part
            yield dcols, dv, len(dv), {}

    def _dispatch_part(cols, v, m, bind_keys):
        """Upload + async-dispatch one fact partition with the
        currently learned lowering parameters. Returns everything the
        consume step needs to validate the run."""
        cap = shape_bucket(m)
        agg_kind, agg_param, ecap = low.choose(cap)
        dim_args, dim_layouts = _dims_for(
            agg_kind in ("posdense", "posruns"))
        key = _fused_cache_key(copr, plan, fact_tbl, dim_metas, cap,
                               tuple(dim_caps), tuple(dim_ns),
                               tuple(dim_sns), agg_kind, agg_param,
                               ecap, fp, dim_layouts)
        kern = copr._kernel_cache.get(key)
        if kern is None:
            kern = _build_fused_kernel(
                plan, cap, fact_sdicts, tuple(dim_caps),
                tuple(dim_ns), tuple(dim_sns), tuple(dim_layouts),
                agg_kind, agg_param, ecap=ecap, fold=fp)
            kern = copr._kernel_cache.put(key, kern)
        with phase.bind_span():
            fjc_full, fvv = copr._pad_upload(cols, v, m, cap,
                                             bind_keys=bind_keys)
        fjc = {k: (d, nl) for k, (d, nl, _) in fjc_full.items()}
        kargs = dim_args
        oh_table = None
        if agg_kind == "onehot":
            # carry the table in the dispatch state: a sibling
            # pipelined partition's miss may pop the learned entry
            # before this partition consumes, so consume must never
            # re-read it
            oh_table = st.onehot
            dev = oh_table.get("dev")
            if dev is None:
                dev = {"skeys": jnp.asarray(oh_table["skeys"]),
                       "los": jnp.asarray(oh_table["los"]),
                       "spans": jnp.asarray(oh_table["spans"]),
                       "nslots": jnp.asarray([oh_table["nslots"]],
                                             dtype=jnp.int64)}
                oh_table["dev"] = dev
            kargs = list(dim_args) + [dev]
        # chaos hook: per-partition kernel dispatch. The supervised
        # retry lives one level up (executors.FusedPipeline.partials
        # wraps the whole fused_partials call in device_guard) — the
        # kernel cache makes a whole-call retry cheap.
        failpoint.inject("device_guard/fused/kernel")
        # tpulint: disable=unguarded-dispatch — the supervised retry
        # lives one level up (executors.FusedPipeline wraps the whole
        # fused_partials call in guarded_dispatch site="fused")
        res = prefetch(kern(fjc, fvv, kargs))
        return res, cap, agg_kind, agg_param, ecap, oh_table

    def _consume_part(part, state, cols, v, m, bind_keys):
        """Validate one partition's run against the learned lowering
        parameters and turn it into host partials. A policy that says
        "retry" re-dispatches this partition inside the span: its
        `bind`/`dispatch` children are the cost of the retry."""
        with phase.row_block(part, parts), \
                _tracing.span("consume", part=part, parts=parts) as sp:
            retries = 0
            while not _consume(state, m):
                state = _dispatch_part(cols, v, m, bind_keys)
                retries += 1
            if sp is not None:
                sp.attrs["retries"] = retries

    def _host_keys(res, posruns, pos_dims, ng):
        """The first ng groups' keys of a "sort" or "posruns" result,
        as values -> (keys, key_nulls, key_dicts)."""
        ks = [host_array(k)[:ng] for k in res["keys"]]
        if posruns:
            return _decode_pos_keys(low.posruns[0],
                                    dict(zip(pos_dims, ks)), dim_metas)
        return ks, [host_array(kn)[:ng] for kn in res["key_nulls"]], kd

    def _count(metric):
        if getattr(copr, "domain", None) is not None:
            copr.domain.inc_metric(metric)

    def _emit(ng, ks, kns, kds, sts):
        out.append(PartialAggResult(
            ngroups=ng, keys=ks, key_nulls=kns, states=sts,
            key_dicts=kds, state_dicts=sd, ident=ident))

    def _consume(state, m):
        """One run's result into `out` -> True, or False when the
        partition has to run again with what this run taught."""
        res, cap, agg_kind, agg_param, ecap, oh_table = state
        runs_like = agg_kind in ("sort", "posruns")
        ngroups = host_int(res["ngroups"]) if runs_like else None
        # a one-hot run can still miss a key, a top-n run its proof:
        # those are counted (`settle`) where that is known
        held = agg_kind == "onehot" or \
            (runs_like and agg_param[2] is not None)
        if low.observe(agg_kind, agg_param, ecap, cap, m, ngroups,
                       host_int(res["nvalid"]) if runs_like else None,
                       host_int(res["fnvalid"]), hold=held) == "retry":
            return False
        if agg_kind == "posdense":
            out.append(_compact_pos_dense(plan, res, low.pos[0],
                                          low.pos[1], dim_metas, sd))
            return True
        if agg_kind == "dense":
            out.append(compact_dense(shim, res, low.sizes, kd, sd))
            return True
        if agg_kind == "onehot":
            OH = oh_table
            states, rowcnt, miss = onehot_states(
                res, plan.aggs, OH, agg_param[1])
            if miss > 0:
                # new/changed keys since the table was learned:
                # fall back to the sorted lowering and relearn
                _count("fused_onehot_miss")
                low.settle(agg_kind, agg_param, "onehot_miss")
                del st.onehot
                return False
            low.settle(agg_kind, agg_param)
            _count("fused_onehot_agg")
            oh_parts.append((len(out), rowcnt))
            out.append(PartialAggResult(
                ngroups=OH["nslots"],
                keys=[k.copy() for k in OH["key_vals"]],
                key_nulls=[kn.copy() for kn in OH["key_nulls"]],
                states=states, key_dicts=kd, state_dicts=sd))
            return True
        posruns = agg_kind == "posruns"
        topn_k = agg_param[2]
        if topn_k is not None:
            # candidate partials only: verify the candidate set
            # provably covers the true top k before trusting it
            ts = low.topn
            kprime = topn_k[3]
            ncand = min(ngroups, kprime)
            ckeys, cnulls, ckd = _host_keys(res, posruns,
                                            agg_param[1], ncand)
            cstates = [[host_array(s)[:ncand] for s in st_]
                       for st_ in res["states"]]
            if ngroups > kprime:
                sel = host_array(res["sel"])[:ncand]
                real_m = _topn_metric_host(ts, plan.aggs, ckeys,
                                           cnulls, cstates)
                nf = ~((sel == 0) | (sel == ngroups - 1))
                # the coverage proof may count only COMPLETE groups
                # (non-forced candidates): a forced partition-edge
                # partial's metric is not its merged total, so it
                # cannot vouch for excluding other groups
                mnf = real_m[nf]
                safe = len(mnf) > 0 and \
                    int((mnf > mnf.min()).sum()) >= ts[3]
                if not safe:
                    # boundary ties could hide true top-k members:
                    # permanently disable topn for this query shape
                    st.topn_off = True
                    low.settle(agg_kind, agg_param, "topn_unproven")
                    return False
            low.settle(agg_kind, agg_param)
            _emit(ncand, ckeys, cnulls, ckd, cstates)
            return True
        ks, kns, kds = _host_keys(res, posruns, agg_param[1], ngroups)
        sts = [[host_array(s)[:ngroups] for s in st_]
               for st_ in res["states"]]
        if oh_elig and st.onehot is None:
            # runs partials may repeat a key once per run, so the
            # slot-count limit applies AFTER the union dedupes
            # (_oh_learn_table). The CUMULATIVE row bound caps the
            # staged host copies: runs-degrade already limits each
            # partition to ~65k partials, so only very-many-
            # partition shapes (which could never learn a small
            # table anyway) hit it
            if sum(len(e[0][0]) for e in oh_learn) + ngroups \
                    > (1 << 21):
                st.onehot = False
                oh_learn.clear()
            else:
                oh_learn.append((ks, kns))
        _emit(ngroups, ks, kns, kds, sts)
        return True

    # partition pipelining: partition i+1's padding/upload/dispatch is
    # issued BEFORE partition i's results are consumed, so the fixed
    # per-round-trip dispatch + fetch latency overlaps device compute
    # instead of adding up across partitions (one block ahead: deeper
    # has not been measured on this chip — ROADMAP D2).
    # A consume-time policy retry re-dispatches only its own partition
    # with the freshly learned state; a speculatively dispatched
    # successor then self-corrects the same way (one extra kernel run
    # on the rare learning executions, steady state unchanged).
    held = None
    for part, (cols, v, m, bkeys) in enumerate(_partitions()):
        with phase.row_block(part, parts):
            state = _dispatch_part(cols, v, m, bkeys)
        if held is not None:
            _consume_part(*held)
        held = (part, state, cols, v, m, bkeys)
    if held is not None:
        _consume_part(*held)
    if oh_parts:
        # drop slots with zero rows across every one-hot partition:
        # stale learned keys (deletes, older read_ts) must not emit
        # phantom groups; keys live only in sorted partials still
        # merge normally
        total = np.zeros(len(oh_parts[0][1]), dtype=np.int64)
        for _i, rc in oh_parts:
            total += rc
        if (total == 0).any():
            keep = np.nonzero(total > 0)[0]
            for i, _rc in oh_parts:
                p0 = out[i]
                out[i] = PartialAggResult(
                    ngroups=len(keep),
                    keys=[k[keep] for k in p0.keys],
                    key_nulls=[kn[keep] for kn in p0.key_nulls],
                    states=[[s[keep] for s in st_] for st_ in p0.states],
                    key_dicts=p0.key_dicts, state_dicts=p0.state_dicts)
    if oh_elig and oh_learn and len(oh_learn) == len(out) and \
            st.onehot is None:
        _oh_learn_table(st, plan, oh_learn, rows=n, version=fact_version)
    return out


def _try_fused_shuffle(copr, plan, mesh, dim_metas, fact_tbl, fact_arrays,
                       fact_valid, n, handles, threshold, ectx=None):
    """Hash-exchange path (reference ExchangeType_Hash,
    fragment.go:168): single huge dimension + group-by a dim column +
    sum/count/avg over fact expressions -> both sides all_to_all by join
    key, local merge join + dense agg, psum (mpp/exec.py
    mpp_shuffle_join_agg). Returns [PartialAggResult] or None when the
    shape doesn't match (caller broadcasts instead)."""
    from ..expression import Column
    from ..mpp.exec import mpp_shuffle_join_agg
    if len(plan.dims) != 1 or plan.post_filters:
        return None
    dim, meta = plan.dims[0], dim_metas[0]
    if dim.join_type != "inner" or dim.extra_keys or \
            dim.subplan is not None or meta["n"] <= threshold:
        return None
    if len(plan.group_items) != 1 or not isinstance(plan.group_items[0],
                                                    Column):
        return None
    g = plan.group_items[0]
    gcid = None
    for sc in dim.dag.cols:
        if sc.col.idx == g.idx:
            gcid = _cid_of(dim.dag, sc)
    if gcid is None or gcid == -1:
        return None
    nd = meta["n"]
    pdata, pnulls, psdict = meta["arrays"][gcid]
    if pnulls is not None and pnulls[:nd].any():
        return None
    if psdict is not None:
        lo, size = 0, len(psdict.values) + 1
    else:
        if pdata.dtype.kind not in "iu" or nd == 0:
            return None
        lo = int(pdata[:nd].min())
        size = int(pdata[:nd].max()) - lo + 1
    if size > (1 << 18):
        return None
    fact_idxs = {sc.col.idx for sc in plan.fact_dag.cols}
    vals = []
    for a in plan.aggs:
        if a.name not in ("sum", "count", "avg"):
            return None
        if a.args:
            if not (_expr_idxs(a.args[0]) <= fact_idxs):
                return None
            vals.append(a.args[0])
        else:
            vals.append(None)
    # host-side prep: masks + probe keys + agg args (numpy, vectorized)
    key_cid = _cid_of(dim.dag, dim.build_key)
    bk = meta["arrays"][key_cid][0][:nd].astype(np.int64)
    dcols = {sc.col.idx: (meta["arrays"][_cid_of(dim.dag, sc)][0][:nd],
                          meta["arrays"][_cid_of(dim.dag, sc)][1],
                          meta["arrays"][_cid_of(dim.dag, sc)][2])
             for sc in dim.dag.cols if _cid_of(dim.dag, sc) != -1}
    dctx = EvalCtx(np, nd, dcols, host=True)
    dmask = meta["valid"][:nd].copy()
    for f in dim.dag.filters:
        dmask &= np.asarray(eval_bool_mask(dctx, f))
    payload = (pdata[:nd].astype(np.int64) - lo)
    fcols = copr._bind_cols(plan.fact_dag, fact_tbl, fact_arrays,
                            slice(0, n), handles)
    fctx = EvalCtx(np, n, fcols, host=True)
    fmask = fact_valid[:n].copy()
    for f in plan.fact_dag.filters:
        fmask &= np.asarray(eval_bool_mask(fctx, f))
    pk, pnl, _ = eval_expr(fctx, dim.probe_expr)
    if np.isscalar(pk):
        pk = np.full(n, pk)
    pk = np.asarray(pk).astype(np.int64)
    pnm = np.asarray(materialize_nulls(fctx, pnl))
    fmask &= ~pnm
    val_arrays = []
    for a, v in zip(plan.aggs, vals):
        if v is None:
            val_arrays.append(np.ones(n, dtype=np.int64))
        else:
            d, nl, _ = eval_expr(fctx, v)
            if np.isscalar(d):
                d = np.full(n, d)
            nm = np.asarray(materialize_nulls(fctx, nl))
            if nm.any():
                return None               # per-val null masks unsupported
            val_arrays.append(np.asarray(d))
    ndev = int(mesh.devices.size)
    lane = 128 * ndev

    def pad(arr, m, fill=0):
        p = ((m + lane - 1) // lane) * lane
        if p == m:
            return arr
        return np.concatenate([arr, np.full(p - m, fill, dtype=arr.dtype)])

    cap_hint = 0
    if ectx is not None:
        try:
            cap_hint = int(ectx.sv.get("tidb_tpu_mpp_shuffle_cap"))
        except Exception:               # noqa: BLE001
            pass
    # capacity cache key: both tables' uid+version (either side's DML
    # invalidates the learned bound) + the probe expression + BOTH
    # sides' filters (a selective query's small learned cap must not
    # leak to an unfiltered query over the same tables, nor the
    # reverse permanently oversize the selective one) + topology
    cap_key = (fact_tbl.uid, fact_tbl.version, meta["tbl"].uid,
               meta["tbl"].version, dim.probe_expr.fingerprint(),
               tuple(f.fingerprint() for f in plan.fact_dag.filters),
               tuple(f.fingerprint() for f in dim.dag.filters),
               key_cid, ndev)
    sums, cnts = mpp_shuffle_join_agg(
        mesh, pad(pk, n), [pad(v, n) for v in val_arrays],
        pad(fmask, n, False), pad(bk, nd), pad(payload, nd),
        pad(dmask, nd, False), n_groups=size, ectx=ectx,
        cap_key=cap_key, cap_hint=cap_hint)
    cnts = np.asarray(cnts)
    slots = np.nonzero(cnts > 0)[0]
    keys = [(slots + lo).astype(np.int64)]
    states = []
    for a, s in zip(plan.aggs, sums):
        s = np.asarray(s)[slots]
        if a.name == "count":
            states.append([cnts[slots]])
        else:
            states.append([s, cnts[slots]])
    if getattr(copr, "domain", None) is not None:
        copr.domain.inc_metric("fused_shuffle_join")
    _tracing.tag(exchange="hash", kind="dense")
    return [PartialAggResult(
        ngroups=len(slots), keys=keys,
        key_nulls=[np.zeros(len(slots), dtype=bool)],
        states=states, key_dicts=[psdict], state_dicts=[None] * len(states))]



def _run_fused_mpp(copr, plan, mesh, fact_tbl, fact_arrays, fact_valid,
                   n, handles, dim_args, dim_metas, dim_caps, dim_ns,
                   dim_sns, dim_layouts, fact_sdicts, low, shim, kd, sd,
                   read_ts, fold=None):
    """Mesh execution: ONE shard_map call over the whole fact table."""
    from ..mpp.exec import exchange_observed, tree_nbytes
    from .delta import append_key
    ndev = int(mesh.devices.size)
    padded, local = shard_lanes(n, ndev)
    with phase.bind_span():
        cols = copr._bind_cols(plan.fact_dag, fact_tbl, fact_arrays,
                               slice(0, n), handles)
        fjc = {}
        ver = fact_tbl.version
        epoch = fact_tbl.gc_epoch
        for sc in plan.fact_dag.cols:
            cid = _cid_of(plan.fact_dag, sc)
            data, nulls, _sd = cols[sc.col.idx]
            jd = copr._dev_put_append(
                append_key(fact_tbl.uid, "mppf",
                           cid, "h" if cid == -1 else "d", epoch, (ndev,),
                           padded),
                data, n, padded, fact_tbl.uid, ver, epoch, 0, None,
                mesh=mesh, spec="sharded")
            jn = None
            if nulls is not None:
                jn = copr._dev_put_append(
                    append_key(fact_tbl.uid, "mppf", cid, "n", epoch,
                               (ndev,), padded),
                    nulls, n, padded, fact_tbl.uid, ver, epoch, 0, None,
                    pad_fill=True, mesh=mesh, spec="sharded")
            fjc[sc.col.idx] = (jd, jn)
        # the fact validity mask is (version, read_ts)-immutable: residency
        # (same contract as the sharded columns above) instead of a raw
        # device_put, which re-uploaded it warm on every statement
        mver, mts = copr._whole_mask_key(fact_tbl, fact_valid, read_ts)
        fvv = copr._dev_put_sharded(
            (fact_tbl.uid, "mppfv", mver, mts, ndev, padded),
            fact_valid[:n], mesh, padded, pad_fill=False, uid=fact_tbl.uid,
            version=mver)
    retries = 0     # re-dispatches the learned lowering forced
    while True:
        agg_kind, agg_param, _ecap = low.choose(local)
        key = _fused_cache_key(copr, plan, fact_tbl, dim_metas, local,
                               tuple(dim_caps), tuple(dim_ns),
                               tuple(dim_sns), agg_kind, agg_param,
                               fold=fold, dim_layouts=dim_layouts) + \
            ("mpp", ndev, padded)
        kern = copr._kernel_cache.get(key)
        if kern is None:
            kern = _build_fused_kernel_mpp(
                plan, local, fact_sdicts, tuple(dim_caps), tuple(dim_ns),
                tuple(dim_sns), tuple(dim_layouts), agg_kind, agg_param,
                mesh, fold)
            kern = copr._kernel_cache.put(key, kern)
        # tpulint: disable=unguarded-dispatch — supervised by
        # executors.FusedPipeline's guarded_dispatch site="fused/mpp"
        # (a degraded mesh run retries single-chip there)
        res = prefetch(kern(fjc, fvv, dim_args))
        # PassThrough exchange: dense layouts merge via psum ON the
        # mesh (the result tree is already global); the sort layout
        # ships per-shard partials to the coordinator in one fetch
        exchange_observed("passthrough", tree_nbytes(res))
        # on the route's own span (executors.FusedPipelineExec opens it)
        _tracing.tag(exchange="passthrough", kind=agg_kind, lanes=local)
        # shards: whose partials the host merges (1: the mesh merged
        # them, psum); merged_groups: how many partial groups that is
        with _tracing.span("consume", retries=retries, shards=1,
                           merged_groups=0) as csp:
            # the verdict is the fullest shard's: every shard runs the
            # one program (the psum-merged kinds report no sizes)
            ngroups = nvalid = None
            if agg_kind == "sort":
                ngroups_arr = host_array(res["ngroups"])     # [ndev]
                ngroups = int(ngroups_arr.max())
                nvalid = int(host_array(res["nvalid"]).max())
            if low.observe(agg_kind, agg_param, None, local, local,
                           ngroups, nvalid) == "retry":
                retries += 1
                continue
            if agg_kind == "posdense":
                return [_compact_pos_dense(plan, res, low.pos[0],
                                           low.pos[1], dim_metas, sd)]
            if agg_kind == "dense":
                return [compact_dense(shim, res, low.sizes, kd, sd)]
            group_bucket = agg_param[0]
            ident = _ident_items(plan)
            # unstack the per-shard partials
            out = []
            for si in range(ndev):
                ng = int(ngroups_arr[si])
                if ng <= 0:
                    continue
                sl = slice(si * group_bucket, (si + 1) * group_bucket)
                out.append(PartialAggResult(
                    ngroups=ng,
                    keys=[host_array(k)[sl][:ng] for k in res["keys"]],
                    key_nulls=[host_array(kn)[sl][:ng]
                               for kn in res["key_nulls"]],
                    states=[[host_array(s)[sl][:ng] for s in st]
                            for st in res["states"]],
                    key_dicts=kd, state_dicts=sd, ident=ident))
            if csp is not None:
                csp.attrs.update(shards=len(out), merged_groups=sum(
                    p.ngroups for p in out))
            return out


def _fused_cache_key(copr, plan, fact_tbl, dim_metas, cap, dim_caps,
                     dim_ns, dim_sns, agg_kind, agg_param, ecap=None,
                     fold=None, dim_layouts=()):
    dict_vers = [tuple(sorted((cid, len(d.values))
                              for cid, d in fact_tbl.dicts.items()))]
    for meta in dim_metas:
        t = meta["tbl"]
        dict_vers.append(tuple(sorted((cid, len(d.values))
                                      for cid, d in t.dicts.items())))
    fps = tuple(f.fingerprint() for f in plan.fact_dag.filters)
    dimsig = tuple(
        (d.dag.table_info.id, d.build_key.col.idx, d.join_type,
         d.probe_expr.fingerprint(), m["probe"].signature(),
         tuple(f.fingerprint() for f in d.dag.filters),
         tuple(sorted((sc.col.idx, sc.name) for sc in d.dag.cols)),
         tuple((sc.col.idx, pe.fingerprint()) for sc, pe in d.extra_keys),
         m.get("dictsig", ()))
        for d, m in zip(plan.dims, dim_metas))
    postfps = tuple(f.fingerprint() for f in plan.post_filters)
    gfps = tuple(g.fingerprint() for g in plan.group_items)
    afps = tuple(a.fingerprint() for a in plan.aggs)
    colsig = tuple(sorted((sc.col.idx, sc.name)
                          for sc in plan.fact_dag.cols))
    return ("fused", fact_tbl.uid, cap, dim_caps, dim_ns, dim_sns, fps,
            dimsig, postfps, gfps, afps, tuple(dict_vers), colsig,
            agg_kind, agg_param, ecap, _al.policy(),
            None if fold is None else fold.sig(),
            # which field of a composed word is read where, and the
            # physical type a word (like a `lut`) is gathered in, is
            # program text; its shifts, masks and minima are operands
            tuple((lay.get("pack"), lay.get("words"))
                  for lay in dim_layouts))
