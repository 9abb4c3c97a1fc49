"""Coordinator-broadcast CoprDAG execution over a MULTI-HOST mesh.

Reference mapping: the TiDB coordinator serializes a plan fragment as a
tipb.DAGRequest and dispatches one MPP task per store
(pkg/store/copr/mpp.go:94 DispatchMPPTask; executor builds the request
in executor/internal/builder/builder_utils.go:64). TPU-native redesign:
the SAME pickled CoprDAG arrives at every host over the cluster RPC
control plane, each host binds its LOCAL store shard into one global
array (parallel/dist.bind_host_rows), and every host launches the
IDENTICAL XLA program over the global mesh — the "exchange" between the
per-store fragments is a psum riding ICI/DCN, not a software stream.

SPMD invariant: the traced program must be bit-identical on every
process. Everything that parametrizes the trace (filters, agg exprs,
n_groups, local_cap) comes from the coordinator's broadcast; nothing
host-local (like a per-process dictionary) may leak into the trace —
dict-coded columns are rejected until dictionary broadcast lands.
"""
from __future__ import annotations

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..expression import EvalCtx, eval_expr, eval_bool_mask
from ..expression.vec import materialize_nulls
from ..parallel.dist import bind_host_rows
from ..utils import device_guard
from ..utils.fetch import prefetch, host_array
from .exec import (_cached_kernel, _mesh_fingerprint, _arg_sig,
                   exchange_observed, tree_nbytes)


def run_dag_spmd(domain, dag, mesh, local_cap, n_groups=None,
                 axis: str = "dp"):
    """Execute a broadcast scan->filter->partial-agg CoprDAG over the
    global mesh, this process contributing its local shard.

    Supported fragment shapes (the Q6/Q1 classes):
      - no group items: global aggregation, result replicated;
      - group items that evaluate to int64 in [0, n_groups): dense
        partial tables merged with one psum (the allreduce-exchange
        lowering of mpp/exec.py, across hosts).
    Returns {"sums": [np per agg], "counts": np} (counts = rows per
    group / matching rows)."""
    tbl_local = domain.infoschema().table_by_name(
        dag.db_name or "test", dag.table_info.name)
    if tbl_local is None:
        raise ValueError(f"table {dag.table_info.name} not on this host")
    ctab = domain.columnar.table(tbl_local)
    col_ids = []
    for sc in dag.cols:
        ci = tbl_local.find_column(sc.name)
        if ci is None:
            raise ValueError(f"column {sc.name} not in local schema")
        col_ids.append(ci.id)
    arrays, valid = ctab.snapshot(col_ids)
    for cid in col_ids:
        if arrays[cid][2] is not None:
            raise ValueError(
                "dict-coded column in SPMD fragment: per-process codes "
                "cannot cross the trace (dictionary broadcast TBD)")

    n_local = len(valid)
    bound = {}
    for sc, cid in zip(dag.cols, col_ids):
        data, nulls, _ = arrays[cid]
        bound[sc.col.idx] = (
            bind_host_rows(mesh, data, local_cap, axis),
            None if nulls is None
            else bind_host_rows(mesh, nulls, local_cap, axis))
    vpad = np.zeros(local_cap, dtype=bool)
    vpad[:n_local] = valid
    gvalid = bind_host_rows(mesh, vpad, local_cap, axis)

    idxs = sorted(bound.keys())
    filters = list(dag.filters)
    groups = list(dag.group_items)
    aggs = list(dag.aggs)
    if groups and n_groups is None:
        raise ValueError("grouped SPMD fragment needs n_groups")
    if len(groups) > 1:
        # same refusal policy as the agg guard below: a single-key
        # segment over groups[0] would silently merge distinct
        # (a, b, ...) groups identically on every host
        raise ValueError("multi-column GROUP BY not supported in SPMD "
                         "fragment yet")
    for a in aggs:
        # only additive partials here: min/max/first_row/avg partial
        # states need the full state-merge contract — refusing beats a
        # SUM silently mislabeled as MIN on every host identically
        # (which the cross-host divergence check cannot catch)
        if a.name not in ("sum", "count"):
            raise ValueError(f"agg {a.name} not supported in SPMD "
                             f"fragment yet")

    def tidb_mpp_spmd(valid_l, *flat):
        cols = {}
        i = 0
        for ix in idxs:
            has_n = bound[ix][1] is not None
            cols[ix] = (flat[i], flat[i + 1] if has_n else None, None)
            i += 2 if has_n else 1
        ctx = EvalCtx(jnp, valid_l.shape[0], cols, host=False)
        mask = valid_l
        for f in filters:
            mask = mask & eval_bool_mask(ctx, f)
        outs = []
        if not groups:
            for a in aggs:
                if a.args:
                    d, nl, _ = eval_expr(ctx, a.args[0])
                    ok = mask & ~materialize_nulls(ctx, nl)
                else:
                    d, ok = jnp.ones_like(mask, dtype=jnp.int64), mask
                if a.name == "count":
                    outs.append(jax.lax.psum(
                        jnp.sum(ok.astype(jnp.int64)), axis))
                else:
                    outs.append(jax.lax.psum(
                        jnp.sum(jnp.where(ok, d, 0)), axis))
            cnt = jax.lax.psum(jnp.sum(mask.astype(jnp.int64)), axis)
            return tuple(outs) + (cnt,)
        gd, gn, _ = eval_expr(ctx, groups[0])
        seg = jnp.clip(gd.astype(jnp.int64), 0, n_groups - 1)
        gok = mask & ~materialize_nulls(ctx, gn)
        for a in aggs:
            if a.args:
                d, nl, _ = eval_expr(ctx, a.args[0])
                ok = gok & ~materialize_nulls(ctx, nl)
            else:
                d, ok = jnp.ones_like(mask, dtype=jnp.int64), gok
            if a.name == "count":
                d = jnp.ones_like(d)
            outs.append(jax.lax.psum(jax.ops.segment_sum(
                jnp.where(ok, d, 0), seg, num_segments=n_groups), axis))
        cnts = jax.lax.psum(jax.ops.segment_sum(
            gok.astype(jnp.int64), seg, num_segments=n_groups), axis)
        return tuple(outs) + (cnts,)

    flat_args, in_specs = [gvalid], [P(axis)]
    for ix in idxs:
        d, nl = bound[ix]
        flat_args.append(d)
        in_specs.append(P(axis))
        if nl is not None:
            flat_args.append(nl)
            in_specs.append(P(axis))
    nouts = len(aggs) + 1

    def build():
        fn = shard_map(tidb_mpp_spmd, mesh=mesh,
                       in_specs=tuple(in_specs),
                       out_specs=tuple(P() for _ in range(nouts)),
                       check_vma=False)
        return jax.jit(fn)

    # the compiled-program cache is keyed by the SAME broadcast state
    # that parametrizes the trace (SPMD invariant above), so every
    # process resolves an identical program — and a repeated fragment
    # skips the per-statement retrace
    kern = _cached_kernel(
        ("spmd", _mesh_fingerprint(mesh), axis, n_groups,
         tuple(f.fingerprint() for f in filters),
         tuple(g.fingerprint() for g in groups),
         tuple(a.fingerprint() for a in aggs),
         tuple(idxs), tuple(ix for ix in idxs
                            if bound[ix][1] is not None),
         _arg_sig(flat_args)), build)
    # supervised mesh launch: the worker control plane (cluster/worker
    # spmd_frag) calls this NAKED — without the guard a device lost
    # mid-collective is an unclassified worker crash instead of a
    # retryable error the coordinator can reason about
    # fallback_is_host=False: a degrade here propagates to the
    # coordinator, which retries on another DEVICE path (single-chip) —
    # a topology retreat, not a host fallback (PR 2 exclusion contract)
    res = device_guard.guarded_dispatch(
        lambda: kern(*flat_args), site="mpp/spmd", domain=domain,
        fallback_is_host=False)
    exchange_observed("passthrough", tree_nbytes(res))
    res = prefetch(res)
    return {"sums": [host_array(r) for r in res[:-1]],
            "counts": host_array(res[-1])}
