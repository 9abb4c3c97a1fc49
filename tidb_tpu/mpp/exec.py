"""MPP execution: plan fragments as SPMD programs over a device mesh.

Reference mapping (SURVEY.md §3.3): a TiFlash MPP plan is a tree of
Fragments split at Exchange operators (physicalop/fragment.go:49); exchange
types PassThrough / Broadcast / Hash (fragment.go:78). TPU-native redesign:

  * one pjit/shard_map program per fragment chain — the exchange between
    fragments is not a network stream but an XLA collective on ICI:
      - Hash exchange + small group domain  -> dense partial tables + psum
        (allreduce replaces shuffle entirely; every device ends with the
        global aggregate — far cheaper than a software shuffle on TPU)
      - Hash exchange, large domain         -> all_to_all by key hash
      - Broadcast exchange                  -> all_gather of the build side
  * fragments never materialize between operators: scan -> filter -> agg
    fuse into one XLA kernel per shard.

These building blocks execute the same partial-agg layout the single-chip
copr produces, so the session layer can route a CoprDAG to a mesh without
changing the final-merge code.
"""
from __future__ import annotations

import os
import threading

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..expression import EvalCtx, eval_expr, eval_bool_mask
from ..expression.vec import materialize_nulls
from ..utils import device_guard
from ..utils import phase
from ..utils import metrics as _metrics
from ..utils.fetch import prefetch, host_int

# Compiled exchange-fragment cache. jax.jit keys its executable cache
# on the FUNCTION OBJECT: the fresh shard_map closure each call used
# to force a retrace (and on a cold disk cache, a recompile) per
# statement. Keyed by mesh topology + fragment semantics + arg
# shapes/dtypes; entries are phase.timed_kernel-wrapped so mesh
# dispatches land in the same dispatch/compile counters (and Top SQL
# per-digest device ms) as single-chip kernels.
_KERN_CACHE: dict = {}
_KERN_MU = threading.Lock()
_KERN_CACHE_MAX = 256

# Hash-exchange capacity cache: (table uid, version, ndev)-style keys
# -> per-(sender, destination) bucket capacity. A repeated shuffle
# join over an unchanged table never re-sizes — neither on host nor on
# device.
_CAP_CACHE: dict = {}
_CAP_MU = threading.Lock()
_CAP_CACHE_MAX = 4096


def _mesh_fingerprint(mesh: Mesh):
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names))


def _arg_sig(args):
    """Static shape/dtype signature of positional kernel args."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in args)


def _lru_touch(cache: dict, key):
    """Hit path of a bounded insertion-ordered cache: re-insert so
    insertion order tracks recency and _lru_put's oldest-half purge
    evicts true LRU, not the steady state's warmest entries. Caller
    holds the cache's lock."""
    val = cache.pop(key, None)
    if val is not None:
        cache[key] = val
    return val


def _lru_put(cache: dict, key, val, cap: int):
    """Insert into a bounded insertion-ordered cache, dropping the
    least-recently-touched half at capacity. Keys embed churning parts
    (table versions, dict lengths, capacities, padded shape buckets),
    so unbounded growth on a long-running server is the alternative.
    Caller holds the cache's lock."""
    if len(cache) >= cap:
        for k in list(cache)[:cap // 2]:
            cache.pop(k, None)
    cache[key] = val


def _cached_kernel(key, build):
    """Get-or-build a compiled exchange fragment under the module lock
    (build-under-lock also dedups the phase wrapper)."""
    with _KERN_MU:
        kern = _lru_touch(_KERN_CACHE, key)
        if kern is None:
            kern = phase.timed_kernel("mpp", build())
            _lru_put(_KERN_CACHE, key, kern, _KERN_CACHE_MAX)
    return kern


def _cap_cache_get(cap_key):
    if cap_key is None:
        return None
    with _CAP_MU:
        return _lru_touch(_CAP_CACHE, cap_key)


def _cap_cache_put(cap_key, cap):
    if cap_key is None:
        return
    with _CAP_MU:
        _lru_put(_CAP_CACHE, cap_key, cap, _CAP_CACHE_MAX)


def exchange_observed(kind: str, nbytes: int):
    """Exchange observability (docs/PERFORMANCE.md "Exchange
    lowering"): one exchange executed as an on-mesh collective, and the
    aggregate bytes it moved across the mesh (summed over devices).
    Phase counters ride the statement's thread-local dict, so Top SQL
    attributes collective traffic per digest alongside device ms."""
    _metrics.MPP_EXCHANGE.labels(kind).inc()
    _metrics.MPP_EXCHANGE_BYTES.labels(kind).inc(max(int(nbytes), 0))
    phase.inc("mpp_exchanges")
    phase.add("mpp_exchange_bytes", max(int(nbytes), 0))


def tree_nbytes(tree) -> int:
    """Static aggregate byte size of a result pytree (shape/dtype
    metadata only — never forces a device sync)."""
    if isinstance(tree, dict):
        return sum(tree_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(tree_nbytes(v) for v in tree)
    return int(getattr(tree, "nbytes", 0) or 0)


def _local_ctx(cols, n):
    return EvalCtx(jnp, n, cols, host=False)


def mpp_global_sum(mesh: Mesh, cols_sharded: dict, sdicts: dict,
                   filters: list, sum_exprs: list, axis: str = "dp",
                   ectx=None):
    """Fragment: sharded scan -> fused filter -> local masked sums -> psum.
    Returns (sums per expr, count) replicated on every device.

    The PassThrough exchange (partials -> coordinator) is the psum: the
    merge happens ON the mesh inside the fragment program, and the host
    fetches one already-merged result tree."""

    # flatten cols into positional args for shard_map
    names_static = sorted(cols_sharded.keys())
    has_nulls = {k: cols_sharded[k][1] is not None for k in names_static}
    args = []
    in_specs = []
    for k in names_static:
        data, nulls = cols_sharded[k][0], cols_sharded[k][1]
        args.append(data)
        in_specs.append(P(axis))
        if nulls is not None:
            args.append(nulls)
            in_specs.append(P(axis))
    valid = cols_sharded[names_static[0]][2]
    args.append(valid)
    in_specs.append(P(axis))

    def build():
        def tidb_mpp_gsum(*vals):
            local_n = vals[0].shape[0]
            cols = {}
            i = 0
            for k in names_static:
                data = vals[i]
                nulls = vals[i + 1] if has_nulls[k] else None
                i += 2 if has_nulls[k] else 1
                cols[k] = (data, nulls, sdicts.get(k))
            valid_l = vals[-1]
            ctx = _local_ctx(cols, local_n)
            mask = valid_l
            for f in filters:
                mask = mask & eval_bool_mask(ctx, f)
            outs = []
            for e in sum_exprs:
                d, nl, _ = eval_expr(ctx, e)
                nm = materialize_nulls(ctx, nl)
                ok = mask & ~nm
                outs.append(jax.lax.psum(jnp.sum(jnp.where(ok, d, 0)),
                                         axis))
            cnt = jax.lax.psum(jnp.sum(mask.astype(jnp.int64)), axis)
            return tuple(outs) + (cnt,)

        fn = shard_map(tidb_mpp_gsum, mesh=mesh,
                       in_specs=tuple(in_specs),
                       out_specs=tuple(P() for _ in
                                       range(len(sum_exprs) + 1)),
                       check_vma=False)
        return jax.jit(fn)

    # dict identity rides (id, len): the cached closure holds a strong
    # ref to each captured dict, so a live id() match IS the same
    # object (no recycling while the entry exists), and len catches
    # append growth — a different table's same-length dictionary can
    # never hit this kernel (expression fingerprints are plan-local)
    key = ("gsum", _mesh_fingerprint(mesh), axis,
           tuple(names_static), tuple(sorted(has_nulls.items())),
           tuple((k, id(sdicts[k]), len(sdicts[k].values))
                 for k in names_static if sdicts.get(k) is not None),
           tuple(f.fingerprint() for f in filters),
           tuple(e.fingerprint() for e in sum_exprs),
           _arg_sig(args))
    kern = _cached_kernel(key, build)
    # supervised: these exchange fragments are invoked naked by the
    # cluster worker control plane; under the fused pipeline the outer
    # "fused/mpp" guard composes (inner degrade -> outer fallback, see
    # device_guard.classify 'degraded')
    # ectx (when a session drives this fragment) supplies the
    # statement-deadline clamp, kill checks, and per-session retry/
    # timeout sysvars — the supervision contract the outer guard used
    # to provide before these sites grew their own
    res = device_guard.guarded_dispatch(
        lambda: kern(*args), site="mpp/global_sum", ectx=ectx,
        fallback_is_host=False)
    exchange_observed("passthrough", tree_nbytes(res))
    return res


def mpp_filter_agg(mesh: Mesh, key_arr, val_arr, valid, n_groups: int,
                   axis: str = "dp", ectx=None):
    """Fragment: sharded grouped aggregation over a SMALL group domain.
    Hash exchange replaced by dense partial tables + psum: each device
    scatter-adds into its local [n_groups] table, one allreduce merges.
    Returns (sums[n_groups], counts[n_groups]) replicated."""

    def build():
        def tidb_mpp_fagg(keys, vals, ok):
            seg = jnp.clip(keys, 0, n_groups - 1)
            sums = jax.ops.segment_sum(jnp.where(ok, vals, 0), seg,
                                       num_segments=n_groups)
            cnts = jax.ops.segment_sum(ok.astype(jnp.int64), seg,
                                       num_segments=n_groups)
            return jax.lax.psum(sums, axis), jax.lax.psum(cnts, axis)

        fn = shard_map(tidb_mpp_fagg, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=(P(), P()), check_vma=False)
        return jax.jit(fn)

    args = (key_arr, val_arr, valid)
    kern = _cached_kernel(("fagg", _mesh_fingerprint(mesh), axis,
                           n_groups, _arg_sig(args)), build)
    res = device_guard.guarded_dispatch(
        lambda: kern(*args),
        site="mpp/filter_agg", ectx=ectx, fallback_is_host=False)
    exchange_observed("passthrough", tree_nbytes(res))
    return res


def _shuffle_capacity(keys, ok, ndev):
    """Exact per-(sender, destination) bucket maximum for a hash
    exchange, computed on host before tracing. Sizing the exchange
    frames to this bound makes overflow *impossible by construction*
    (reference fragment.go:78 hash exchange never drops rows): a skewed
    key distribution grows the frame instead of silently spilling rows.
    Returns 0 for an empty side."""
    keys = np.asarray(keys)
    ok = np.asarray(ok)
    n = keys.shape[0]
    local = n // ndev
    mx = 0
    for d in range(ndev):
        sl = slice(d * local, (d + 1) * local)
        dk = keys[sl][ok[sl]] % ndev
        if dk.size:
            mx = max(mx, int(np.bincount(dk, minlength=ndev).max()))
    return mx


def _round_capacity(cap):
    """Quarter-pow2 bucketing (same policy as the copr buffer pool) so
    repeated runs with similar skew reuse one compiled kernel."""
    if cap <= 128:
        return 128
    p = 1 << (int(cap - 1).bit_length())
    for q in (p // 2 + p // 4, p // 2 + p // 2):
        if cap <= q:
            return q
    return p


def mpp_shuffle_join_agg(mesh: Mesh, probe_keys, probe_vals, probe_valid,
                         build_keys, build_payload, build_valid,
                         n_groups: int, axis: str = "dp", cap=None,
                         ectx=None, cap_key=None, cap_hint=0):
    """Fragment pair with a HASH exchange: both sides all_to_all'd by
    key % n_devices so matching keys land on the same device, then a local
    sort-merge join feeds a grouped aggregation on the build payload,
    merged with psum. This is the TiFlash shuffle-join fragment
    (ExchangeType_Hash) as XLA collectives — chosen over a Broadcast
    exchange when the build side is too large to replicate.

    Local shapes are static: each device keeps `cap` slots per peer
    (pow2-bucketed for kernel-cache reuse), so a hot key grows the frame
    rather than overflowing it, and the all_to_all payload shrinks from
    ndev*local_n to ndev*cap when the hash is balanced. Capacity is
    sized WITHOUT a host histogram on the hot path:

      * explicit `cap` (the multi-host SPMD seam: the coordinator sizes
        it so every process traces the identical program) is trusted
        as-is — no overflow loop, exactly the old contract;
      * else the per-(table uid, version, ndev) capacity cache
        (`cap_key`) serves the steady state — a repeated shuffle join
        over an unchanged table re-sizes NOTHING;
      * else the fragment itself computes the exact per-(sender,
        destination) bucket maximum ON DEVICE (pmax over local
        bincounts) and returns it alongside the result: the first
        statement guesses a balanced-load capacity (or `cap_hint`,
        sysvar tidb_tpu_mpp_shuffle_cap), and an overflowed guess
        triggers ONE re-trace at the exact returned bound.
        TIDB_TPU_MPP_HOST_CAP=1 restores host-side sizing (still
        cap-cached) for debugging.

    probe_vals may be one array or a list (multi-agg); returns
    (sums[n_groups] per val, counts[n_groups]) replicated."""
    ndev = int(mesh.devices.size)
    single = not isinstance(probe_vals, (list, tuple))
    pvals = [probe_vals] if single else list(probe_vals)
    nvals = len(pvals)
    explicit_cap = cap is not None
    if cap is None:
        cap = _cap_cache_get(cap_key)
    if cap is None and os.environ.get("TIDB_TPU_MPP_HOST_CAP") == "1":
        # fallback host-sizing path: exact, but one host pass over both
        # key columns before tracing — kept for debugging; its result
        # still lands in the capacity cache
        cap = _round_capacity(max(
            _shuffle_capacity(probe_keys, probe_valid, ndev),
            _shuffle_capacity(build_keys, build_valid, ndev), 1))
        _cap_cache_put(cap_key, cap)
    if cap is None:
        # balanced-load first guess with 2x skew headroom; an overflow
        # costs one re-trace at the device-measured exact bound
        local = max(int(probe_keys.shape[0]), int(build_keys.shape[0]))
        local //= max(ndev, 1)
        cap = _round_capacity(max(int(cap_hint), 128,
                                  2 * (local // max(ndev, 1))))

    def build_kern(cap):
        def exchange(keys, vals, ok):
            """Route rows to device (key % ndev) via one all_to_all
            each; also returns this shard's exact per-destination
            bucket maximum (the overflow observable)."""
            local_n = keys.shape[0]
            dest = (keys % ndev).astype(jnp.int32)
            dest = jnp.where(ok, dest, ndev)    # invalid -> dropped bucket
            counts = jnp.zeros(ndev + 1, dtype=jnp.int32).at[dest].add(1)
            local_max = jnp.max(counts[:ndev])
            # stable sort rows by destination, slot i*cap..(i+1)*cap per
            # peer
            order = jnp.argsort(dest, stable=True)
            skeys, sok, sdest = keys[order], ok[order], dest[order]
            svals = [v[order] for v in vals]
            # position within destination bucket
            onehot = (sdest[:, None] == jnp.arange(ndev + 1)[None, :])
            pos_in_bucket = jnp.cumsum(onehot, axis=0)[
                jnp.arange(local_n), sdest] - 1
            slot = jnp.where(sdest < ndev, pos_in_bucket, cap)
            keep = (slot < cap) & sok
            # scatter into [ndev, cap] frames; dropped rows go to a
            # scratch row (ndev) sliced off afterwards — writing them to
            # (0, 0) would clobber the real row in that slot
            didx = jnp.where(keep, sdest, ndev)
            sidx = jnp.where(keep, slot, 0)
            fk = jnp.zeros((ndev + 1, cap), dtype=keys.dtype)
            fk = fk.at[didx, sidx].set(jnp.where(keep, skeys, 0))[:ndev]
            fo = jnp.zeros((ndev + 1, cap), dtype=bool)
            fo = fo.at[didx, sidx].max(keep)[:ndev]
            fvs = []
            for v in svals:
                fv = jnp.zeros((ndev + 1, cap), dtype=v.dtype)
                fvs.append(fv.at[didx, sidx].set(
                    jnp.where(keep, v, 0))[:ndev])
            # one collective per frame: device d receives bucket d of all
            fk = jax.lax.all_to_all(fk, axis, 0, 0, tiled=False)
            fo = jax.lax.all_to_all(fo, axis, 0, 0, tiled=False)
            fvs = [jax.lax.all_to_all(fv, axis, 0, 0, tiled=False)
                   for fv in fvs]
            return (fk.reshape(-1), [fv.reshape(-1) for fv in fvs],
                    fo.reshape(-1), local_max)

        def tidb_mpp_shuf(pk, pok, bk, bp, bok, *pvs):
            pk2, pv2s, pok2, pmax = exchange(pk, list(pvs), pok)
            bk2, (bp2,), bok2, bmax = exchange(bk, [bp], bok)
            # exact global capacity bound, computed where the data is:
            # the max over every (sender, destination) bucket count
            needed = jax.lax.pmax(jnp.maximum(pmax, bmax), axis)
            # local sort-merge equi-join: probe rows find matching build
            # rows
            border = jnp.argsort(
                jnp.where(bok2, bk2, jnp.iinfo(jnp.int64).max),
                stable=True)
            sbk = jnp.where(bok2, bk2, jnp.iinfo(jnp.int64).max)[border]
            sbp = bp2[border]
            idx = jnp.searchsorted(sbk, pk2)
            idx = jnp.clip(idx, 0, sbk.shape[0] - 1)
            matched = pok2 & (sbk[idx] == pk2)
            payload = sbp[idx]
            # grouped agg on build payload (e.g. nation of matched
            # supplier)
            seg = jnp.clip(payload, 0, n_groups - 1)
            sums = tuple(
                jax.lax.psum(jax.ops.segment_sum(
                    jnp.where(matched, pv2, 0), seg,
                    num_segments=n_groups), axis) for pv2 in pv2s)
            cnts = jax.ops.segment_sum(matched.astype(jnp.int64), seg,
                                       num_segments=n_groups)
            return sums + (jax.lax.psum(cnts, axis), needed)

        fn = shard_map(tidb_mpp_shuf, mesh=mesh,
                       in_specs=tuple(P(axis) for _ in range(5 + nvals)),
                       out_specs=tuple(P() for _ in range(nvals + 2)),
                       check_vma=False)
        return jax.jit(fn)

    args = (probe_keys, probe_valid, build_keys, build_payload,
            build_valid) + tuple(pvals)
    if jax.process_count() == 1:
        # commit the whole input tree row-sharded in ONE device_put
        # (parallel.sharding_tree): an overflow re-trace then reuses
        # the committed shards instead of re-transferring every column
        # from host. Multi-host callers hand in bind_host_rows global
        # arrays that are already placed.
        from ..parallel import sharding_tree
        args = jax.device_put(args, sharding_tree(args, mesh, axis))
    mesh_fp = _mesh_fingerprint(mesh)
    while True:
        kern = _cached_kernel(
            ("shuf", mesh_fp, axis, n_groups, nvals, cap,
             _arg_sig(args)), lambda: build_kern(cap))
        res = device_guard.guarded_dispatch(
            lambda: kern(*args),
            site="mpp/shuffle_join", ectx=ectx, fallback_is_host=False)
        res = prefetch(res)
        if explicit_cap:
            # multi-host SPMD: the overflow decision would have to be
            # bit-identical on every process; the coordinator's exact
            # host sizing already guarantees no drop
            break
        needed = host_int(res[-1])
        if needed <= cap:
            # remember the capacity that WORKED (not the tight bound:
            # re-keying to a smaller cap would retrace for nothing)
            _cap_cache_put(cap_key, cap)
            break
        cap = _round_capacity(needed)
        _cap_cache_put(cap_key, cap)
    res = res[:-1]
    # aggregate all_to_all payload: [ndev, cap] frames per side per
    # device (keys + validity + value columns), across ndev devices
    row_bytes = (probe_keys.dtype.itemsize + 1 +
                 sum(v.dtype.itemsize for v in pvals) +
                 build_keys.dtype.itemsize + build_payload.dtype.itemsize
                 + 1)
    exchange_observed("hash", ndev * ndev * cap * row_bytes)
    if single:
        return res[0], res[-1]
    return list(res[:-1]), res[-1]
