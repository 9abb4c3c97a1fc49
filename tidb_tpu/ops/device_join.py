"""Device equi-join kernels (reference HashJoinV2's partitioned build/probe
— re-designed sort-based for XLA: no hash tables, two fixed-shape kernels).

Phase 1 (count):  sort build keys (argsort), searchsorted probe keys ->
                  per-probe match ranges; returns counts + range starts.
Phase 2 (expand): with a static output bucket, each output row r finds its
                  probe row by searchsorted(cumsum(counts), r) and its build
                  row by offset into the sorted range — the dynamic-size
                  duplicate expansion expressed as two gathers.

Semi/anti joins stop after phase 1 (counts>0 is the matched mask).
Everything is static-shaped: inputs pad to buckets, output pads to the
bucket of the true total (host reads one scalar between phases).
"""
from __future__ import annotations

import functools
import threading

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp

from ..chunk.device import shape_bucket
from ..utils.fetch import prefetch

_I64_MAX = np.iinfo(np.int64).max


@functools.partial(jax.jit, static_argnames=())
def tidb_join_probe(bk, bvalid, pk, pvalid):
    skey = jnp.where(bvalid, bk, _I64_MAX)
    border = jnp.argsort(skey)
    sbk = skey[border]
    lo = jnp.searchsorted(sbk, pk, side="left")
    hi = jnp.searchsorted(sbk, pk, side="right")
    counts = jnp.where(pvalid, hi - lo, 0)
    return counts, lo, border


def _phase2(out_cap):
    @jax.jit
    def tidb_join_expand(counts, lo, border, total):
        starts = jnp.cumsum(counts) - counts
        r = jnp.arange(out_cap)
        valid = r < total
        # probe row owning output slot r
        pi = jnp.searchsorted(starts + counts, r, side="right")
        pi = jnp.clip(pi, 0, counts.shape[0] - 1)
        j = r - starts[pi]
        bpos = border[jnp.clip(lo[pi] + j, 0, border.shape[0] - 1)]
        return pi, bpos, valid
    return tidb_join_expand


_EXPAND_CACHE: dict = {}
_EXPAND_MU = threading.Lock()   # joins run on per-connection threads


def device_join_index(bk: np.ndarray, bnull: np.ndarray,
                      pk: np.ndarray, pnull: np.ndarray,
                      semi_only: bool = False):
    """-> (pi, bi) int64 arrays of matched pairs (or (matched_mask, None)
    when semi_only). Keys are int64; null rows never match."""
    nb, npr = len(bk), len(pk)
    cb, cp = shape_bucket(max(nb, 1)), shape_bucket(max(npr, 1))
    bkd = jnp.asarray(np.concatenate([bk, np.zeros(cb - nb, dtype=np.int64)]))
    bvd = jnp.asarray(np.concatenate([~bnull, np.zeros(cb - nb, dtype=bool)]))
    pkd = jnp.asarray(np.concatenate([pk, np.full(cp - npr, _I64_MAX,
                                                  dtype=np.int64)]))
    pvd = jnp.asarray(np.concatenate([~pnull, np.zeros(cp - npr, dtype=bool)]))
    # supervised by the caller: executors.HashJoinExec wraps
    # device_join_index in guarded_dispatch(site="join") with the host
    # hash-join fallback on DeviceDegradedError
    # tpulint: disable=unguarded-dispatch
    counts, lo, border = tidb_join_probe(bkd, bvd, pkd, pvd)
    if semi_only:
        return np.asarray(counts)[:npr] > 0, None
    total = int(jnp.sum(counts))
    if total == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
    out_cap = shape_bucket(total)
    with _EXPAND_MU:
        expand = _EXPAND_CACHE.get((out_cap, cp))
        if expand is None:
            expand = _phase2(out_cap)
            _EXPAND_CACHE[(out_cap, cp)] = expand
    # same supervision as tidb_join_probe above (guarded at the executors site)
    # tpulint: disable=unguarded-dispatch
    pi, bpos, valid = expand(counts, lo, border,
                             jnp.asarray(total, dtype=jnp.int64))
    prefetch(pi, bpos)
    pi = np.asarray(pi)[:total]
    bpos = np.asarray(bpos)[:total]
    return pi, bpos
