"""Device window functions (VERDICT r2 weak item 9; reference
pkg/executor/window.go + shuffle.go — goroutine-data-parallel windows).

TPU-first redesign: one jit kernel per (function, key-count, shape
bucket) computes sort + partition/peer boundaries + the windowed value
entirely on device — `jnp.lexsort` does the O(n log n) work, boundaries
come from flag cumsums and `nonzero(size=n)` gathers (static shapes),
and segmented MIN/MAX ride `lax.associative_scan` with reset flags.
Rows are padded to a quarter-pow2 bucket; a pad flag participates as
the MOST SIGNIFICANT partition key so pad rows sort last and form their
own partition, never perturbing real boundaries.

Host keeps: sort-KEY evaluation (one linear pass; dict/string keys are
already rank arrays), decimal AVG finalization, and every frame/rare
function — those fall back to the host path transparently.
"""
from __future__ import annotations

import threading

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp

from ..chunk.device import shape_bucket

DEVICE_FNS = {"row_number", "rank", "dense_rank", "sum", "count",
              "avg", "min", "max", "lag", "lead"}

_KERN_CACHE: dict = {}
# concurrent window statements on different connections share the
# compiled-kernel cache; build-under-lock also dedups the jit wrapper
_KERN_MU = threading.Lock()


def _seg_scan_minmax(filled, resets, is_min):
    """Running min/max with partition resets (associative segmented
    scan — the same lowering the copr aggs use)."""
    def combine(a, b):
        va, fa = a
        vb, fb = b
        v = jnp.where(fb, vb,
                      jnp.minimum(va, vb) if is_min
                      else jnp.maximum(va, vb))
        return v, fa | fb
    v, _ = jax.lax.associative_scan(combine, (filled, resets))
    return v


def _build_kernel(name, nkeys, npart, has_order, cap, val_float,
                  has_default):
    """Trace one window kernel. Static: function name, key counts,
    ORDER BY presence, shape bucket, value dtype. The lag/lead shift
    and default are traced runtime args — one kernel serves every
    offset (a long-lived server would otherwise compile and pin a
    kernel per user-supplied constant)."""

    def tidb_window(keys, vals, ok, default, shift):
        order = jnp.lexsort(tuple(reversed(keys)))
        sk = [k[order] for k in keys]
        svals = vals[order]
        sok = ok[order]
        idx = jnp.arange(cap)
        first = idx == 0
        part_chg = first
        for j in range(npart + 1):          # +1: the pad-flag key
            part_chg = part_chg | jnp.concatenate(
                [jnp.zeros(1, dtype=bool), sk[j][1:] != sk[j][:-1]])
        peer_chg = part_chg
        if has_order:
            for j in range(npart + 1, nkeys):
                peer_chg = peer_chg | jnp.concatenate(
                    [jnp.zeros(1, dtype=bool), sk[j][1:] != sk[j][:-1]])
        part_id = jnp.cumsum(part_chg) - 1
        starts = jnp.nonzero(part_chg, size=cap, fill_value=cap)[0]
        nparts = part_chg.sum()
        part_start = starts[part_id]
        part_end = jnp.where(part_id + 1 < nparts,
                             starts[jnp.minimum(part_id + 1, cap - 1)],
                             cap)
        seq = idx - part_start
        if name == "row_number":
            out, onulls = seq + 1, None
        elif name in ("rank", "dense_rank"):
            peer_id = jnp.cumsum(peer_chg) - 1
            pstarts = jnp.nonzero(peer_chg, size=cap, fill_value=cap)[0]
            peer_start = pstarts[peer_id]
            if name == "rank":
                out, onulls = peer_start - part_start + 1, None
            else:
                # dense rank = number of peer starts in the partition
                # up to (and including) this row's peer group
                peers_before = jnp.cumsum(peer_chg.astype(jnp.int64))
                base = peers_before[jnp.maximum(part_start - 1, 0)]
                base = jnp.where(part_start > 0, base, 0)
                out = peers_before[peer_start] - base
                onulls = None
        elif name in ("lag", "lead"):
            tgt = idx + shift
            valid = (tgt >= part_start) & (tgt < part_end)
            tgt = jnp.clip(tgt, 0, cap - 1)
            out = svals[tgt]
            onulls = (~sok[tgt]) | ~valid
            if has_default:
                out = jnp.where(valid, out, default)
                onulls = jnp.where(valid, onulls, False)
        else:
            # aggregates over the partition (or up to the peer group
            # when ORDER BY is present — running totals)
            if has_order:
                peer_id = jnp.cumsum(peer_chg) - 1
                pstarts = jnp.nonzero(peer_chg, size=cap,
                                      fill_value=cap)[0]
                npeers = peer_chg.sum()
                pend = jnp.where(
                    peer_id + 1 < npeers,
                    pstarts[jnp.minimum(peer_id + 1, cap - 1)], cap)
                end = jnp.minimum(pend, part_end) - 1
            else:
                end = part_end - 1
            cnt_cum = jnp.cumsum(sok.astype(jnp.int64))
            cbase = jnp.where(part_start > 0,
                              cnt_cum[jnp.maximum(part_start - 1, 0)], 0)
            c = cnt_cum[end] - cbase
            if name == "count":
                out, onulls = c, None
            elif name in ("sum", "avg"):
                acc = jnp.cumsum(jnp.where(sok, svals, 0))
                base = jnp.where(part_start > 0,
                                 acc[jnp.maximum(part_start - 1, 0)], 0)
                s = acc[end] - base
                if name == "sum":
                    out, onulls = s, c == 0
                else:
                    out = s.astype(jnp.float64) / jnp.maximum(c, 1)
                    onulls = c == 0
            else:                            # min / max
                if val_float:
                    ident = jnp.inf if name == "min" else -jnp.inf
                else:
                    big = jnp.iinfo(jnp.int64).max
                    ident = big if name == "min" else -big
                filled = jnp.where(sok, svals, ident)
                run = _seg_scan_minmax(filled, part_chg, name == "min")
                out = run[end]
                onulls = c == 0
        res = jnp.zeros(cap, dtype=out.dtype).at[order].set(out)
        if onulls is None:
            return res, jnp.zeros(cap, dtype=bool)
        rnulls = jnp.zeros(cap, dtype=bool).at[order].set(onulls)
        return res, rnulls

    return jax.jit(tidb_window)


def run_window_device(name, key_arrays, n_part_keys, has_order, svals,
                      sok, n, shift=0, default=None):
    """-> (out, nulls) in input-row order, or None if ineligible.
    key_arrays: int64 sort keys, partition keys first. All arrays
    length n (unsorted input order)."""
    cap = shape_bucket(n)
    pad = cap - n

    def padk(a, fill):
        a = np.asarray(a)
        if a.dtype.kind == "f":
            # float sort keys (incl. +-inf NULL sentinels): rank-encode
            # on host — order AND equality survive exactly (bit tricks
            # would split -0.0 from 0.0 and silently truncate), and the
            # device kernel stays all-int64
            _, inv = np.unique(a, return_inverse=True)
            a = inv
        a = a.astype(np.int64, copy=False)
        return a if not pad else np.concatenate(
            [a, np.full(pad, fill, dtype=np.int64)])
    # pad flag is the most significant partition key: pads sort last
    # and form their own partition
    keys = [padk(np.zeros(n, dtype=np.int64), 1)]
    # pad fill of the real keys: any value; pads are isolated by the
    # pad-flag key above, which sorts them after every real row
    keys += [padk(a, 0) for a in key_arrays]
    sv = np.asarray(svals)
    val_float = sv.dtype.kind == "f"
    svp = sv if not pad else np.concatenate(
        [sv, np.zeros(pad, dtype=sv.dtype)])
    okp = np.asarray(sok) if not pad else np.concatenate(
        [np.asarray(sok), np.zeros(pad, dtype=bool)])
    key = (name, len(keys), n_part_keys, bool(has_order), cap,
           val_float, default is not None, svp.dtype.str)
    with _KERN_MU:
        kern = _KERN_CACHE.get(key)
        if kern is None:
            kern = _build_kernel(name, len(keys), n_part_keys,
                                 bool(has_order), cap, val_float,
                                 default is not None)
            _KERN_CACHE[key] = kern
    dv = default if default is not None else 0
    # supervised by the caller: executor/window.py wraps
    # run_window_device in guarded_dispatch(site="window") and handles
    # DeviceDegradedError with the host window path
    # tpulint: disable=unguarded-dispatch
    out, nulls = kern([jnp.asarray(k) for k in keys], jnp.asarray(svp),
                      jnp.asarray(okp), dv, jnp.int64(shift))
    out = np.asarray(out)[:n]
    nulls = np.asarray(nulls)[:n]
    return out, (nulls if nulls.any() else None)
