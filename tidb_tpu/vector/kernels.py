"""Vector-search kernels: tiled distance matmuls + top-k on the MXU,
with numpy host twins sharing the same selection-key construction.

Device/host parity contract: both paths rank by the SAME key
    dead/pad row        -> -inf   (never selected while live rows remain)
    NULL/invalid vector -> +inf   (MySQL ORDER BY ASC: NULLs first)
    live row            -> -distance (float32)
and both break ties by lowest row index (jax.lax.top_k is stable in
index order; the host twin sorts with kind='stable'). The executor
re-ranks the returned candidate slate on host with the statement's
own expression evaluator, so a float32-vs-float64 ulp at the k-th
boundary can shuffle candidates but never the final rows (the slate
carries slack past k).

Distances are float32 — the MXU's native tile — computed via the
matmul forms (||m||^2 - 2 m.q + ||q||^2 for L2) so the whole scan is
one [rows, k] x [k] contraction: the tensor-runtime thesis applied to
nearest-neighbor search.
"""
from __future__ import annotations

import numpy as np

from ..utils import jaxcfg  # noqa: F401  (jax import order contract)
import jax
import jax.numpy as jnp


def _distances_xp(xp, mat, q, metric):
    """Metric distances of every matrix row to q, in float32, via the
    matmul form. Shared between the jitted kernels (xp=jnp) and the
    host twins (xp=np) so both see the same op sequence."""
    s = mat @ q                                     # [rows]  (MXU)
    if metric == "vec_l2_distance":
        m2 = (mat * mat).sum(axis=1)
        q2 = (q * q).sum()
        return xp.sqrt(xp.maximum(m2 - 2.0 * s + q2, 0.0))
    if metric == "vec_cosine_distance":
        m2 = (mat * mat).sum(axis=1)
        q2 = (q * q).sum()
        den = xp.sqrt(m2) * xp.sqrt(q2)
        # zero vector -> 0/0 -> NaN -> NULL (sorts first, like host)
        return 1.0 - s / den
    if metric == "vec_negative_inner_product":
        return -s
    raise ValueError(f"unsupported vector metric {metric}")


def _select_key_xp(xp, d, valid):
    """The shared selection key (module docstring). NULL vectors are
    NaN rows in the fixed-width matrix, so their distance is NaN."""
    inf = xp.float32(np.inf)
    return xp.where(valid,
                    xp.where(xp.isnan(d), inf, -d),
                    -inf)


def build_topk_kernel(metric: str, kcap: int):
    """Exact brute-force top-k: ONE program = distances over the whole
    resident matrix + lax.top_k. -> (keys[kcap] f32, idx[kcap] i32);
    keys <= -inf mark dead padding the host must drop, keys == +inf
    mark NULL rows (ordered first, ASC semantics)."""

    def tidb_vec_topk(mat, valid, q):
        d = _distances_xp(jnp, mat, q, metric)
        key = _select_key_xp(jnp, d, valid)
        vals, idx = jax.lax.top_k(key, kcap)
        return vals, idx.astype(jnp.int32)

    return jax.jit(tidb_vec_topk)


def build_ivf_score_kernel(metric: str, kcap: int):
    """ANN candidate scoring: gather the probed posting lists' rows
    from the RESIDENT matrix (only the candidate index vector rides
    host->device per query) and top-k them. cand is padded with 0s;
    cvalid gates padding and MVCC-dead rows off."""

    def tidb_vec_ivf_score(mat, cand, cvalid, q):
        sub = jnp.take(mat, cand, axis=0)
        d = _distances_xp(jnp, sub, q, metric)
        key = _select_key_xp(jnp, d, cvalid)
        vals, pos = jax.lax.top_k(key, kcap)
        return vals, jnp.take(cand, pos).astype(jnp.int32)

    return jax.jit(tidb_vec_ivf_score)


def build_kmeans_step():
    """One Lloyd iteration: nearest-centroid assignment (matmul
    distance form) + one-hot segment means — both MXU contractions.
    Empty clusters keep their previous centroid."""

    def tidb_vec_kmeans_step(mat, valid, cent):
        # zero the dead/NULL (NaN) rows BEFORE the segment matmul:
        # their one-hot weight is 0, but 0 * NaN = NaN and one poisoned
        # row would NaN every centroid
        m = jnp.where(valid[:, None], mat, 0.0)
        d2 = _pair_d2(m, cent)
        a = jnp.argmin(d2, axis=1)
        oh = jax.nn.one_hot(a, cent.shape[0], dtype=jnp.float32)
        oh = oh * valid[:, None].astype(jnp.float32)
        sums = oh.T @ m                        # [nlist, dim]  (MXU)
        cnts = oh.sum(axis=0)
        return jnp.where(cnts[:, None] > 0,
                         sums / jnp.maximum(cnts, 1.0)[:, None], cent)

    return jax.jit(tidb_vec_kmeans_step)


def build_assign_kernel():
    """Nearest-centroid id per row (posting-list construction and the
    incremental delta fold)."""

    def tidb_vec_assign(mat, cent):
        return jnp.argmin(_pair_d2(mat, cent), axis=1).astype(jnp.int32)

    return jax.jit(tidb_vec_assign)


def _pair_d2(mat, cent):
    """Squared L2 distance matrix [rows, nlist] in matmul form. NaN
    (NULL) rows produce NaN everywhere; callers gate them with the
    valid mask."""
    m2 = (mat * mat).sum(axis=1)[:, None]
    c2 = (cent * cent).sum(axis=1)[None, :]
    return m2 - 2.0 * (mat @ cent.T) + c2


# ---- host twins --------------------------------------------------------

def host_distances(mat, q, metric):
    """The numpy twin of the device distance computation (float32, same
    matmul form)."""
    return _distances_xp(np, np.asarray(mat, dtype=np.float32),
                         np.asarray(q, dtype=np.float32), metric)


def host_topk(mat, valid, q, metric, k):
    """Full host ranking with the shared selection key; ties broken by
    row index (stable sort) exactly like lax.top_k. -> positions of
    the k best live rows (may be shorter than k)."""
    with np.errstate(invalid="ignore", divide="ignore"):
        d = host_distances(mat, q, metric)
        key = _select_key_xp(np, d, np.asarray(valid, dtype=bool))
    order = np.argsort(-key, kind="stable")[:k]
    return order[key[order] > -np.inf]


def host_kmeans(mat, valid, cent, iters):
    """Numpy Lloyd twin of build_kmeans_step (the vector/train host
    fallback)."""
    mat = mat.astype(np.float32)
    v = np.asarray(valid, dtype=bool)
    for _ in range(iters):
        with np.errstate(invalid="ignore"):
            a = np.argmin(_pair_d2_np(mat, cent), axis=1)
        a = np.where(v, a, -1)
        sums = np.zeros_like(cent)
        cnts = np.zeros(len(cent), dtype=np.float32)
        live = a >= 0
        np.add.at(sums, a[live], mat[live])
        np.add.at(cnts, a[live], 1.0)
        cent = np.where(cnts[:, None] > 0,
                        sums / np.maximum(cnts, 1.0)[:, None], cent)
    return cent


def host_assign(mat, cent):
    with np.errstate(invalid="ignore"):
        return np.argmin(_pair_d2_np(mat.astype(np.float32), cent),
                         axis=1).astype(np.int32)


def _pair_d2_np(mat, cent):
    m2 = (mat * mat).sum(axis=1)[:, None]
    c2 = (cent * cent).sum(axis=1)[None, :]
    return m2 - 2.0 * (mat @ cent.T) + c2
