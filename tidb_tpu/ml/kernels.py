"""Model forward-pass kernel builders + numpy host twins.

The forward pass is a dense matmul chain (linear / ReLU-MLP), i.e. the
same [rows, k] x [k, m] contractions the vector kernels already feed
the MXU — "Query Processing on Tensor Computation Runtimes" applied to
model scoring. `forward_xp` is xp-generic so the SAME op sequence
serves three call shapes:

  * fused-fragment lowering (xp=jnp, traced inside a copr pipeline
    body — weights become XLA constants of the fragment program),
  * the standalone full-table kernel from `build_forward_kernel`
    (weights ride in as device-resident arguments, uploaded once),
  * the numpy host twin `host_forward` (chaos parity: bit-identical
    float32 op order).

Device/host parity contract: both paths run float32 end to end in the
same order, so outputs are bit-identical on the cpu backend and within
normal MXU ulp elsewhere; NULL handling lives outside the kernel (any
NULL feature nulls the output row, computed by the caller's mask).
"""
from __future__ import annotations

import numpy as np

from ..utils import jaxcfg  # noqa: F401  (jax import order contract)
import jax
import jax.numpy as jnp


def forward_xp(xp, X, weights, biases):
    """Dense forward chain: X [n, f] float32 through len(weights)
    layers, ReLU between hidden layers, linear last. Returns [n] when
    the final width is 1, else [n, out]."""
    h = X
    last = len(weights) - 1
    # the parity contract is float32: on a TPU the default float32
    # matmul is a single bf16 MXU pass (0.1 absolute error against the
    # numpy twin on a 4-16-1 MLP), so the device chain asks for full
    # precision. numpy has no such knob, and the CPU backend ignores it.
    prec = {"precision": jax.lax.Precision.HIGHEST} if xp is jnp else {}
    for i, (W, b) in enumerate(zip(weights, biases)):
        h = xp.matmul(h, xp.asarray(W, dtype=xp.float32), **prec) \
            + xp.asarray(b, dtype=xp.float32)
        if i != last:
            h = xp.maximum(h, xp.float32(0.0))
    if h.ndim == 2 and h.shape[1] == 1:
        h = h[:, 0]
    return h


def build_forward_kernel(nlayers: int):
    """Standalone full-table inference: ONE program = the whole matmul
    chain over the resident feature matrix. Weights/biases are passed
    as arguments (device-resident under the model's uid — uploaded
    once, never per statement), so one compiled kernel serves every
    snapshot of the table at the same (cap, nf, layer-dims) shape."""

    def tidb_ml_forward(X, *params):
        ws = params[:nlayers]
        bs = params[nlayers:]
        return forward_xp(jnp, X, ws, bs)

    return jax.jit(tidb_ml_forward)


def host_forward(X, weights, biases) -> np.ndarray:
    """Numpy twin of `build_forward_kernel` (same float32 op order)."""
    return np.asarray(
        forward_xp(np, np.asarray(X, dtype=np.float32), weights, biases))


def embed_lookup(table: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Embedding-table gather (host-side: embed() runs at ingest /
    host eval and folds into the resident vector matrix through the
    delta path — its device story is the computed VECTOR column)."""
    n = len(table)
    return table[np.asarray(ids, dtype=np.int64) % n]
