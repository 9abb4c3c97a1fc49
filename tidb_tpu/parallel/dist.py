"""Multi-host process-group bootstrap + per-host shard binding
(docs/DISTRIBUTED.md sections 1 and 3, now code).

Reference mapping: the gRPC DispatchMPPTask topology — one MPP task per
store, software exchanges between them (pkg/store/copr/mpp.go:94,
pkg/planner/core/operator/physicalop/fragment.go:168). TPU-native
redesign: every host joins ONE jax process group, the fragment is ONE
SPMD program over the global mesh, and the exchange is a
compiler-scheduled collective — ICI within a slice, DCN across hosts.
The only cross-host software traffic is the control plane (cluster/rpc).
"""
from __future__ import annotations

import os

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def row_sharding(mesh: Mesh, axis: str = "dp") -> NamedSharding:
    """THE row-partitioned placement: one shard of the row axis per
    mesh device (SNIPPETS.md [2] get_naive_sharding, at the engine's
    column altitude). Every sharded upload seam (copr mpp columns,
    shuffle inputs, validity masks) builds its NamedSharding here so
    the residency store's "sharded" entries all mean the same thing."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Broadcast-exchange placement: a full copy on every mesh device
    (SNIPPETS.md [2] get_empty_sharding)."""
    return NamedSharding(mesh, P())


def sharding_tree(tree, mesh: Mesh, axis: str = "dp"):
    """Per-leaf placement for a pytree of column arrays (SNIPPETS.md
    [2] get_sharding_tree): row arrays (ndim >= 1) partition over the
    row axis, scalars/0-d leaves replicate. Used to device_put a whole
    bound-column tree in one call."""
    import jax.tree_util as jtu

    def leaf_sharding(x):
        nd = getattr(x, "ndim", 0)
        return row_sharding(mesh, axis) if nd else replicated_sharding(
            mesh)
    return jtu.tree_map(leaf_sharding, tree)


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int) -> None:
    """jax.distributed.initialize, idempotent per process. On the CPU
    platform (TIDB_TPU_PLATFORM / JAX_PLATFORMS = cpu) the backend is
    pinned first and cross-process collectives ride gloo."""
    if jax.distributed.is_initialized():
        return
    plat = (os.environ.get("TIDB_TPU_PLATFORM") or
            os.environ.get("JAX_PLATFORMS") or "")
    if plat.lower() == "cpu":
        from .. import force_cpu_backend
        force_cpu_backend()
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis: str = "dp") -> Mesh:
    """Mesh over every device of every process in the group.
    jax.devices() orders devices by process index, so host h's devices
    are contiguous — the row layout of bind_host_rows below is
    [host0 rows | host1 rows | ...]."""
    return Mesh(np.array(jax.devices()), (axis,))


def local_row_cap(n_rows: int, mesh: Mesh) -> int:
    """Smallest per-host row capacity >= n_rows divisible by the local
    device count. Every process must agree on ONE cap (static shapes);
    the coordinator takes the max over workers and broadcasts it."""
    ld = max(1, len([d for d in mesh.devices.flat
                     if d.process_index == jax.process_index()]))
    return -(-max(n_rows, 1) // ld) * ld


def bind_host_rows(mesh: Mesh, arr, local_cap: int, axis: str = "dp"):
    """Per-host shard binding: THIS process's rows become its local
    devices' shards of one global array with no cross-host data
    movement (jax.make_array_from_single_device_arrays). Rows are
    padded/truncated to local_cap, which must be identical on every
    process and divisible by the local device count; pad rows carry
    zeros, so callers must pass a validity mask bound the same way."""
    arr = np.asarray(arr)
    if arr.shape[0] < local_cap:
        pad = np.zeros((local_cap - arr.shape[0],) + arr.shape[1:],
                       dtype=arr.dtype)
        arr = np.concatenate([arr, pad])
    elif arr.shape[0] > local_cap:
        raise ValueError(f"rows {arr.shape[0]} exceed local_cap "
                         f"{local_cap}")
    mine = [d for d in mesh.devices.flat
            if d.process_index == jax.process_index()]
    per = local_cap // len(mine)
    if per * len(mine) != local_cap:
        raise ValueError(f"local device count {len(mine)} must divide "
                         f"local_cap {local_cap}")
    shards = [jax.device_put(arr[i * per:(i + 1) * per], d)
              for i, d in enumerate(mine)]
    gshape = (per * mesh.devices.size,) + arr.shape[1:]
    return jax.make_array_from_single_device_arrays(
        gshape, NamedSharding(mesh, P(axis)), shards)
