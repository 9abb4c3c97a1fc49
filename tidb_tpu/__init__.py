"""tidb_tpu — a TPU-native distributed SQL database framework.

A from-scratch re-design of pingcap/tidb's capabilities for TPU hardware:
the SQL layer (parser → planner → executor) orchestrates on host, while the
vectorized OLAP data path (scan, filter, projection, aggregation, join, sort)
executes as jit-compiled XLA programs on device. MPP fragments map to
pjit/shard_map programs over a `jax.sharding.Mesh`; exchange operators become
XLA collectives over ICI/DCN.

Layer map (mirrors reference SURVEY.md §1, re-architected TPU-first):

    session/     -- session lifecycle, txn state machine, bootstrap
    parser/      -- hand-written lexer + recursive-descent SQL parser -> AST
    planner/     -- logical plan build, rewrite rules, physical plan + cost
    executor/    -- batch Volcano operators (host orchestration)
    expression/  -- expression trees compiled to fused jax kernels
    ops/         -- standalone device kernels (sort-based equi-join)
    chunk/       -- columnar batch: host numpy <-> padded device arrays
    copr/        -- in-process "coprocessor": pushed-down DAG on device
    distsql/     -- range split -> parallel partition tasks -> stream merge
    mpp/         -- plan fragments -> pjit programs, exchange = collectives
    parallel/    -- mesh construction, sharding specs, collective helpers
    storage/     -- MVCC KV store + columnar store (delta + stable)
    codec/       -- key/value encoding contract (tablecodec analog)
    meta/        -- schema metadata persisted in the KV store
    models/      -- schema model structs (DBInfo/TableInfo/ColumnInfo/IndexInfo)
    infoschema/  -- immutable snapshot schema cache
    stats/       -- histograms, sketches, ANALYZE
    types/       -- datum types, decimal, time, field types, coercion
    utils/       -- memory tracker, ranger, misc
"""

__version__ = "0.1.0"


def force_cpu_backend():
    """Pin jax to the host-CPU backend through public configuration
    only. Must run before the first jax device op (the platform is read
    at backend initialization); used by the CLI (--cpu),
    TIDB_TPU_PLATFORM=cpu, tests/conftest.py, scripts/cpuforce.py,
    __graft_entry__.py and init_distributed."""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


import os as _os
if _os.environ.get("TIDB_TPU_PLATFORM", "").lower() == "cpu":
    force_cpu_backend()
del _os
