"""Device kernel library.

The engine's hot ops are expressed in jax.numpy and fused by XLA
(filter+projection+partial-agg compile into one kernel per copr
partition, tidb_tpu/copr/dag_exec.py; the fused join pipeline in
tidb_tpu/copr/pipeline.py). This package holds the standalone device
kernels operators call directly: `device_join` — the sort-based
equi-join build/probe behind `tidb_join_exec`. There is no hand-written
Pallas kernel in the tree: the engine's values are int64, which Mosaic
does not lower, so a Pallas path needs a 32-bit limb design first.
"""
