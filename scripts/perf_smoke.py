#!/usr/bin/env python
"""Perf smoke: the whole-query single-dispatch contract, enforced.

All 22 TPC-H queries at SF0.05 (CPU backend by default — the contract
is about dispatch STRUCTURE, not device speed) must, at steady state:

  * cross the host<->device boundary at most twice:
    phase `dispatches` <= 2 and `syncs` <= 1 per query
    (docs/PERFORMANCE.md sync budget; ISSUE 6 acceptance);
  * re-upload ZERO bytes — every base-table buffer is resident in the
    device store from the warmup pass (`upload_bytes` == 0);
  * return rows identical to the pure-host path.

MESH MODE (PERF_MESH=1, ISSUE 7 acceptance): the same budget on an
8-virtual-device mesh with MPP exchanges on. Every query that routes
through a mesh path (fused-mpp pipeline / copr mpp fragment) must hold
dispatches <= 2, syncs <= 1, and zero warm re-uploads — the collective
exchanges (psum/all_gather/all_to_all) and the mesh-sharded residency
store may not smuggle host round trips or re-upload sharded columns.
The gate also requires a minimum number of mesh-routed queries so a
silent mpp->single-chip routing regression can't make it vacuous.

The warmup pass pays compiles, uploads, and capacity learning; the
measured pass is the steady state a dashboard workload lives in. A fast
slice runs in tier-1
(tests/test_device_residency.py::test_perf_smoke_fast_slice, and
::test_perf_smoke_mesh_fast_slice for mesh mode); this script is the
full gate.

Usage:  python scripts/perf_smoke.py
Env:    PERF_SF (0.05), PERF_QUERIES (comma list, default all),
        PERF_MAX_DISPATCHES (2), PERF_MAX_SYNCS (1),
        PERF_MESH (0; 1 = 8-device mesh mode),
        PERF_MESH_MIN_ELIGIBLE (12)
Exit:   0 every query within budget and host-identical; 1 otherwise.
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("TIDB_TPU_LOCKRANK", "1")   # lock-rank sanitizer armed
# a structure gate: the CPU backend unless a platform is asked for by
# name (PERF_SF=1 JAX_PLATFORMS=tpu runs the same budget on the chip)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if os.environ.get("PERF_MESH") == "1" and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # must land before the first jax import: the device count is read
    # at backend init
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count"
                               "=8").strip()


def run(queries=None, sf=None, max_dispatches=None, max_syncs=None,
        out=sys.stderr, mesh=None, mesh_min_eligible=None):
    """-> list of failure strings (empty = gate green). Importable so
    the tier-1 fast slices reuse the exact gate predicate."""
    sf = float(os.environ.get("PERF_SF", "0.05")) if sf is None else sf
    max_dispatches = int(os.environ.get("PERF_MAX_DISPATCHES", "2")) \
        if max_dispatches is None else max_dispatches
    max_syncs = int(os.environ.get("PERF_MAX_SYNCS", "1")) \
        if max_syncs is None else max_syncs
    if mesh is None:
        mesh = os.environ.get("PERF_MESH") == "1"
    if mesh_min_eligible is None:
        mesh_min_eligible = int(os.environ.get("PERF_MESH_MIN_ELIGIBLE",
                                               "12"))

    from tidb_tpu.testkit import TestKit
    from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES
    from tidb_tpu.utils import phase

    if queries is None:
        qenv = os.environ.get("PERF_QUERIES", "")
        queries = qenv.split(",") if qenv else \
            sorted(ALL_QUERIES, key=lambda q: int(q[1:]))

    failures = []
    if mesh:
        import jax
        ndev = len(jax.devices())
        if ndev < 2:
            return [f"mesh mode needs >= 2 devices, have {ndev} "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=8 before jax imports)"]

    import jax
    tk = TestKit()
    print(f"# perf_smoke: backend={jax.default_backend()} sf={sf} "
          f"queries={len(queries)} mesh={'on' if mesh else 'off'} "
          f"budget: dispatches<={max_dispatches} syncs<={max_syncs} "
          f"upload_bytes==0", file=out)
    load_tpch(tk, sf=sf, seed=42)
    if mesh:
        # route everything eligible over the mesh: the gate is about
        # the exchange/residency structure, not the row-count heuristic
        tk.must_exec("set @@tidb_enable_mpp = on")
        tk.must_exec("set @@tidb_mpp_min_rows = 0")

    host = {}
    tk.domain.copr.use_device = False
    try:
        for q in queries:
            host[q] = tk.must_query(ALL_QUERIES[q]).rows
    finally:
        tk.domain.copr.use_device = True

    import time
    for q in queries:                    # warmup: compiles + uploads +
        t0 = time.time()                 # learned shuffle capacities
        tk.must_query(ALL_QUERIES[q])
        print(f"# warmup {q}: {time.time() - t0:.1f}s", file=out,
              flush=True)

    def _mpp_marks(m):
        return (m.get("fused_pipeline_mpp_hit", 0),
                m.get("copr_mpp_exec", 0),
                m.get("fused_shuffle_join", 0))

    eligible = []
    for q in queries:
        before = _mpp_marks(tk.domain.metrics)
        phase.reset()
        try:
            rows = tk.must_query(ALL_QUERIES[q]).rows
        except Exception as e:           # noqa: BLE001
            failures.append(f"{q}: error {type(e).__name__}: "
                            f"{str(e)[:120]}")
            continue
        s = phase.snap()
        on_mesh = mesh and _mpp_marks(tk.domain.metrics) != before
        if on_mesh:
            eligible.append(q)
        d = s.get("dispatches", 0)
        sy = s.get("syncs", 0)
        ub = s.get("upload_bytes", 0)
        line = (f"{q}:{' mesh' if on_mesh else ''} dispatches={d} "
                f"syncs={sy} upload_bytes={ub} "
                f"upload_hits={s.get('upload_hits', 0)} "
                f"exchanges={s.get('mpp_exchanges', 0)}")
        print(f"# {line}", file=out)
        if d > max_dispatches:
            failures.append(f"{q}: {d} dispatches > {max_dispatches}")
        if sy > max_syncs:
            failures.append(f"{q}: {sy} host syncs > {max_syncs}")
        if ub > 0:
            failures.append(f"{q}: re-uploaded {ub} bytes on a warm "
                            "statement (residency broken)")
        if rows != host[q]:
            failures.append(f"{q}: device rows != host rows "
                            f"({len(rows)} vs {len(host[q])})")
    if mesh and len(eligible) < mesh_min_eligible:
        failures.append(
            f"only {len(eligible)} of {len(queries)} queries routed "
            f"over the mesh ({','.join(eligible) or 'none'}); "
            f"expected >= {mesh_min_eligible} — mpp routing regressed")
    return failures


def main():
    failures = run()
    if failures:
        print("perf_smoke: FAIL", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    mode = "mesh (8-device)" if os.environ.get("PERF_MESH") == "1" \
        else "single-chip"
    print(f"perf_smoke: OK — every query within the dispatch/sync "
          f"budget on the {mode} path, zero warm re-uploads, "
          "host-identical rows", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
