#!/usr/bin/env python
"""Benchmark driver: TPC-H on the TPU-native engine vs its own host path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "backend": <jax platform>, "device": {platform, kind, count},
   "queries": {per-query ms}}

value       = rows/sec scanned through the full SQL stack on the device path
vs_baseline = host-path wall time / device-path wall time (geomean across
              queries) — the engine's own `domain.copr.use_device = False`
              mode is the baseline, mirroring BASELINE.md's "vs CPU-only
              tidb-server" target on the same host.

One process, on the platform jax gives it, named in every result. A run
that was not asked for the CPU (JAX_PLATFORMS=cpu / TIDB_TPU_PLATFORM=cpu)
and finds no accelerator exits nonzero; so does a query error. A number
from the CPU backend is a count or a correctness result, never a device
speed, and its unit says so.
"""
import json
import math
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)

# perf run: measured write paths must match a real deployment, not the
# testing build with the row<->index mutation checker enabled
os.environ.setdefault("TIDB_TPU_MUTATION_CHECK", "0")


def _resolve_device():
    """-> {platform, kind, count} as jax reports it. The CPU must be
    asked for by name; anything else must find an accelerator."""
    asked_cpu = "cpu" in (
        os.environ.get("JAX_PLATFORMS", "").lower(),
        os.environ.get("TIDB_TPU_PLATFORM", "").lower())
    if asked_cpu:
        from tidb_tpu import force_cpu_backend
        force_cpu_backend()
    import jax
    devs = jax.devices()        # raises when the platform asked for is absent
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] == "cpu" and not asked_cpu:
        print("bench: no accelerator found and the CPU was not asked for "
              "(set JAX_PLATFORMS=cpu for a CPU-backend run)",
              file=sys.stderr)
        sys.exit(2)
    print(f"# backend: {device}", file=sys.stderr)
    return device


def _unit(text, device):
    """Every unit names what it was measured on."""
    if device["platform"] == "cpu":
        return text + " [jax CPU backend — not a device measurement]"
    return text + f" [{device['kind']} x{device['count']}]"


def htap_main(device):
    """CH-benCHmark-style HTAP mix (BASELINE stage 5): OLTP threads doing
    point reads + updates on orders while an OLAP thread loops TPC-H Q1.
    Reports OLTP TPS alongside OLAP latency."""
    import threading
    sf = float(os.environ.get("BENCH_SF", "0.05"))
    seconds = float(os.environ.get("BENCH_SECONDS", "10"))
    n_oltp = int(os.environ.get("BENCH_OLTP_THREADS", "2"))

    from tidb_tpu.testkit import TestKit
    from tidb_tpu.bench.tpch import load_tpch, QUERIES

    tk = TestKit()
    load_tpch(tk, sf=sf, seed=42)
    n_ord = tk.domain.table_rows("test", tk.domain.infoschema()
                                 .table_by_name("test", "orders"))
    tk.must_query(QUERIES["q1"])       # warm OLAP kernels

    stop = threading.Event()
    oltp_counts = [0] * n_oltp
    olap_lat = []

    def oltp_worker(i):
        s = tk.new_session()
        rng = __import__("random").Random(i)
        while not stop.is_set():
            key = rng.randrange(1, int(n_ord))
            if rng.random() < 0.5:
                s.must_query(
                    f"select o_totalprice from orders where o_orderkey = {key}")
            else:
                s.must_exec(
                    f"update orders set o_shippriority = o_shippriority + 1 "
                    f"where o_orderkey = {key}")
            oltp_counts[i] += 1

    def olap_worker():
        s = tk.new_session()
        while not stop.is_set():
            t0 = time.time()
            s.must_query(QUERIES["q1"])
            olap_lat.append(time.time() - t0)

    rw_lat = []

    def rw_analyst():
        """The dirty-overlay HTAP case: update+insert lineitem in an
        open transaction, run Q1 INSIDE it (must see own writes and
        stay on the fused device path), then roll back."""
        s = tk.new_session()
        rng = __import__("random").Random(99)
        k = 0
        while not stop.is_set():
            k += 1
            s.must_exec("begin")
            s.must_exec(f"update lineitem set l_quantity = l_quantity + 1 "
                        f"where l_orderkey = {rng.randrange(1, 6) * 4 + 1} "
                        f"and l_linenumber = 1")
            s.must_exec(f"insert into lineitem (l_orderkey, l_linenumber, "
                        f"l_partkey, l_suppkey, l_quantity, l_extendedprice,"
                        f" l_discount, l_tax, l_returnflag, l_linestatus, "
                        f"l_shipdate, l_commitdate, l_receiptdate, "
                        f"l_shipinstruct, l_shipmode, l_comment) values "
                        f"(1, {200 + k}, 1, 1, 5, 100.0, 0.05, 0.02, 'N', "
                        f"'O', '1996-03-13', '1996-02-12', '1996-03-22', "
                        f"'NONE', 'MAIL', 'bench overlay row')")
            t0 = time.time()
            s.must_query(QUERIES["q1"])
            rw_lat.append(time.time() - t0)
            s.must_exec("rollback")

    threads = [threading.Thread(target=oltp_worker, args=(i,), daemon=True)
               for i in range(n_oltp)]
    threads.append(threading.Thread(target=olap_worker, daemon=True))
    threads.append(threading.Thread(target=rw_analyst, daemon=True))
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    tps = sum(oltp_counts) / seconds
    q1_ms = 1000 * sum(olap_lat) / max(len(olap_lat), 1)
    m = tk.domain.metrics
    routing = {k: m.get(k, 0) for k in (
        "fused_pipeline_hit", "fused_pipeline_mpp_hit",
        "fused_pipeline_dirty_overlay", "fused_pipeline_fallback",
        "copr_device_exec", "copr_host_exec")}
    rw_ms = 1000 * sum(rw_lat) / max(len(rw_lat), 1)
    print(f"# htap: oltp_tps={tps:.1f} q1_avg={q1_ms:.1f}ms "
          f"olap_queries={len(olap_lat)} dirty_q1_avg={rw_ms:.1f}ms "
          f"dirty_queries={len(rw_lat)} routing={routing}",
          file=sys.stderr)
    print(json.dumps({
        "metric": f"ch_benchmark_sf{sf}_htap",
        "value": round(tps, 1),
        "unit": _unit(f"oltp ops/s with concurrent Q1 (avg {q1_ms:.0f}ms)",
                      device),
        "vs_baseline": round(q1_ms / 1000.0, 3),
        "backend": device["platform"],
        "device": device,
        "routing": routing,
        "dirty_q1_ms": round(rw_ms, 1),
        "dirty_queries": len(rw_lat),
    }))


def _percentiles(lat_s):
    """p50/p95/p99 in ms from a list of per-op seconds."""
    if not lat_s:
        return {}
    xs = sorted(lat_s)
    n = len(xs)

    def pct(p):
        return round(1000.0 * xs[min(n - 1, int(n * p))], 3)
    return {"p50_ms": pct(0.50), "p95_ms": pct(0.95),
            "p99_ms": pct(0.99),
            "max_ms": round(1000.0 * xs[-1], 3)}


def oltp_main(device):
    """sysbench-style OLTP benchmark (the reference's headline numbers
    are TPC-C/sysbench — docs/design cites +27-54% QPS pushdown gains):
    point SELECT by PK, UPDATE by PK, and a small secondary-index range
    read, each run across a thread-count sweep (BENCH_OLTP_THREADS, a
    comma list — the serving-tier question is how throughput and tail
    latency hold up as sessions pile on, not one fixed concurrency)
    with p50/p95/p99 latency capture per (op, thread-count) cell."""
    import threading
    import random
    sf = float(os.environ.get("BENCH_SF", "0.1"))
    seconds = float(os.environ.get("BENCH_SECONDS", "10"))
    sweep = [int(x) for x in
             os.environ.get("BENCH_OLTP_THREADS", "4,64,256").split(",")
             if x.strip()]

    from tidb_tpu.testkit import TestKit
    tk = TestKit()
    tk.must_exec("create table sbtest (id int primary key, "
                 "k int, c varchar(120), pad varchar(60), key k_k (k))")
    n_rows = int(100_000 * sf)
    rng = random.Random(42)
    for start in range(0, n_rows, 5000):
        vals = ",".join(
            f"({i}, {rng.randrange(n_rows)}, 'c{i % 997}', 'p{i % 97}')"
            for i in range(start, min(start + 5000, n_rows)))
        tk.must_exec(f"insert into sbtest values {vals}")

    def bench_op(name, fn, nthreads):
        stop = threading.Event()
        counts = [0] * nthreads
        errs = [0] * nthreads
        lats = [None] * nthreads
        perf = time.perf_counter

        def worker(i):
            s = tk.new_session()
            r = random.Random(i)
            mylat = []
            while not stop.is_set():
                t0 = perf()
                try:
                    fn(s, r)
                    counts[i] += 1
                    mylat.append(perf() - t0)
                except Exception as e:          # noqa: BLE001
                    # a dead worker silently deflates QPS: count and
                    # keep going, surface the tally in the artifact
                    errs[i] += 1
                    if errs[i] == 1:
                        print(f"# oltp {name} thread {i} error: "
                              f"{type(e).__name__}: {str(e)[:120]}",
                              file=sys.stderr)
            lats[i] = mylat
        ths = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(nthreads)]
        for t in ths:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in ths:
            t.join(timeout=30)
        qps = sum(counts) / seconds
        all_lat = [x for ls in lats if ls for x in ls]
        cell = {"ops_s": round(qps, 1), "errors": sum(errs),
                **_percentiles(all_lat)}
        print(f"# oltp {name} x{nthreads}: {qps:.1f} ops/s "
              f"p99={cell.get('p99_ms', 0)}ms "
              f"({cell['errors']} errors)", file=sys.stderr)
        return cell

    ops = [
        ("point_select", lambda s, r: s.must_query(
            f"select c from sbtest where id = {r.randrange(n_rows)}")),
        ("index_range", lambda s, r: s.must_query(
            f"select id from sbtest where k >= {r.randrange(n_rows)} "
            f"limit 10")),
        ("update_pk", lambda s, r: s.must_exec(
            f"update sbtest set k = k + 1 "
            f"where id = {r.randrange(n_rows)}")),
    ]
    sweep_res = {}
    for nthreads in sweep:
        sweep_res[str(nthreads)] = {
            name: bench_op(name, fn, nthreads) for name, fn in ops}
    # headline cell: point selects at the highest swept concurrency —
    # the serving-tier claim under test. `errors` describes the SAME
    # cell as `ops` (per-cell tallies live in sweep), matching the
    # seed artifact's pairing.
    top = str(sweep[-1])
    res = {name: sweep_res[top][name]["ops_s"] for name, _ in ops}
    errors = {name: sweep_res[top][name]["errors"] for name, _ in ops}
    print(json.dumps({
        "metric": f"oltp_sf{sf}_sysbench",
        "value": res["point_select"],
        "unit": _unit("point-select ops/s (sysbench-style, %s threads)"
                      % top, device),
        "vs_baseline": 0,
        "backend": device["platform"],
        "device": device,
        "ops": res,
        "errors": errors,
        "threads": sweep,
        "sweep": sweep_res,
    }))


def vector_main(device):
    """Vector-search benchmark (ISSUE 15, docs/VECTOR.md): corpus-size
    x nprobe sweep over a clustered VECTOR corpus, measuring exact
    single-dispatch qps, IVF ANN qps, and recall@10 vs the float64
    host oracle per cell, at the runtime seam the executor calls.
    Emits the artifact to BENCH_VECTOR_OUT (default
    BENCH_VECTOR_cpu.json on the cpu backend)."""
    import numpy as np
    dim = int(os.environ.get("BENCH_VECTOR_DIM", "32"))
    sizes = [int(x) for x in os.environ.get(
        "BENCH_VECTOR_ROWS", "10000,50000").split(",") if x.strip()]
    nprobes = [int(x) for x in os.environ.get(
        "BENCH_VECTOR_NPROBE", "4,8,16").split(",") if x.strip()]
    nq = int(os.environ.get("BENCH_VECTOR_QUERIES", "50"))

    from tidb_tpu.testkit import TestKit
    from tidb_tpu.executor.exec_base import ExecContext

    def fmt(v):
        return "[" + ",".join(f"{x:.4f}" for x in v.tolist()) + "]"

    cells = {}
    for rows in sizes:
        tk = TestKit()
        tk.must_exec("create table corpus (id bigint primary key, "
                     f"grp bigint, e vector({dim}))")
        rng = np.random.RandomState(42)
        centers = rng.randn(256, dim).astype(np.float32) * 4.0
        mat = (centers[rng.randint(0, 256, rows)] +
               rng.randn(rows, dim).astype(np.float32) * 0.35)
        texts = np.array([fmt(mat[i]) for i in range(rows)],
                         dtype=object)
        grp = (np.arange(rows, dtype=np.int64) * 7919) % 1000
        tbl = tk.domain.infoschema().table_by_name("test", "corpus")
        ctab = tk.domain.columnar.table(tbl)
        ctab.bulk_append({"id": np.arange(rows, dtype=np.int64),
                          "grp": grp, "e": texts}, rows,
                         handles=np.arange(1, rows + 1,
                                           dtype=np.int64))
        stored = np.array([np.fromstring(t[1:-1], sep=",")
                           for t in texts], dtype=np.float32)
        tk.must_exec("create vector index vidx on corpus (e) "
                     "using ivf")
        tbl = tk.domain.infoschema().table_by_name("test", "corpus")
        rt, copr = tk.domain.vector, tk.domain.copr
        ci = tbl.find_column("e")
        idx = rt.index_for(tbl, "e")
        ectx = ExecContext(tk.sess)
        queries = (mat[rng.randint(0, rows, nq)] +
                   rng.randn(nq, dim).astype(np.float32) * 0.15)

        def oracle(q):
            d = np.linalg.norm(
                stored.astype(np.float64) - q.astype(np.float64),
                axis=1)
            return set(np.argsort(d, kind="stable")[:10].tolist())

        rt.exact_topk(copr, ctab, ci.id, dim, "vec_l2_distance",
                      queries[0], 10, None, ectx=ectx)
        t0 = time.perf_counter()
        for i in range(nq):
            rt.exact_topk(copr, ctab, ci.id, dim, "vec_l2_distance",
                          queries[i], 10, None, ectx=ectx)
        exact_qps = nq / (time.perf_counter() - t0)
        for nprobe in nprobes:
            tk.must_exec(f"set @@tidb_tpu_vector_nprobe = {nprobe}")
            ectx = ExecContext(tk.sess)
            rt.ivf_topk(copr, ctab, idx, "vec_l2_distance",
                        queries[0], 10, None, ectx=ectx)
            hits = 0
            reps = max(nq * 4, 200)
            t0 = time.perf_counter()
            for i in range(reps):
                rt.ivf_topk(copr, ctab, idx, "vec_l2_distance",
                            queries[i % nq], 10, None, ectx=ectx)
            ivf_qps = reps / (time.perf_counter() - t0)
            for i in range(nq):
                cand = rt.ivf_topk(copr, ctab, idx, "vec_l2_distance",
                                   queries[i], 10, None, ectx=ectx)[:10]
                hits += len(oracle(queries[i]) &
                            set(np.asarray(cand).tolist()))
            cells[f"rows={rows},nprobe={nprobe}"] = {
                "exact_qps": round(exact_qps, 1),
                "ivf_qps": round(ivf_qps, 1),
                "speedup": round(ivf_qps / max(exact_qps, 1e-9), 2),
                "recall_at_10": round(hits / (10 * nq), 4),
            }
            print(f"# rows={rows} nprobe={nprobe}: "
                  f"{cells[f'rows={rows},nprobe={nprobe}']}",
                  file=sys.stderr)
        # hybrid cells (ISSUE 20, docs/ML.md): scalar predicate +
        # ORDER BY distance LIMIT k through the full statement path —
        # the predicate mask gates candidates BEFORE top-k, so recall
        # is vs the MASKED float64 oracle at each selectivity
        from tidb_tpu.utils import phase as _phase
        tk.must_exec("set @@tidb_tpu_vector_nprobe = 8")
        for lbl, pred, maskfn in (
                ("0.1%", "grp = 7", lambda g: g == 7),
                ("1%", "grp < 10", lambda g: g < 10),
                ("10%", "grp < 100", lambda g: g < 100)):
            mask = maskfn(grp)

            def hsql(q):
                return (f"select id from corpus where {pred} order "
                        f"by vec_l2_distance(e, '{fmt(q)}') limit 10")

            def horacle(q):
                d = np.linalg.norm(stored.astype(np.float64) -
                                   q.astype(np.float64), axis=1)
                d = np.where(mask, d, np.inf)
                return set(
                    int(i) for i in np.argsort(d, kind="stable")[:10]
                    if d[i] < np.inf)

            tk.must_query(hsql(queries[0]))         # warm
            hits = ideal = 0
            _phase.reset()
            t0 = time.perf_counter()
            for i in range(nq):
                got = {r[0] for r in
                       tk.must_query(hsql(queries[i])).rows}
                want = horacle(queries[i])
                hits += len(got & want)
                ideal += len(want)
            dt = time.perf_counter() - t0
            snap = _phase.snap()
            cells[f"rows={rows},hybrid={lbl}"] = {
                "qps": round(nq / dt, 1),
                "recall_at_10": round(hits / max(ideal, 1), 4),
                "dispatches_per_query": round(
                    snap.get("dispatches", 0) / nq, 2),
            }
            print(f"# rows={rows} hybrid={lbl}: "
                  f"{cells[f'rows={rows},hybrid={lbl}']}",
                  file=sys.stderr)
    headline = cells.get(f"rows={sizes[-1]},nprobe=8") or \
        list(cells.values())[-1]
    doc = {
        "metric": f"vector_search_dim{dim}",
        "value": headline["ivf_qps"],
        "unit": _unit("IVF searches/s, 50k x 32d clustered corpus, "
                      "nprobe=8", device),
        "vs_baseline": headline["speedup"],
        "backend": device["platform"],
        "device": device,
        "recall_at_10": headline["recall_at_10"],
        "cells": cells,
    }
    out = os.environ.get(
        "BENCH_VECTOR_OUT",
        os.path.join(_REPO, f"BENCH_VECTOR_{device['platform']}.json"))
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"# artifact -> {out}", file=sys.stderr)
    print(json.dumps(doc))


def main():
    device = _resolve_device()
    mode = os.environ.get("BENCH_MODE")
    if mode == "htap":
        return htap_main(device)
    if mode == "oltp":
        return oltp_main(device)
    if mode == "vector":
        return vector_main(device)
    sf = float(os.environ.get("BENCH_SF", "1"))
    qenv = os.environ.get("BENCH_QUERIES", "all")
    repeats = int(os.environ.get("BENCH_REPEATS", "3"))
    # the single-threaded numpy baseline can take minutes/query at SF10;
    # cap total baseline time so it can't starve the device measurement
    # (negative = skip baselines)
    cpu_budget = float(os.environ.get("BENCH_CPU_BUDGET", "900"))

    from tidb_tpu.testkit import TestKit
    from tidb_tpu.bench.tpch import load_tpch, ALL_QUERIES
    from tidb_tpu.utils import phase as _phase

    if qenv == "all":
        queries = sorted(ALL_QUERIES, key=lambda q: int(q[1:]))
    else:
        queries = qenv.split(",")

    tk = TestKit()
    t0 = time.time()
    load_tpch(tk, sf=sf, seed=42)
    load_s = time.time() - t0
    li = tk.domain.infoschema().table_by_name("test", "lineitem")
    n_rows = tk.domain.columnar.tables[li.id].live_count()
    print(f"# lineitem rows={n_rows} load={load_s:.1f}s", file=sys.stderr)

    def peak_rss_gb():
        import resource
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KB, darwin reports bytes
        div = (1 << 30) if sys.platform == "darwin" else (1 << 20)
        return round(rss / div, 2)

    phases = {}

    def run(q, use_device, n_runs, warmup):
        """Best-of-n wall seconds; a query error propagates and ends
        the run nonzero."""
        tk.domain.copr.use_device = use_device
        try:
            if warmup:
                _phase.reset()
                t = time.time()
                tk.must_query(ALL_QUERIES[q])   # warmup (compile)
                w = _phase.snap()
                w["total_ms"] = round((time.time() - t) * 1000, 1)
                phases.setdefault(q, {})["warmup"] = w
            best = math.inf
            for _ in range(n_runs):
                _phase.reset()
                t = time.time()
                tk.must_query(ALL_QUERIES[q])
                dt = time.time() - t
                if dt < best and use_device:
                    s = _phase.snap()
                    s["total_ms"] = round(dt * 1000, 1)
                    phases.setdefault(q, {})["best"] = s
                best = min(best, dt)
            return best
        finally:
            tk.domain.copr.use_device = True

    speedups = []
    per_query = {}
    dev_times = {}
    cpu_spent = 0.0
    for q in queries:
        t_dev = dev_times[q] = run(q, True, repeats, warmup=True)
        per_query[q] = {"ms": round(t_dev * 1000, 1)}
        if cpu_spent > cpu_budget:
            per_query[q]["cpu_skipped"] = "baseline budget exhausted"
            continue
        # no compile on the host path: one un-warmed run per query, so
        # the budget covers as many queries as possible
        t0 = time.time()
        t_cpu = run(q, False, 1, warmup=False)
        cpu_spent += time.time() - t0
        speedups.append(t_cpu / t_dev)
        per_query[q].update({"cpu_ms": round(t_cpu * 1000, 1),
                             "speedup": round(t_cpu / t_dev, 2)})
        print(f"# {q}: device={t_dev*1000:.1f}ms host={t_cpu*1000:.1f}ms "
              f"speedup={t_cpu/t_dev:.2f}x", file=sys.stderr)

    # per-query phase decomposition (dispatch counts, kernel/compile/
    # upload/host ms) written where BENCH_PHASES_PATH says: a losing
    # query's time is attributable without a rerun
    side = os.environ.get("BENCH_PHASES_PATH")
    if side:
        with open(side, "w") as f:
            json.dump({"sf": sf, "backend": device["platform"],
                       "device": device, "phases": phases}, f,
                      indent=1, sort_keys=True)
    # vs_baseline is 0 when every host baseline was skipped
    # (BENCH_CPU_BUDGET<0 spends the whole run on the device path)
    geo = math.exp(sum(math.log(s) for s in speedups)
                   / len(speedups)) if speedups else 0.0
    if "q6" in dev_times:
        hq = "q6"
    else:                    # no q6: slowest query (never inflates)
        hq = max(dev_times, key=dev_times.get)
    print(json.dumps({
        "metric": f"tpch_sf{sf}_scan_agg_throughput",
        "value": round(n_rows / dev_times[hq], 1),
        "unit": _unit(f"rows/s/chip ({hq} full-stack, {len(speedups)}q "
                      "geomean)", device),
        "vs_baseline": round(geo, 3),
        "backend": device["platform"],
        "device": device,
        "load_s": round(load_s, 1),
        "peak_rss_gb": peak_rss_gb(),
        "queries": per_query,
    }))


if __name__ == "__main__":
    main()
