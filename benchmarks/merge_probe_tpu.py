"""What the final merge of a join statement's partials costs on the
chip machine's host, and what merging on the items that identify the
group (`PartialAggResult.ident`) takes off it: ISSUE 32's kill
criterion and its reading after the change.

    python benchmarks/merge_probe_tpu.py --scale 1 --seed 32 \
        --queries q10,q3,q18 --runs 7

The data set, its loader and the statements are the benchmark's
(benchmark/datasets/tpch.py, loaded by path and not edited); the
statements run in-process on one session (on the mesh when the process
sees more than one device), so a time here is the statement's and not
the wire's. Per statement it prints the partials that reached
`HashAggExec._merge_partials` (how many, their rows, the `ident` they
carry), and medians of: the statement; the host's tail of it (from the
moment the partials are on the host to the statement's end: merge,
projection, top-n); the merge as the tree does it; the merge with
`ident` taken off every partial (every group item: the parent's); the
`np.unique(..., axis=0)` call alone on the stacked items and a
`np.unique` of the first identifying item alone; `TopNExec._prune`;
`pipeline._decode_pos_keys` (one chip's "posruns": positions to values,
a row block). These are host timings of host code. Off the chip
(JAX_PLATFORMS=cpu) the script runs for rehearsal and says so: the
partials then have other shapes (the CPU's lowering policy, unless
`--policy runs`), and its times mean nothing.
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".cache", "jax"))
os.environ.setdefault("TIDB_TPU_JAX_CACHE_MIN_COMPILE_SECS", "0")
T0 = time.time()


def log(msg):
    print(f"[{time.time() - T0:7.1f}s] {msg}", flush=True)


def _dataset():
    path = os.path.join(ROOT, "benchmark", "datasets", "tpch.py")
    spec = importlib.util.spec_from_file_location("merge_probe_tpch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _median_ms(fn, runs):
    out = []
    for _ in range(runs):
        t = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t) * 1e3)
    return statistics.median(out)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", default="q10,q3,q18")
    ap.add_argument("--runs", type=int, default=7)
    ap.add_argument("--policy", default="",
                    help="force a lowering policy (a rehearsal on the CPU "
                    "backend: `runs` is the chip's)")
    args = ap.parse_args()

    import jax
    import numpy as np
    import tidb_tpu.copr.agg_lowering as al
    import tidb_tpu.copr.pipeline as pl
    import tidb_tpu.executor.executors as ex
    from tidb_tpu.session import new_store
    from tidb_tpu.testkit import TestKit
    al._FORCE_SEGMENT_IMPL = args.policy or None
    dev = jax.devices()[0]
    ndev = len(jax.devices())
    log(f"device {dev.platform} {dev.device_kind} x{ndev}"
        + ("" if dev.platform == "tpu" else
           " -- NOT a chip: a rehearsal, its times mean nothing"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    ds = _dataset()
    tk = TestKit(new_store(tempfile.mkdtemp(prefix="merge_probe_")))
    tables = ds.generate(args.scale, args.seed)
    log("data generated")
    dom = tk.domain

    def bulk_table(name):
        return dom.columnar.table(
            dom.infoschema().table_by_name("test", name))
    ds.load(tables, tk.must_exec, bulk_table)
    log("data loaded")

    seen = {}           # what the statement under the clock did
    merge = ex.HashAggExec._merge_partials
    prune = ex.TopNExec._prune
    partials_of = ex.FusedPipelineExec.partials
    decode = pl._decode_pos_keys    # positions -> values, a row block

    def spy_partials(self):
        res = partials_of(self)
        seen["on_host"] = time.perf_counter()
        return res

    def spy_merge(self, partials):
        t = time.perf_counter()
        res = merge(self, partials)
        if len(partials) > 1:       # not a subquery's single partial
            seen["merge_ms"] = (time.perf_counter() - t) * 1e3
            seen["merge"] = (self, partials)
        return res

    def spy_prune(self, chunk, k):
        t = time.perf_counter()
        res = prune(self, chunk, k)
        seen["prune_ms"] = seen.get("prune_ms", 0.0) + \
            (time.perf_counter() - t) * 1e3
        seen["prune_rows"] = len(chunk)
        return res

    def spy_decode(*a):
        t = time.perf_counter()
        res = decode(*a)
        seen["decode_ms"] = seen.get("decode_ms", 0.0) + \
            (time.perf_counter() - t) * 1e3
        return res
    pl._decode_pos_keys = spy_decode
    ex.FusedPipelineExec.partials = spy_partials
    ex.HashAggExec._merge_partials = spy_merge
    ex.TopNExec._prune = spy_prune

    result = {"scale": args.scale, "seed": args.seed, "devices": ndev,
              "device": f"{dev.platform} {dev.device_kind}",
              "has_ident": "ident" in al.PartialAggResult.__slots__,
              "queries": {}}
    for q in [q for q in args.queries.split(",") if q]:
        sql = ds.STATEMENTS[q]
        t = time.perf_counter()
        tk.must_query(sql).rows
        log(f"{q} first run {time.perf_counter() - t:.1f} s")
        tk.must_query(sql).rows
        tk.must_query(sql).rows
        rec = {"stmt_ms": [], "tail_ms": [], "merge_ms": [],
               "prune_ms": [], "decode_ms": []}
        for _ in range(args.runs):
            seen.clear()
            t = time.perf_counter()
            tk.must_query(sql).rows
            end = time.perf_counter()
            rec["stmt_ms"].append((end - t) * 1e3)
            rec["tail_ms"].append((end - seen["on_host"]) * 1e3
                                  if "on_host" in seen else None)
            rec["merge_ms"].append(seen.get("merge_ms"))
            rec["prune_ms"].append(seen.get("prune_ms"))
            rec["decode_ms"].append(seen.get("decode_ms"))
        out = {k: statistics.median(v) if None not in v else None
               for k, v in rec.items()}
        out["prune_rows"] = seen.get("prune_rows")
        if "merge" in seen:
            agg, partials = seen["merge"]
            live = [p for p in partials if p.ngroups > 0]
            out["partials"] = len(live)
            out["rows"] = [int(p.ngroups) for p in live]
            out["ident"] = [getattr(p, "ident", None) for p in live]
            bare = [al.PartialAggResult(
                ngroups=p.ngroups, keys=p.keys, key_nulls=p.key_nulls,
                states=p.states, key_dicts=p.key_dicts,
                state_dicts=p.state_dicts) for p in partials]
            out["merge_as_is_ms"] = _median_ms(
                lambda: merge(agg, partials), args.runs)
            out["merge_all_items_ms"] = _median_ms(
                lambda: merge(agg, bare), args.runs)
            out["groups"] = len(merge(agg, bare))
            kvecs = [np.where(
                np.concatenate([p.key_nulls[i] for p in live]),
                -(1 << 62), np.concatenate([p.keys[i] for p in live]))
                for i in range(len(live[0].keys))]
            kmat = np.stack(kvecs, axis=1)
            out["unique_axis0_ms"] = _median_ms(
                lambda: np.unique(kmat, axis=0, return_inverse=True),
                args.runs)
            ident = out["ident"][0] or (0,)
            out["unique_one_item_ms"] = _median_ms(
                lambda: np.unique(kvecs[ident[0]], return_inverse=True),
                args.runs)
            out["in_key_order"] = bool(np.all(
                kvecs[ident[0]][:-1] <= kvecs[ident[0]][1:]))
        result["queries"][q] = out
        log(f"{q}: " + json.dumps(out))
    name = f"merge_probe_sf{args.scale:g}_{ndev}dev.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1)
    log(f"done; chiprun_out/{name}")
    dom.timer.stop_all()
    dom.close()


if __name__ == "__main__":
    main()
