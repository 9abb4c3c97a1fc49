"""What the folded dimensions (copr/dimfold.py) change in the join
statements' device programs: a reading, on the chip, of statement times
and of the compiled HLO's `while` loops.

    python benchmarks/fold_probe_tpu.py --scale 1 --seed 28 \
        --time q5,q3,q10,q18 --hlo q18 --variants control,mask,fold,packed

The data set, its loader and the statements are the benchmark's
(benchmark/datasets/tpch.py, loaded by path and not edited); the
statements run in-process on one session, so a time here is the
statement's and not the wire's. Variants:

- `control`: no dimension folds (the program of the commit before);
- `mask`:    only the `valid[pos]` gathers of the unfiltered inner
             dimensions go (ISSUE 28's kill criterion on q5);
- `fold`:    the plan's own fold;
- `packed`:  the same, under ISSUE 30's name: since PR 30 a root's
             payload rides the words its probe table holds (`mask`'s
             roots too) and PR 28's root-width columns are gone. Its
             kill criterion (q5 502.8 -> 212.0 ms at SF1) was read in
             one call before they went; to read it again run PR 29's
             tree's `fold` and this in one call.

`--hlo q` writes the compiled HLO text of q's program with dimensions to
chiprun_out/hlo_<q>_<variant>_sf<scale>.txt and prints every `while`
in it: name, `op_name` metadata (the `jax.named_scope` stage), known
trip count, carried shapes. Off the chip (JAX_PLATFORMS=cpu) the
script runs for rehearsal and says so; its times then mean nothing.
"""
import argparse
import importlib.util
import json
import os
import re
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
    spec = importlib.util.spec_from_file_location("fold_probe_tpch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _whiles(text):
    """[(name, op_name, trip count or None, carried shapes)] of every
    `while` instruction of an HLO module's text."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(while[\w.]*) = (\(.*?\)) while\(",
                     line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        trip = re.search(r'known_trip_count[^0-9]*(\d+)', line)
        out.append((m.group(1), op.group(1) if op else None,
                    int(trip.group(1)) if trip else None, m.group(2)))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--time", default="")
    ap.add_argument("--hlo", default="")
    ap.add_argument("--variants", default="control,fold")
    ap.add_argument("--runs", type=int, default=5)
    args = ap.parse_args()
    timed = [q for q in args.time.split(",") if q]
    hlo = [q for q in args.hlo.split(",") if q]

    import jax
    import numpy as np
    import tidb_tpu.copr.dimfold as df
    import tidb_tpu.copr.pipeline as pl
    from tidb_tpu.session import new_store
    from tidb_tpu.testkit import TestKit
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}"
        + ("" if dev.platform == "tpu" else
           " -- NOT a chip: a rehearsal, its times mean nothing"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    ds = _dataset()
    tk = TestKit(new_store(tempfile.mkdtemp(prefix="fold_probe_")))
    tables = ds.generate(args.scale, args.seed)
    log("data generated")
    dom = tk.domain

    def bulk_table(name):
        return dom.columnar.table(
            dom.infoschema().table_by_name("test", name))
    ds.load(tables, tk.must_exec, bulk_table)
    log("data loaded")

    # every fused kernel built, with the shapes of its first call
    built = []
    orig_build = pl._build_fused_kernel

    def spy(*a, **k):
        kern = orig_build(*a, **k)
        # guard_donation wraps the jitted program where it donates
        rec = {"plan": a[0], "kind": a[7], "shapes": None,
               "jit": kern if hasattr(kern, "lower") else kern.__wrapped__}
        built.append(rec)

        def call(fjc, fvv, kargs):
            if rec["shapes"] is None:
                rec["shapes"] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                                   np.asarray(x).dtype),
                    (fjc, fvv, kargs))
            return kern(fjc, fvv, kargs)
        return call
    pl._build_fused_kernel = spy

    orig_fold_build = df._build

    def timed_build(fp, plan, metas, root):
        t = time.perf_counter()
        res = orig_fold_build(fp, plan, metas, root)
        log(f"  fold built over {plan.dims[root].dag.table_info.name} "
            f"({metas[root]['n']} rows, table {len(res[0])} slots, "
            f"{len(res[1])} position columns): "
            f"{(time.perf_counter() - t) * 1e3:.1f} ms")
        return res
    df._build = timed_build

    orig_compose = df.Fold._compose

    def timed_compose(self, fields):
        t = time.perf_counter()
        res = orig_compose(self, fields)
        log(f"  {len(fields)} field(s) composed over dimension {self.root}: "
            f"{len(res.tables)} word(s) of {len(res.tables[0])} "
            f"slots, {res.nbytes} bytes: "
            f"{(time.perf_counter() - t) * 1e3:.1f} ms")
        return res
    df.Fold._compose = timed_compose

    real = df.fold_plan

    def control(plan):
        return df.FoldPlan(len(plan.dims))

    def mask_only(plan):
        fp, full = df.FoldPlan(len(plan.dims)), real(plan)
        fp.masked = [(full.masked[i] or full.parent[i] is not None) and
                     d.join_type == "inner" and not d.dag.filters
                     for i, d in enumerate(plan.dims)]
        return fp
    variants = {"control": control, "mask": mask_only, "fold": real,
                "packed": real}

    result = {"scale": args.scale, "seed": args.seed,
              "device": f"{dev.platform} {dev.device_kind}", "ms": {},
              "whiles": {}}
    for variant in args.variants.split(","):
        df.fold_plan = variants[variant]
        dom.copr._kernel_cache.clear()
        log(f"variant {variant}")
        for q in dict.fromkeys(timed + hlo):
            if variant == "mask" and q != "q5":
                continue            # the kill criterion is q5's
            del built[:]
            sql = ds.STATEMENTS[q]
            t = time.perf_counter()
            tk.must_query(sql).rows
            log(f"  {q} first run {time.perf_counter() - t:.1f} s")
            tk.must_query(sql).rows
            tk.must_query(sql).rows
            if q in timed:
                ms = []
                for _ in range(args.runs):
                    t = time.perf_counter()
                    tk.must_query(sql).rows
                    ms.append((time.perf_counter() - t) * 1e3)
                result["ms"][f"{q}.{variant}"] = ms
                log(f"  {q} {variant}: median "
                    f"{statistics.median(ms):.1f} ms of {args.runs}: "
                    + " ".join(f"{x:.1f}" for x in ms))
            if q in hlo:
                recs = [r for r in built
                        if r["plan"].dims and r["shapes"] is not None]
                for i, rec in enumerate(recs):
                    text = rec["jit"].lower(*rec["shapes"]).compile() \
                        .as_text()
                    name = f"hlo_{q}_{variant}_sf{args.scale:g}_{i}.txt"
                    with open(os.path.join(out_dir, name), "w") as f:
                        f.write(text)
                    ws = _whiles(text)
                    result["whiles"][f"{q}.{variant}.{i}"] = ws
                    log(f"  {q} {variant} program {i} "
                        f"(jit_tidb_fused_{rec['kind']}): "
                        f"{len(ws)} while loop(s), text in "
                        f"chiprun_out/{name}")
                    for w in ws:
                        log(f"    {w[0]}: op_name={w[1]} trip={w[2]} "
                            f"carries {w[3][:300]}")
    stats = dev.memory_stats() or {}
    result["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    with open(os.path.join(
            out_dir, f"fold_probe_sf{args.scale:g}.json"), "w") as f:
        json.dump(result, f, indent=1)
    log(f"done; HBM peak {result['memory_peak_bytes']}")
    dom.timer.stop_all()
    dom.close()


if __name__ == "__main__":
    main()
