"""What the folded dimensions (copr/dimfold.py) change in the join
statements' device programs: a reading, on the chip, of statement times
and of the compiled HLO's `while` loops.

    python benchmarks/fold_probe_tpu.py --scale 1 --seed 28 \
        --time q5,q3,q10,q18 --hlo q18 --variants control,mask,fold,packed

The data set, its loader and the statements are the benchmark's
(benchmark/datasets/tpch.py, or with `--dataset tpch_set2` the second
set's Q4, Q9, Q12, Q13, Q17, Q19; loaded by path and not edited); the
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

`--hlo q` writes the compiled HLO text of q's programs (those with
dimensions; all of them for a statement that has none, q1 and q6) to
chiprun_out/hlo_<q>_<variant>_sf<scale>[_x<devices>]_<i>.txt and
prints of each its sha256 (and that of the text less its source
locations, which is what two trees' programs are compared by), its ` gather(` instructions counted by element type and width
(an s64 table gathered at fact width shows as two u32 gathers: XLA:TPU
splits it), and every `while` in it: name, `op_name` metadata (the
`jax.named_scope` stage), known trip count, carried shapes. Each
composed word is printed with the bits it uses and the type that holds
it when it is built. With more than one device the mesh programs
(`jit_tidb_mpp_fused_*`) are what the statements build, and what is
written. Off the chip (JAX_PLATFORMS=cpu) the script runs for rehearsal
and says so; its times then mean nothing. `--aot v5e:2x2` is for there:
it forces the chip's lowering policy ("runs") and compiles each program
for that described topology with the TPU's compiler instead of for the
backend that ran it (one device of it, or a mesh of as many as ran): the
texts and their counts are the chip's compiler's, nothing is timed.
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


def _dataset(name):
    path = os.path.join(ROOT, "benchmark", "datasets", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"fold_probe_{name}",
                                                  path)
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


def _gathers(text):
    """{"u32[4194304]": count} of an HLO module's ` gather(`
    instructions by result element type and shape."""
    out = {}
    for m in re.finditer(r"= (\w+\[[\d,]*\])\S* gather\(", text):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return dict(sorted(out.items()))


def _program_only(text):
    """An HLO module's text less what names the source: the FileNames /
    FunctionNames / FileLocations / StackFrames tables under its first
    line and every instruction's `stack_frame_id`. Two trees whose
    Python moved by a line compile the same program to texts that
    differ there and nowhere else."""
    head, _, rest = text.partition("\n\nFileNames\n")
    if rest:
        tables, _, body = rest.partition("\n\n\n")
        if not body:            # no blank pair: cut at the first block
            m = re.search(r"\n\n(?=[%\w].* \{\n|ENTRY )", rest)
            body = rest[m.end():] if m else rest
        text = head + "\n\n" + body
    return re.sub(r",? ?stack_frame_id=\d+", "", text)


def _used_bits(packed):
    """[(bits used, type)] a word of a dimfold.Packed, from the fields'
    shifts and masks (a mask of -1: a 64-bit field, as it is)."""
    used = [0] * len(packed.tables)
    for (_k, _ident, wi, _dt), sh, mk in zip(packed.text, packed.shift,
                                             packed.mask):
        used[wi] = max(used[wi], int(sh) + (64 if int(mk) == -1
                                            else int(mk).bit_length()))
    return [(u, t.dtype.name) for u, t in zip(used, packed.tables)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--time", default="")
    ap.add_argument("--hlo", default="")
    ap.add_argument("--variants", default="control,fold")
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--aot", default="")
    ap.add_argument("--dataset", default="tpch",
                    choices=("tpch", "tpch_set2"))
    args = ap.parse_args()
    timed = [q for q in args.time.split(",") if q]
    hlo = [q for q in args.hlo.split(",") if q]

    import hashlib
    import jax
    import numpy as np
    import tidb_tpu.copr.agg_lowering as al
    import tidb_tpu.copr.dimfold as df
    import tidb_tpu.copr.pipeline as pl
    from tidb_tpu.session import new_store
    from tidb_tpu.testkit import TestKit
    from tidb_tpu.utils.metrics import AGG_LOWERING
    dev = jax.devices()[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}"
        + ("" if dev.platform == "tpu" else
           " -- NOT a chip: a rehearsal, its times mean nothing"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    # the files of a mesh run beside a one-chip run's
    tag = f"sf{args.scale:g}" + (f"_x{len(jax.devices())}"
                                 if len(jax.devices()) > 1 else "")
    topo = None
    if args.aot:
        if dev.platform == "tpu":
            sys.exit("--aot is for a run off the chip")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=args.aot)
        al._FORCE_SEGMENT_IMPL = "runs"
        # a TPU executable cannot be read back here: keep it out of
        # the CPU run's cache
        jax.config.update("jax_enable_compilation_cache", False)
        log(f"--aot: the chip's lowering policy forced, programs compiled "
            f"for {args.aot} ({topo.devices[0].device_kind}), nothing run "
            "there")

    ds = _dataset(args.dataset)
    tk = TestKit(new_store(tempfile.mkdtemp(prefix="fold_probe_")))
    tables = ds.generate(args.scale, args.seed)
    log("data generated")
    dom = tk.domain

    def bulk_table(name):
        return dom.columnar.table(
            dom.infoschema().table_by_name("test", name))
    ds.load(tables, tk.must_exec, bulk_table)
    log("data loaded")

    # every fused kernel built, one chip's or the mesh's, with the
    # shapes (and shardings) of its first call
    built = []

    def spy_on(name, family):
        orig_build = getattr(pl, name)

        def spy(*a, **k):
            kern = orig_build(*a, **k)
            # guard_donation wraps the jitted program where it donates
            rec = {"plan": a[0], "kind": a[7], "shapes": None,
                   "family": family, "build": (orig_build, a, k),
                   "jit": kern if hasattr(kern, "lower")
                   else kern.__wrapped__}
            built.append(rec)

            def call(fjc, fvv, kargs):
                if rec["shapes"] is None:
                    rec["shapes"] = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            np.shape(x), np.asarray(x).dtype,
                            sharding=getattr(x, "sharding", None)),
                        (fjc, fvv, kargs))
                return kern(fjc, fvv, kargs)
            return call
        setattr(pl, name, spy)
    spy_on("_build_fused_kernel", "fused_")
    spy_on("_build_fused_kernel_mpp", "mpp_fused_")

    def compiled_text(rec):
        """The program's compiled text: for the backend it ran on, or
        (--aot) for the described topology."""
        if topo is None:
            return rec["jit"].lower(*rec["shapes"]).compile().as_text()
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                                  SingleDeviceSharding)
        orig_build, a, k = rec["build"]
        if rec["family"] == "fused_":
            jit = orig_build(*a, **k)
            jit = jit if hasattr(jit, "lower") else jit.__wrapped__

            def place(x):
                return SingleDeviceSharding(topo.devices[0])
        else:
            ran = a[9]
            mesh = Mesh(np.array(topo.devices[:ran.devices.size]),
                        ran.axis_names)
            jit = orig_build(*a[:9], mesh, *a[10:], **k)

            def place(x):
                spec = getattr(x.sharding, "spec", PartitionSpec())
                return NamedSharding(mesh, spec)
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=place(x)),
            rec["shapes"])
        return jit.lower(*shapes).compile().as_text()

    def lowering_runs():
        return {"/".join((lb["site"], lb["kind"], lb["verdict"])): int(v)
                for _n, lb, v in AGG_LOWERING.sample_rows()}

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
            f"{(time.perf_counter() - t) * 1e3:.1f} ms; bits used a word: "
            + ", ".join(f"{u} ({dt})" for u, dt in _used_bits(res)))
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
              "whiles": {}, "gathers": {}, "sha256": {}, "lowering": {},
              "compiled_for": args.aot or f"{dev.platform} {dev.device_kind}"}
    for variant in args.variants.split(","):
        df.fold_plan = variants[variant]
        dom.copr._kernel_cache.clear()
        log(f"variant {variant}")
        for q in dict.fromkeys(timed + hlo):
            if variant == "mask" and q != "q5":
                continue            # the kill criterion is q5's
            del built[:]
            sql = ds.STATEMENTS[q]
            judged = [lowering_runs()]
            t = time.perf_counter()
            tk.must_query(sql).rows
            log(f"  {q} first run {time.perf_counter() - t:.1f} s")
            for _ in range(2):
                judged.append(lowering_runs())
                tk.must_query(sql).rows
            judged.append(lowering_runs())
            # what tidb_tpu_agg_lowering_total grew by, a run: the
            # third is a steady window's
            grew = [{k: v - a.get(k, 0) for k, v in b.items()
                     if v != a.get(k, 0)}
                    for a, b in zip(judged, judged[1:])]
            result["lowering"][f"{q}.{variant}"] = grew
            log(f"  {q} judged runs, first / second / third: {grew}")
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
                recs = [r for r in built if r["shapes"] is not None]
                if any(r["plan"].dims for r in recs):
                    recs = [r for r in recs if r["plan"].dims]
                for i, rec in enumerate(recs):
                    text = compiled_text(rec)
                    name = f"hlo_{q}_{variant}_{tag}_{i}.txt"
                    with open(os.path.join(out_dir, name), "w") as f:
                        f.write(text)
                    ws, gs = _whiles(text), _gathers(text)
                    sha = hashlib.sha256(text.encode()).hexdigest()
                    prog = hashlib.sha256(
                        _program_only(text).encode()).hexdigest()
                    result["whiles"][f"{q}.{variant}.{i}"] = ws
                    result["gathers"][f"{q}.{variant}.{i}"] = gs
                    result["sha256"][f"{q}.{variant}.{i}"] = (sha, prog)
                    log(f"  {q} {variant} program {i} "
                        f"(jit_tidb_{rec['family']}{rec['kind']}, "
                        f"{rec['shapes'][1].shape[0]} lanes): "
                        f"{len(text)} bytes, sha256 {sha[:16]}, less "
                        f"source locations {prog[:16]}, "
                        f"{len(ws)} while loop(s), text in "
                        f"chiprun_out/{name}")
                    log(f"    {sum(gs.values())} gather(s): "
                        + (", ".join(f"{n} x {t}" for t, n in gs.items())
                           or "none"))
                    for w in ws:
                        log(f"    {w[0]}: op_name={w[1]} trip={w[2]} "
                            f"carries {w[3][:300]}")
    stats = dev.memory_stats() or {}
    result["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    with open(os.path.join(
            out_dir, f"fold_probe_{tag}.json"), "w") as f:
        json.dump(result, f, indent=1)
    log(f"done; HBM peak {result['memory_peak_bytes']}")
    dom.timer.stop_all()
    dom.close()


if __name__ == "__main__":
    main()
