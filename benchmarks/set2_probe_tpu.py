"""ISSUE 38, step 1: each statement of `tpch_set2` alone, cold, over
the wire, under a bound.

    chiprun --timeout 3000 -- python benchmarks/set2_probe_tpu.py \
        --sf 1 --seed 38 [--query q12,q19,q4,q9,q13,q17] [--bound 600]

One process starts the store and the wire server as `--serve` does
(`benchmark/run.py`'s `System`), loads the benchmark's data set and
sends each statement three times from the benchmark's raw-socket client.
A line a statement goes to stdout and to
`chiprun_out/set2_probe.jsonl` as soon as it is known, so that a
statement that never answers costs the ones after it and nothing
before it: seconds to the first answer, programs built by the first
run (`kernel_builds` + the persistent cache's misses), `kernel_builds`
of the second and third run, their seconds, what
`tidb_tpu_dim_fold_total`, `tidb_tpu_agg_lowering_total`,
`tidb_tpu_fused_dim_probe_total`, `tidb_tpu_matdim_total` and
`tidb_tpu_dict_filter_total` grew by, warnings 9013, the degrade
counters, whether the statement took the fused pipeline, and whether
each answer equals the data set's reference. A statement past `--bound`
seconds ends the process (exit 3) with every thread's stack on stderr.
Exit 0: every statement asked for was served `correct` with every
degrade counter at 0 and no program built by its third run. Off the
chip (JAX_PLATFORMS=cpu) it runs for rehearsal: its times mean nothing.
"""
import argparse
import faulthandler
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".cache", "jax"))
os.environ.setdefault("TIDB_TPU_JAX_CACHE_MIN_COMPILE_SECS", "0")
T0 = time.time()
GROWN = ("tidb_tpu_dim_fold_total", "tidb_tpu_agg_lowering_total",
         "tidb_tpu_fused_dim_probe_total", "tidb_tpu_matdim_total",
         "tidb_tpu_dict_filter_total", "tidb_tpu_xla_cache_total")
FLAT = ("fused_pipeline_hit", "fused_pipeline_miss", "device_fallback",
        "device_dispatch_error", "device_retry", "device_breaker_open",
        "fused_pipeline_error")


def log(msg):
    print(f"[{time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--query", default="q12,q19,q4,q9,q13,q17")
    ap.add_argument("--bound", type=int, default=600)
    ap.add_argument("--runs", type=int, default=3)
    args = ap.parse_args()
    import counters
    import run
    from wire import Wire, WireError
    import jax
    log(f"device {run.describe(jax)}")
    ds = run.load_module("datasets", "tpch_set2", "data set")
    data_dir = os.path.join(ROOT, ".cache", "bench", "set2_probe")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "set2_probe.jsonl"), "a")
    system = run.System(data_dir)
    bad = 0
    try:
        wire = Wire(system.port, timeout=args.bound + 60)
        tables = ds.generate(args.sf, args.seed)
        log("data generated")
        ds.load(tables, wire.query, system.bulk_table)
        log("data loaded")
        for name in args.query.split(","):
            want = ds.reference(tables, name)
            line = {"stmt": name, "sf": args.sf, "seed": args.seed,
                    "runs": []}
            first = counters.snapshot(wire)
            for i in range(args.runs):
                before = counters.snapshot(wire)
                built = counters.builds(wire)
                faulthandler.dump_traceback_later(args.bound, exit=True)
                t = time.perf_counter()
                try:
                    got, err = wire.rows(ds.STATEMENTS[name]), None
                except WireError as e:
                    got, err = None, str(e)
                sec = time.perf_counter() - t
                faulthandler.cancel_dump_traceback_later()
                warn = list(wire.rows("show warnings"))
                g = counters.Growth(before, counters.snapshot(wire))
                line["runs"].append({
                    "seconds": sec, "error": err,
                    "programs_built": counters.builds(wire) - built,
                    "kernel_builds": g.top_sql("kernel_builds",
                                               counters.is_query),
                    "dispatches": g.top_sql("dispatches",
                                            counters.is_query),
                    "warnings": warn,
                    "equal": got is not None and
                    not ds.answer_wrong(got, want)})
                log(f"{name} run {i + 1}: {sec:.2f} s, "
                    f"{line['runs'][-1]['programs_built']:g} programs, "
                    f"equal {line['runs'][-1]['equal']}")
            g = counters.Growth(first, counters.snapshot(wire))
            line["grown"] = {
                m: {k: v for k, v in g.metric_by_label(m).items() if v}
                for m in GROWN}
            line["flat"] = {k: g.metric(k) for k in FLAT if g.metric(k)}
            line["rows"] = len(want)
            ok = all(r["equal"] and not r["warnings"] for r in line["runs"]) \
                and not sum(g.degrades().values()) \
                and not line["runs"][-1]["programs_built"]
            line["ok"] = ok
            bad += not ok
            for f in (out, sys.stdout):
                print(json.dumps(line), file=f, flush=True)
        after = counters.snapshot(wire)
        resident = {k[1]: v for k, v in after["metrics"].items()
                    if k[0] == "tidb_tpu_device_resident_bytes" and v}
        tail = {"resident_bytes": resident,
                "memory_peak_bytes": run.memory_peak(jax)}
        for f in (out, sys.stdout):
            print(json.dumps(tail), file=f, flush=True)
        wire.close()
    finally:
        system.close()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
