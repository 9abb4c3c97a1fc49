"""Records the small trace kept beside the check of the stage readers
(`stage_trace_1chip.xplane.pb`, kept gzipped: `gzip -9`) and the stage catalogue the program
served after it (`stage_trace_1chip.catalogue.json`): the system started
as the harness starts it, TPC-H at scale 0.01 loaded through the
harness's own path, then q3, q10, q6, q1 served over the wire by the
harness's own client inside a profiler session set up as the harness's
is: q3 and q10 run two programs of one family
(`jit_tidb_fused_posruns`) whose instructions share names. The catalogue
is what `information_schema.metrics_summary` holds of
`tidb_tpu_kernel_stage_ops` and `tidb_tpu_kernel_stage_catalogue_total`
at the first read after the session (that read fills it). Run on the
chip: `chiprun -- python3 benchmark/tests/record_stage_trace.py`; both
files come back under chiprun_out/stage_trace/."""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)


def main():
    import run
    sys.path.insert(0, run.ROOT)
    jax = run.require_chips(1)
    import counters
    from traffic import Client
    from wire import Wire
    dataset = run.load_module("datasets", "tpch", "data set")
    out = os.path.join(run.ROOT, "chiprun_out", "stage_trace")
    data_dir = os.path.join(run.ROOT, ".cache", "bench", "stage_trace")
    for d in (out, data_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    system = run.System(data_dir)
    try:
        admin = Wire(system.port)
        tables = dataset.generate(0.01, 2_500_000_003)
        dataset.load(tables, admin.query, system.bulk_table)
        spec = {"name": "stream", "kind": "query_stream", "order": "fixed",
                "statements": ["q3", "q10", "q6", "q1"]}
        client = Client(spec, system.port, dataset, 2_500_000_003, True)
        client.deadline = float("inf")
        for _ in range(3):                  # every program built
            client.one_pass()
        client.records.clear()
        client.deadline = 0.0               # one pass, then stop
        before = counters.snapshot(admin)["metrics"]
        tracer = run.Tracer(jax, os.path.join(data_dir, "trace"))
        prof = jax.profiler
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        prof.start_trace(tracer.dir, profiler_options=opts)
        with prof.TraceAnnotation("bench:traced_window"):
            for _ in range(run.CLOCK_PROBES):
                with prof.TraceAnnotation("bench:clock_probe"):
                    tracer.probe(tracer.x).block_until_ready()
                time.sleep(0.05)
            client.start()
            client.join()
            time.sleep(0.02)
        prof.stop_trace()
        pb = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        if client.crash is not None:
            raise client.crash
        after = counters.snapshot(admin)["metrics"]
        admin.close()
        client.close()
    finally:
        system.close()
    served = [k for k in before if "kernel_stage" in k[0]]
    print("served before the session:", served, file=sys.stderr)
    rows = sorted([name, labels, value] for (name, labels), value
                  in after.items() if "kernel_stage" in name)
    kept = os.path.join(out, "stage_trace_1chip.xplane.pb")
    shutil.copy(pb, kept)
    with open(os.path.join(out, "stage_trace_1chip.catalogue.json"),
              "w") as f:
        json.dump(rows, f, indent=0)
    print("trace", kept, os.path.getsize(kept), "bytes;", len(rows),
          "catalogue rows", file=sys.stderr)
    import kernel_stages
    import trace_reduce
    run_ = {"trace": trace_reduce.reduce(kept),
            "growth": counters.Growth({"metrics": before, "top_sql": {}},
                                      {"metrics": after, "top_sql": {}})}
    kernel_stages.log_tables(run_)
    print("busy_s", run_["trace"]["busy_s"], file=sys.stderr)


if __name__ == "__main__":
    main()
