"""Records the small four-chip trace kept beside the check of the mesh
cell's readers (`mesh_trace_4chip.xplane.pb.gz`: four device planes are
1.7 MB raw, 0.25 MB gzipped, and the test unpacks it): the system started as
the harness starts it on one mesh of four chips, TPC-H at scale 0.01 (a
60,000-row lineitem, `tidb_mpp_min_rows` 0 since that is under the
default), then q6, q1, q5, q3 served over the wire by the harness's own
client inside a profiler session set up as the harness's is: the window
span, the clock probes, the client's `stmt:` spans, the program's
`tidb:` segments (`tidb:mpp_dispatch` among them) and four device
planes with the mesh programs' collectives. Run on the chips:
`chiprun --chips 4 -- python3 benchmark/tests/record_mesh_trace.py`;
the trace comes back as
chiprun_out/mesh_trace/mesh_trace_4chip.xplane.pb and .pb.gz. It also prints what
the three trace readers make of it, the numbers
`test_mesh_readers.py` pins."""
import glob
import gzip
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
    jax = run.require_chips(4)
    from traffic import Client
    from wire import Wire
    dataset = run.load_module("datasets", "tpch", "data set")
    out = os.path.join(run.ROOT, "chiprun_out", "mesh_trace")
    data_dir = os.path.join(run.ROOT, ".cache", "bench", "mesh_trace")
    for d in (out, data_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    system = run.System(data_dir)
    try:
        admin = Wire(system.port)
        tables = dataset.generate(0.01, 3_100_000_001)
        dataset.load(tables, admin.query, system.bulk_table)
        admin.query("set global tidb_mpp_min_rows = 0")
        spec = {"name": "stream", "kind": "query_stream", "order": "fixed",
                "statements": ["q6", "q1", "q5", "q3"]}
        client = Client(spec, system.port, dataset, 3_100_000_001, True)
        client.deadline = float("inf")
        for _ in range(3):                  # every program built
            client.one_pass()
        client.records.clear()
        client.deadline = 0.0               # one pass, then stop
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
        for r in client.records:
            print(f"{r.name}: {(r.t_done - r.t_send) * 1e3:.2f} ms "
                  f"{len(r.rows)} rows wrong="
                  f"{dataset.answer_wrong(r.rows, dataset.reference(tables, r.name))}",
                  file=sys.stderr)
        admin.close()
        client.close()
    finally:
        system.close()
    kept = os.path.join(out, "mesh_trace_4chip.xplane.pb")
    shutil.copy(pb, kept)
    with open(kept, "rb") as raw, gzip.GzipFile(
            kept + ".gz", "wb", compresslevel=9, mtime=0) as packed:
        shutil.copyfileobj(raw, packed)
    print("trace", kept, os.path.getsize(kept), "bytes,",
          os.path.getsize(kept + ".gz"), "gzipped", file=sys.stderr)
    import trace_reduce
    reduced = trace_reduce.reduce(kept)
    if reduced is None:
        raise SystemExit("the trace holds no device plane or no window")
    t = reduced["trace"]
    print("devices", {n: len(ops) for n, ops in t["devices"].items()},
          "offset_ns", reduced["offset_ns"], "window_s",
          reduced["window_s"], file=sys.stderr)
    print("busy_s_by_device", reduced["busy_s_by_device"], file=sys.stderr)
    print("ops", sorted({trace_reduce.short(n) for ops in
                         t["devices"].values() for n, _, _ in ops}),
          file=sys.stderr)
    print("modules", sorted({n for mods in t["modules"].values()
                             for n, _, _ in mods}), file=sys.stderr)
    print("host spans", sorted({n for n, _, _ in t["host"]}),
          file=sys.stderr)
    run_ = {"trace": reduced}
    for name in ("collective_share", "shard_busy_skew",
                 "mesh_host_ms_per_query"):
        reader = run.load_module("layer_metrics", name, "per-layer metric")
        print(name, repr(reader.read(run_)), file=sys.stderr)


if __name__ == "__main__":
    main()
