"""Records the small trace kept beside the check of the `tidb:` segment
readers (`span_trace_1chip.xplane.pb`): the system started as the harness
starts it, TPC-H at scale 0.01 (a 60,000-row lineitem) loaded through
the harness's own path, then q6, q1, q6, q1 served over the wire by the
harness's own client inside a profiler session set up as the harness's
is, so the trace has the window span, the clock probes, the client's
`stmt:` spans, the program's `tidb:` segments and the device's planes.
Run on the chip: `chiprun -- python3
benchmark/tests/record_span_trace.py`; the trace comes back as chiprun_out/span_trace/span_trace_1chip.xplane.pb. It also
prints what `tools/trace_names.py` finds there: the programs' names and,
for PERF.md section 7, where the raw trace carries the `jax.named_scope`
stage names of the fused programs."""
import glob
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
    from traffic import Client
    from wire import Wire
    dataset = run.load_module("datasets", "tpch", "data set")
    out = os.path.join(run.ROOT, "chiprun_out", "span_trace")
    data_dir = os.path.join(run.ROOT, ".cache", "bench", "span_trace")
    for d in (out, data_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    system = run.System(data_dir)
    try:
        admin = Wire(system.port)
        tables = dataset.generate(0.01, 2_500_000_001)
        dataset.load(tables, admin.query, system.bulk_table)
        spec = {"name": "stream", "kind": "query_stream", "order": "fixed",
                "statements": ["q6", "q1", "q6", "q1"]}
        client = Client(spec, system.port, dataset, 2_500_000_001, True)
        client.deadline = float("inf")
        for _ in range(3):                  # every program built
            client.one_pass()
        client.records.clear()
        client.deadline = 0.0               # one pass, then stop
        # the harness's tracer builds the probe program; its trace()
        # sleeps through a window that clients fill, and here the
        # window is the one pass, so the session is driven by hand
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
                  f"{len(r.rows)} rows", file=sys.stderr)
        admin.close()
        client.close()
    finally:
        system.close()
    kept = os.path.join(out, "span_trace_1chip.xplane.pb")
    shutil.copy(pb, kept)
    print("trace", kept, os.path.getsize(kept), "bytes", file=sys.stderr)
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    import trace_names
    for fam, seconds, runs in trace_names.module_seconds(kept):
        print(f"module {fam}: {seconds:.6f} s in {runs} runs",
              file=sys.stderr)
    trace_names.where_scopes_are(kept, sys.stderr)
    import trace_reduce
    trace = trace_reduce.load(kept)
    print("host events", trace["host"], file=sys.stderr)
    print("modules", sorted({n for mods in trace["modules"].values()
                             for n, _, _ in mods}), file=sys.stderr)


if __name__ == "__main__":
    main()
