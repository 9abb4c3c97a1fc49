#!/usr/bin/env python3
"""The benchmark's one command:

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches jax. It starts the database as
`python -m tidb_tpu --serve --data-dir <dir>` does (store, background
workers, the MySQL wire server on a free port), makes the cell's data
from the seed and loads it through the bulk-load entry, warms the
cell's own statements, then drives the cell's traffic over the wire from
raw-socket clients on their own threads for `--seconds`. The last
stdout line is the result. Without the cell's chips it fails.

Everything that belongs to one cell is found by the name in
BENCHMARK.json: `configs/<config>.json`, `traffic/<mix>.json`,
`datasets/<data set>.py`, `layer_metrics/<metric>.py`. An unknown name
is refused, never defaulted.
"""
import time
T_PROCESS = time.time()

import argparse                                     # noqa: E402
import faulthandler                                 # noqa: E402
import glob                                         # noqa: E402
import importlib.util                               # noqa: E402
import json                                         # noqa: E402
import math                                         # noqa: E402
import os                                           # noqa: E402
import re                                           # noqa: E402
import shutil                                       # noqa: E402
import sys                                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

TRACE_SECONDS = 8.0        # the traced part of a --trace 1 window
CLOCK_PROBES = 5


def log(msg):
    print(f"benchmark [{time.time() - T_PROCESS:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def die(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def load_json(path, what):
    if not os.path.isfile(path):
        die(f"unknown {what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(package, name, what):
    """The module benchmark/<package>/<name>.py, loaded by its path (an
    installed package may be called `datasets` too)."""
    path = os.path.join(HERE, package, f"{name}.py")
    full = f"benchmark_{package}_{name}"
    if full in sys.modules:
        return sys.modules[full]
    if not re.fullmatch(r"[A-Za-z0-9_.\-]+", name) or \
            not os.path.isfile(path):
        die(f"unknown {what} {name!r}: no file "
            f"benchmark/{package}/{name}.py")
    spec = importlib.util.spec_from_file_location(full, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[full] = module
    spec.loader.exec_module(module)
    return module


def find_cell(workload):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"), "benchmark")
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        die(f"unknown workload {workload!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]),
                       "configuration")
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"),
                        "traffic mix")
    return bench, cell, config, traffic


def metrics_of(bench, kind, cell):
    return [m for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def require_chips(chips):
    """The cell's chips or nothing: -> (jax, device description)."""
    plat = os.environ.get("JAX_PLATFORMS", "")
    if not plat:
        os.environ["JAX_PLATFORMS"] = "tpu"
    elif plat.lower().split(",")[0].strip() != "tpu":
        die(f"JAX_PLATFORMS={plat!r}: the benchmark runs on a TPU only")
    import jax
    if jax.default_backend() != "tpu":
        die(f"jax.default_backend() is {jax.default_backend()!r}, not 'tpu'")
    if len(jax.devices()) != chips:
        die(f"the cell is of {chips} chip(s), jax finds "
            f"{len(jax.devices())}: a mesh of another size is another "
            "configuration")
    return jax


def describe(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def quantile(values, q):
    """The q-quantile by linear interpolation between order statistics."""
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class System:
    """The system under test, started as `--serve --data-dir` starts it."""

    def __init__(self, data_dir):
        from tidb_tpu.session import new_store
        from tidb_tpu.server import Server
        self.domain = new_store(data_dir)
        self.domain.start_background()
        self.server = Server(self.domain, port=0).start()
        self.port = self.server.port

    def bulk_table(self, name):
        tbl = self.domain.infoschema().table_by_name("test", name)
        return self.domain.columnar.table(tbl)

    def close(self):
        self.server.shutdown()
        self.domain.timer.stop_all()
        self.domain.close()


def span_seconds(records, t0):
    """A closed loop's own window: from the window's start to the answer
    to the last request it sent before the deadline. A rate over it is
    not quantised by whether the last request ends just inside the
    deadline or just outside."""
    return max(r.t_done for r in records) - t0


def end_to_end(bench, cell, queries, t0, setup_s):
    """The cell's end-to-end metrics from the window's records: all the
    work and all the time of the window."""
    q_ms = [(q.t_done - q.t_send) * 1e3 for q in queries]
    values = {
        "setup_s": setup_s,
        "query_rate": len(q_ms) / span_seconds(queries, t0) * 3600.0
        if q_ms else None,
        "query_geomean_ms": math.exp(sum(math.log(x) for x in q_ms) /
                                     len(q_ms)) if q_ms else None}
    out = {}
    for m in metrics_of(bench, "end_to_end", cell):
        if m["name"] not in values:
            die(f"end-to-end metric {m['name']!r} has no computation")
        if values[m["name"]] is None:
            die(f"the window finished nothing that {m['name']} counts")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def log_latencies(records):
    by = {}
    for r in records:
        by.setdefault(r.name, []).append((r.t_done - r.t_send) * 1e3)
    for name, ms in sorted(by.items()):
        log(f"window {name}: n={len(ms)} median={quantile(ms, 0.5):.1f}ms "
            f"p95={quantile(ms, 0.95):.1f}ms max={max(ms):.1f}ms")


def per_layer(bench, cell, run):
    out = {}
    for m in metrics_of(bench, "per_layer", cell):
        reader = load_module("layer_metrics", m["name"], "per-layer metric")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Tracer:
    """Traces a part of the running window from the main thread. Probes
    of one small program, built during set-up, bound the device clock's
    offset from the host's (see trace_reduce.clock_offset_ns)."""

    def __init__(self, jax, trace_dir):
        import jax.numpy as jnp
        self.jax, self.dir = jax, trace_dir
        shutil.rmtree(trace_dir, ignore_errors=True)

        def bench_clock_probe(x):
            return x + 1
        self.probe = jax.jit(bench_clock_probe)
        self.x = jnp.zeros((8, 128), jnp.int32)
        self.probe(self.x).block_until_ready()

    def trace(self, seconds):
        """-> the xplane file, or None."""
        prof = self.jax.profiler
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        prof.start_trace(self.dir, profiler_options=opts)
        with prof.TraceAnnotation("bench:traced_window"):
            for _ in range(CLOCK_PROBES):
                with prof.TraceAnnotation("bench:clock_probe"):
                    self.probe(self.x).block_until_ready()
                time.sleep(0.05)
            time.sleep(seconds)
        prof.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None


def run_cell(workload, seed, seconds, trace, need_chips=True, scale=None,
             shape_seed=None, client_wrapper=None, keep=None):
    """-> the result line's dict. `need_chips=False`, `scale`,
    `shape_seed` (sizes drawn from another seed than the data set's
    constant), `client_wrapper` (breaks the timed path) and `keep` (a
    dict that receives what the comparison compared, for the controls)
    are for the rehearsal and the tools and tests: the command line
    never sets them."""
    bench, cell, config, traffic = find_cell(workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".cache", "jax"))
    # every program of the cell is worth keeping: only a cell's first
    # run in a checkout may compile
    os.environ.setdefault("TIDB_TPU_JAX_CACHE_MIN_COMPILE_SECS", "0")
    if need_chips:
        jax = require_chips(cell["chips"])
    else:
        import jax
    sys.path.insert(0, ROOT)
    import checks
    import counters
    from traffic import Client
    from wire import Wire
    dataset = load_module("datasets", config["dataset"], "data set")
    device = describe(jax)
    log(f"device {device}")

    data_dir = os.path.join(ROOT, ".cache", "bench", workload, "data")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    system = System(data_dir)
    clients = []
    try:
        admin = Wire(system.port)
        shape = {} if shape_seed is None else {"shape_seed": shape_seed}
        tables = dataset.generate(config["scale_factor"] if scale is None
                                  else scale, seed, **shape)
        log("data generated")
        dataset.load(tables, admin.query, system.bulk_table)
        log("data loaded")
        clients = [Client(c, system.port, dataset, seed, trace)
                   for c in traffic["clients"]]
        if client_wrapper:
            client_wrapper(clients)

        # warm the cell's own statements, nothing else: whole passes
        # until two in a row build no program, since the program builds
        # further kernels on a statement's second and third run. Every
        # warm statement is followed by SHOW WARNINGS (see traffic.py)
        for c in clients:
            c.deadline, c.ask_warnings = float("inf"), True
        builds, clean = counters.builds(admin), 0
        for n in range(traffic["warm_rounds_max"]):
            for c in clients:       # a crash names its statement
                c.announce = (lambda name: log(f"first run of {name}")) \
                    if n == 0 else None
                c.one_pass()
            was, builds = builds, counters.builds(admin)
            log(f"warm round {n + 1}: {builds - was:g} programs built "
                f"(persistent cache {counters.xla_cache(admin)})")
            clean = clean + 1 if builds == was else 0
            if clean >= 2 and n + 1 >= traffic["warm_rounds_min"]:
                break
        else:
            log("warm-up: the last round still built programs")
        warm_bad = [r for c in clients for r in c.records
                    if r.error or r.warnings]
        if warm_bad:
            die(f"warm-up statement failed: {warm_bad[0].name}: "
                f"{warm_bad[0].error or warm_bad[0].warnings}")
        for c in clients:
            c.records.clear()
            c.ask_warnings = False
        tracer = Tracer(jax, os.path.join(
            ROOT, ".cache", "bench", workload, "trace")) if trace else None
        before = counters.snapshot(admin)

        # the window
        t0 = time.perf_counter()
        setup_s = time.time() - T_PROCESS
        for c in clients:
            c.t0, c.deadline = t0, t0 + seconds
            c.start()
        trace_file = None
        if trace:
            time.sleep(min(1.0, seconds / 4))
            trace_file = tracer.trace(min(TRACE_SECONDS, seconds / 2))
        for c in clients:
            c.join()
        log("window closed")
        crashed = [c for c in clients if c.crash is not None]
        if crashed:
            die(f"client {crashed[0].spec['name']} died: "
                f"{crashed[0].crash!r}")
        after = counters.snapshot(admin)
        peak = memory_peak(jax)
        queries = [r for c in clients for r in c.records]
        admin.close()
    finally:
        for c in clients:
            c.close()
        system.close()
    log("system closed")

    # the window is closed, the peak read, the program's state freed:
    # now the reference
    growth = counters.Growth(before, after)
    log("the window built "
        f"{growth.top_sql('kernel_builds', counters.is_any):g} programs "
        f"(persistent cache {growth.metric_by_label('tidb_tpu_xla_cache_total')})")
    cmp = checks.compare(dataset, tables, queries)
    if keep is not None:
        keep.update(dataset=dataset, tables=tables, queries=queries)
    for line in cmp["details"]:
        log(f"wrong answer: {line}")
    errors = sum(1 for r in queries if r.error)
    warned = sum(1 for r in queries if r.warnings)
    degrades = int(sum(growth.degrades().values()))
    failed = errors + cmp["answers_wrong"] + max(degrades, warned)
    compared = {
        "answers_wrong": [cmp["answers_wrong"], 0],
        "statement_errors": [errors, 0],
        "device_degrades": [degrades, 0],
        "warnings_9013": [warned, 0],
        "answers_compared": [cmp["answers_compared"], None]}
    correct = all(v <= lim for v, lim in compared.values()
                  if lim is not None) and cmp["answers_compared"] > 0

    # every request sent before the deadline was answered: the window is
    # all of them, and the loop's time runs to its last answer
    done = [r for r in queries if r.error is None]
    log_latencies(done)
    result = {"correct": bool(correct), "attempted": len(queries),
              "failed": int(failed)}
    device["memory_peak_bytes"] = peak
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce(trace_file) if trace_file else None
        run = {"traffic": traffic, "growth": growth, "trace": reduced,
               "device": device, "tables": tables, "dataset": dataset,
               "peaks": load_json(os.path.join(HERE, "peaks.json"),
                                  "peaks table")}
        result["metrics"] = per_layer(bench, cell, run)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": [list(x) for x in reduced["device_ops"][:10]],
                "idle_gaps": [list(x) for x in reduced["idle_gaps"][:10]]}
    else:
        result["metrics"] = end_to_end(bench, cell, done, t0, setup_s)
    result["device"] = device
    result["compared"] = compared
    log("compared with the reference")
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value}" +
              ("" if limit is None else f" (limit {limit})"),
              file=sys.stderr)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    faulthandler.enable()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
