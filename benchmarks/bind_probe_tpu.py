"""What a statement's `bind` spans hold on the chip machine's host:
ISSUE 35's first reading (is `bind` work proportional to the table's
rows, recomputed for an unchanged table version?) and its reading
after the change.

    python benchmarks/bind_probe_tpu.py --sf 1 --seed 35 \
        --query q6,q10 --runs 9

The data set, its loader and the statements are the benchmark's
(benchmark/datasets/tpch.py, loaded by path and not edited); the
statements run in-process on one session (on the mesh when the process
sees more than one device), so a time here is the statement's and not
the wire's. Per statement it prints medians of `--runs` executions of:
the statement; everything inside its `bind` spans (`phase.bind_span`,
what `bind_ms_per_query` sums); and, inside those, `valid_at`, the
rest of `snapshot` (a `nl.any()` a read column) for the fact table and
for the dimensions (`_dim_sort_meta`'s snapshots), the rest of
`_dim_sort_meta`, `delta.refresh` + `invalidate`, `_bind_cols`,
`_pad_upload` (on one chip the block's mask is padded and uploaded in
it; where the tree has `_mask_operand`, that call apart), the resident
puts (`_dev_put_append`, `_dev_put_sharded`), and what is left. Then
the pieces alone on the loaded fact table, medians of `--runs`:
`valid_at(None)`, `valid_at(read_ts)`, the `nl.any()` of each column
the statements read, and a row block's mask padded
(`np.concatenate`), handed to the device (`jnp.asarray`, the call and
the call waited for) and copied on the device. These are host timings
of host code on the machine that holds the chip. Off the chip
(JAX_PLATFORMS=cpu) the script runs for rehearsal and says so: its
times mean nothing.
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
    spec = importlib.util.spec_from_file_location("bind_probe_tpch", path)
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


class _Clock:
    """Inclusive milliseconds by name of what ran inside a `bind` span
    of the statement under the clock, and a stack so that a part can
    take its children's time off its own."""

    def __init__(self):
        self.ms = {}
        self.calls = {}
        self.depth = 0          # open `bind` spans
        self.stack = []         # child milliseconds of the open parts

    def clear(self):
        self.ms.clear()
        self.calls.clear()

    def timed(self, name, fn, self_time=False):
        """`fn` under the clock as `name`; with `self_time`, less what
        its timed callees took."""
        clock = self

        def wrapped(*a, **kw):
            if not clock.depth:
                return fn(*a, **kw)
            key = name(*a, **kw) if callable(name) else name
            clock.stack.append(0.0)
            t = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                dt = (time.perf_counter() - t) * 1e3
                inner = clock.stack.pop()
                if clock.stack:
                    clock.stack[-1] += dt
                clock.ms[key] = clock.ms.get(key, 0.0) + \
                    (dt - inner if self_time else dt)
                clock.calls[key] = clock.calls.get(key, 0) + 1
        wrapped.__wrapped__ = fn
        return wrapped


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--query", default="q6,q10")
    ap.add_argument("--runs", type=int, default=9)
    ap.add_argument("--policy", default="",
                    help="force a lowering policy (a rehearsal on the CPU "
                    "backend: `runs` is the chip's)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import tidb_tpu.copr.agg_lowering as al
    import tidb_tpu.copr.dag_exec as de
    import tidb_tpu.copr.pipeline as pl
    from tidb_tpu.chunk.device import shape_bucket
    from tidb_tpu.copr.delta import DeltaMaintainer
    from tidb_tpu.copr.residency import DeviceResidentStore
    from tidb_tpu.session import new_store
    from tidb_tpu.storage.columnar import ColumnarTable
    from tidb_tpu.testkit import TestKit
    from tidb_tpu.utils import metrics, phase
    al._FORCE_SEGMENT_IMPL = args.policy or None
    dev = jax.devices()[0]
    ndev = len(jax.devices())
    log(f"device {dev.platform} {dev.device_kind} x{ndev}"
        + ("" if dev.platform == "tpu" else
           " -- NOT a chip: a rehearsal, its times mean nothing"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    ds = _dataset()
    tk = TestKit(new_store(tempfile.mkdtemp(prefix="bind_probe_")))
    tables = ds.generate(args.sf, args.seed)
    log("data generated")
    dom = tk.domain

    def bulk_table(name):
        return dom.columnar.table(
            dom.infoschema().table_by_name("test", name))
    ds.load(tables, tk.must_exec, bulk_table)
    log("data loaded")
    fact = bulk_table("lineitem")

    clock = _Clock()

    class timed_bind_span(phase.bind_span):
        __slots__ = ("_t",)

        def __enter__(self):
            clock.depth += 1
            self._t = time.perf_counter()
            return super().__enter__()

        def __exit__(self, *exc):
            res = super().__exit__(*exc)
            clock.depth -= 1
            if not clock.depth:
                clock.ms["bind"] = clock.ms.get("bind", 0.0) + \
                    (time.perf_counter() - self._t) * 1e3
                clock.calls["bind"] = clock.calls.get("bind", 0) + 1
            return res
    phase.bind_span = timed_bind_span

    def side(tbl):
        return "fact" if tbl is fact else "dims"
    ColumnarTable.valid_at = clock.timed(
        lambda self, *a, **kw: "valid_at." + side(self),
        ColumnarTable.valid_at)
    ColumnarTable.snapshot = clock.timed(
        lambda self, *a, **kw: "snapshot_rest." + side(self),
        ColumnarTable.snapshot, self_time=True)
    pl._dim_sort_meta = clock.timed("dim_sort_meta_rest",
                                    pl._dim_sort_meta, self_time=True)
    DeltaMaintainer.refresh = clock.timed("refresh_invalidate",
                                          DeltaMaintainer.refresh)
    DeviceResidentStore.invalidate = clock.timed(
        "refresh_invalidate", DeviceResidentStore.invalidate)
    de.CoprExecutor._bind_cols = clock.timed("bind_cols",
                                            de.CoprExecutor._bind_cols)
    de.CoprExecutor._pad_upload = clock.timed(
        "pad_upload_rest", de.CoprExecutor._pad_upload, self_time=True)
    for put in ("_dev_put", "_dev_put_append", "_dev_put_sharded",
                "_dev_put_replicated", "_mask_operand"):
        if hasattr(de.CoprExecutor, put):
            setattr(de.CoprExecutor, put, clock.timed(
                "mask_operand" if put == "_mask_operand" else
                "resident_puts", getattr(de.CoprExecutor, put)))

    def facts():
        """tidb_tpu_snapshot_facts_total, where the tree has it."""
        c = getattr(metrics, "SNAPSHOT_FACTS", None)
        return None if c is None else {
            lb["outcome"]: int(v) for _n, lb, v in c.sample_rows() if v}

    result = {"sf": args.sf, "seed": args.seed, "devices": ndev,
              "device": f"{dev.platform} {dev.device_kind}",
              "rows": int(fact.n), "runs": args.runs,
              "has_facts": facts() is not None, "queries": {}}
    for q in [q for q in args.query.split(",") if q]:
        sql = ds.STATEMENTS[q]
        t = time.perf_counter()
        tk.must_query(sql).rows
        log(f"{q} first run {time.perf_counter() - t:.1f} s")
        tk.must_query(sql).rows
        tk.must_query(sql).rows
        rec = {}
        before = facts()
        for _ in range(args.runs):
            clock.clear()
            t = time.perf_counter()
            tk.must_query(sql).rows
            rec.setdefault("stmt", []).append(
                (time.perf_counter() - t) * 1e3)
            named = sum(v for k, v in clock.ms.items() if k != "bind")
            clock.ms["bind_rest"] = clock.ms.get("bind", 0.0) - named
            for k, v in clock.ms.items():
                rec.setdefault(k, []).append(v)
        out = {k + "_ms": round(statistics.median(v), 4)
               for k, v in sorted(rec.items()) if len(v) == args.runs}
        out["calls"] = dict(sorted(clock.calls.items()))
        after = facts()
        if after is not None:
            out["facts_grown"] = {k: after[k] - before.get(k, 0)
                                  for k in after
                                  if after[k] != before.get(k, 0)}
        result["queries"][q] = out
        log(f"{q}: " + json.dumps(out))

    # the pieces alone, on the loaded fact table
    n = fact.n
    read_ts = dom.storage.oracle.get_ts()
    raw_valid = getattr(ColumnarTable.valid_at, "__wrapped__",
                        ColumnarTable.valid_at)
    pieces = {
        "valid_at_latest_ms": _median_ms(
            lambda: raw_valid(fact, None, n), args.runs),
        "valid_at_read_ts_ms": _median_ms(
            lambda: raw_valid(fact, read_ts, n), args.runs),
        "delete_ts_eq0_ms": _median_ms(
            lambda: fact.delete_ts[:n] == 0, args.runs)}
    cols = {ci.name: ci.id for ci in fact.table_info.columns}
    anys = {name: _median_ms(lambda c=cid: fact.nulls[c][:n].any(),
                             args.runs)
            for name, cid in cols.items()
            if name in ("l_quantity", "l_extendedprice", "l_discount",
                        "l_shipdate", "l_orderkey", "l_returnflag")}
    pieces["null_any_ms"] = anys
    step = dom.copr.device_rows
    m = min(step, n)
    if ndev > 1:
        m = n           # the mesh takes the table whole
    cap = shape_bucket(m)
    v = raw_valid(fact, None, n)[:m]
    pieces["block_rows"], pieces["block_cap"] = int(m), int(cap)

    def pad():
        return np.concatenate([v, np.zeros(cap - m, dtype=bool)]) \
            if len(v) != cap else v
    vv = pad()
    pieces["mask_concatenate_ms"] = _median_ms(pad, args.runs)
    if ndev == 1:
        pieces["mask_asarray_call_ms"] = _median_ms(
            lambda: jnp.asarray(vv), args.runs)
        pieces["mask_asarray_ready_ms"] = _median_ms(
            lambda: jnp.asarray(vv).block_until_ready(), args.runs)
        dv = jnp.asarray(vv)
        jnp.copy(dv).block_until_ready()
        pieces["mask_device_copy_call_ms"] = _median_ms(
            lambda: jnp.copy(dv), args.runs)
        pieces["mask_device_copy_ready_ms"] = _median_ms(
            lambda: jnp.copy(dv).block_until_ready(), args.runs)
    result["pieces"] = pieces
    log("pieces: " + json.dumps(pieces))
    name = f"bind_probe_sf{args.sf:g}_{ndev}dev.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1)
    log(f"done; chiprun_out/{name}")
    dom.timer.stop_all()
    dom.close()


if __name__ == "__main__":
    main()
