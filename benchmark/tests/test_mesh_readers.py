"""The five readers of the mesh cell (`tpch-sf1-mesh4.power`): exact
arithmetic on a hand-built four-plane `run["trace"]` and on counter
snapshots, then the small trace recorded on four chips by
record_mesh_trace.py (one mesh of four TPU v5e chips; q6, q1, q5, q3
served over the wire from a 60,000-row lineitem). Run: python3 -m pytest
benchmark/tests/test_mesh_readers.py (needs no chip)."""
import gzip
import importlib.util
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import counters                                             # noqa: E402
import trace_reduce as tr                                   # noqa: E402

TRACE = os.path.join(HERE, "mesh_trace_4chip.xplane.pb.gz")
OFFSET = 100        # host time = device time + OFFSET
ROUTE = "tidb_tpu_mesh_route_total"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_{name}", os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def host(name, start, end):
    return (name, start + OFFSET, end + OFFSET)


def op(text, start, end):
    """An `XLA Ops` event as the profiler names it."""
    return (f"%{text} = s64[25]{{0}} {text.split('.')[0]}(...)", start, end)


def reduced_of(devices, hosts, lo=0, hi=10000):
    trace = {"devices": devices, "modules": {}, "host": sorted(
        hosts, key=lambda e: e[1])}
    per_device = {n: tr.length(iv) / 1e9
                  for n, iv in tr.busy(trace, lo, hi).items()}
    return {"trace": trace, "lo": lo, "hi": hi, "offset_ns": OFFSET,
            "window_s": (hi - lo) / 1e9, "busy_s_by_device": per_device,
            "busy_s": sum(per_device.values()) / len(per_device)}


def shard(first_end=2000, extra=()):
    """One device's operations: a fusion, the dense result's all-reduce,
    a second fusion, an asynchronous all-gather whose two halves leave a
    gap, and an all-reduce that straddles the window's end."""
    return [op("fusion.1", 1000, first_end),
            op("all-reduce.3", 2000, 2200),
            op("fusion.2", 3000, 3500),
            op("all-gather-start.1", 3500, 3600),
            op("all-gather-done.1", 3800, 3900),
            op("all-reduce.4", 9900, 10100)] + list(extra)


def hand_built(mpp_dispatch=True):
    """Window [0, 10000) on the device's clock. Device 0 also runs a
    single-chip program (`fusion.9`); device 3's first fusion is
    shorter. q6 and a q1 lie whole inside; a second q1 ends after the
    window and is not counted."""
    devices = {0: shard(extra=[op("fusion.9", 5000, 6000)]),
               1: shard(), 2: shard(), 3: shard(first_end=1800)}
    segs = [("execute", 1100, 1400), ("device_attempt", 1400, 1500),
            ("mpp_dispatch", 1500, 1600), ("bind", 1600, 1800),
            ("dispatch", 1800, 1900), ("mpp_dispatch", 1900, 2000),
            ("consume", 2000, 2600),
            ("execute", 3200, 3400), ("mpp_dispatch", 3400, 3700),
            ("fetch", 3700, 5000), ("consume", 5000, 5500),
            ("mpp_dispatch", 9100, 9300)]       # the q1 not counted
    if not mpp_dispatch:
        segs = [("device_attempt" if n == "mpp_dispatch" else n, s, e)
                for n, s, e in segs]
    hosts = [host("bench:traced_window", 0, 10000),
             host("stmt:q6", 1000, 3000), host("stmt:q1", 3100, 6000),
             host("stmt:q1", 9000, 11000)] + \
        [host(f"tidb:{n}", s, e) for n, s, e in segs]
    return {"trace": reduced_of(devices, hosts)}


def test_collective_share_is_of_the_busiest_device():
    run = hand_built()
    busy = run["trace"]["busy_s_by_device"]
    # fusions 1000 + 500 (+ 1000 on device 0), collectives 200 + 100 +
    # 100 and the 100 of the straddling all-reduce inside the window
    assert busy == {0: 3000 / 1e9, 1: 2000 / 1e9, 2: 2000 / 1e9,
                    3: 1800 / 1e9}
    assert reader("collective_share")(run) == \
        pytest.approx(100.0 * 500 / 3000)


def test_shard_busy_skew():
    assert reader("shard_busy_skew")(hand_built()) == \
        pytest.approx(100.0 * (3000 - 1800) / 3000)


def test_mesh_host_ms_is_the_route_spans_self_time():
    # 100 + 100 inside q6, 300 inside q1, over the two counted statements
    assert reader("mesh_host_ms_per_query")(hand_built()) == \
        pytest.approx((100 + 100 + 300) / 2 / 1e6)
    # a program that opens no such span on this route (this PR's parent)
    assert reader("mesh_host_ms_per_query")(hand_built(False)) is None
    assert reader("mesh_host_ms_per_query")({"trace": None}) is None


def test_one_plane_or_no_trace_reads_nothing():
    one = {"trace": reduced_of({0: shard()}, [
        host("bench:traced_window", 0, 10000)])}
    for name in ("collective_share", "shard_busy_skew"):
        assert reader(name)(one) is None
        assert reader(name)({"trace": None}) is None
    idle = {"trace": reduced_of({n: [] for n in range(4)}, [
        host("bench:traced_window", 0, 10000)])}
    assert reader("collective_share")(idle) is None
    assert reader("shard_busy_skew")(idle) is None


def snap(metrics):
    return {"top_sql": {}, "metrics": dict(metrics)}


def test_mesh_dispatch_share_leaves_min_rows_out():
    ok = (ROUTE, 'reason="ok",route="mesh"')
    small = (ROUTE, 'reason="min_rows",route="single_chip"')
    degraded = (ROUTE, 'reason="degraded",route="single_chip"')
    other = ("tidb_tpu_xla_cache_total", 'result="hit"')
    read = reader("mesh_dispatch_share")
    g = counters.Growth(snap({ok: 10.0, small: 5.0, other: 3.0}),
                        snap({ok: 76.0, small: 27.0, other: 9.0}))
    assert read({"growth": g}) == 100.0
    g = counters.Growth(snap({ok: 10.0, small: 5.0}),
                        snap({ok: 76.0, small: 27.0, degraded: 2.0}))
    assert read({"growth": g}) == pytest.approx(100.0 * 66 / 68)
    # no counter (this PR's parent), one device (it does not move), or
    # nothing but small tables: nothing to report
    g = counters.Growth(snap({other: 3.0}), snap({other: 9.0}))
    assert read({"growth": g}) is None
    g = counters.Growth(snap({ok: 10.0}), snap({ok: 10.0}))
    assert read({"growth": g}) is None
    g = counters.Growth(snap({small: 5.0}), snap({small: 27.0}))
    assert read({"growth": g}) is None


# ---- the trace recorded on four chips (PR 31) --------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """-> a run as the readers see one: the reduced trace, one client,
    four v5e chips, the recording's 60,000-row lineitem."""
    import run
    pb = tmp_path_factory.mktemp("mesh_trace") / "mesh_trace_4chip.xplane.pb"
    with gzip.open(TRACE, "rb") as packed, open(pb, "wb") as raw:
        shutil.copyfileobj(packed, raw)
    dataset = run.load_module("datasets", "tpch", "data set")
    return {"trace": tr.reduce(str(pb)),
            "traffic": {"clients": [{"name": "stream"}]},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
            "peaks": run.load_json(os.path.join(BENCH, "peaks.json"), "p"),
            "tables": {"lineitem": {"l_orderkey": range(60000)}},
            "dataset": dataset}


def test_recorded_trace_has_four_planes_and_the_mesh_programs(recorded):
    t = recorded["trace"]["trace"]
    assert sorted(t["devices"]) == [0, 1, 2, 3]
    programs = {n.split("(")[0] for mods in t["modules"].values()
                for n, _, _ in mods}
    assert programs == {"jit_bench_clock_probe", "jit_tidb_mpp_fused_dense",
                        "jit_tidb_mpp_fused_posdense",
                        "jit_tidb_mpp_fused_sort"}
    names = [n for n, _, _ in t["host"]]
    assert [n for n in names if n.startswith("stmt:")] == \
        ["stmt:q6", "stmt:q1", "stmt:q5", "stmt:q3"]
    assert "tidb:mpp_dispatch" in names
    # the dense and position-dense results are merged by all-reduce on
    # every device; the sort layout's program has no collective
    colls = {n: sorted({tr.short(name) for name, _, _ in ops
                        if "all-" in tr.short(name)})
             for n, ops in t["devices"].items()}
    assert colls[0] and all(c == colls[0] for c in colls.values()), colls
    assert all(c.startswith("all-reduce") for c in colls[0])


def test_recorded_trace_pins_the_three_trace_readers(recorded):
    r = recorded["trace"]
    assert r["busy_s_by_device"] == {0: 0.00142004, 1: 0.001413964,
                                     2: 0.001414397, 3: 0.001410648}
    assert reader("shard_busy_skew")(recorded) == pytest.approx(
        100.0 * (0.00142004 - 0.001410648) / 0.00142004)
    assert reader("shard_busy_skew")(recorded) == \
        pytest.approx(0.6613898200050699)
    # device 0 is the busiest: its all-reduce intervals over its busy time
    share = reader("collective_share")(recorded)
    assert share == pytest.approx(1.0933494831131516)
    ops = r["trace"]["devices"][0]
    by_hand = sum(min(e, r["hi"]) - max(s, r["lo"]) for n, s, e in ops
                  if tr.short(n).startswith("all-reduce"))
    assert share == pytest.approx(100.0 * by_hand / 1e9 / 0.00142004)
    # four statements lie whole inside the window
    assert reader("mesh_host_ms_per_query")(recorded) == \
        pytest.approx(1.103385)


def test_mesh_scan_roofline_is_scan_roofline_at_four_chips(recorded):
    alias = reader("mesh_scan_roofline")(recorded)
    assert alias == reader("scan_roofline")(recorded)
    assert 0.0 < alias < 100.0
    json.dumps(alias)


# ---- what decides `correct`, shown to fail in this cell too ------------

CELL = "tpch-sf1-mesh4.power"


def test_the_cell_with_an_altered_answer_or_float32_sums_is_not_correct():
    """The engine on the CPU backend at SF0.01, the look for four chips
    skipped (test_correct.py does the same for `tpch-sf1.power`): one
    answer altered where the client receives it makes the run not
    correct, and so does the reference in float32 in the program's
    place."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import checks
    import run

    def wrapper(clients):
        c = clients[0]
        inner = c.wire.rows
        state = {"n": 0}

        def rows(sql):
            out = inner(sql)
            state["n"] += c.deadline != float("inf") and \
                not sql.startswith("show")
            if state["n"] == 3 and out:      # one answer, in the window
                out[0] = out[0][:-1] + (out[0][-1] + "1",)
                state["n"] += 1
            return out
        c.wire.rows = rows
    keep = {}
    result = run.run_cell(CELL, 3_100_000_029, 3.0, False, need_chips=False,
                          scale=0.01, client_wrapper=wrapper, keep=keep)
    assert not result["correct"] and result["failed"] == 1
    assert result["compared"]["answers_wrong"][0] == 1
    control = checks.compare(
        keep["dataset"], keep["tables"], keep["queries"],
        substitute=checks.control_lower_precision(keep["dataset"],
                                                  keep["tables"]))
    assert control["answers_wrong"] > 0.5 * control["answers_compared"]
