"""The two readers of the resident store's counters (PR 27), on counter
snapshots shaped as `counters.snapshot` returns them: one recorded from
a CPU rehearsal of `tpch-sf3.power` at SF0.01 (metrics_summary rows and
tidb_top_sql columns as served, trimmed to what the readers and
`counters.Growth` touch), one of a program that serves neither counter
(what the parent of the PR that adds a reader may be), and a window with
first touches in it. Then every cell of BENCHMARK.json driven on the CPU
backend: both readers report in each. Run: python3 -m pytest
benchmark/tests/test_residency_readers.py (needs no chip)."""
import importlib.util
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import counters                                             # noqa: E402
import run                                                  # noqa: E402

PEAKS = run.load_json(os.path.join(BENCH, "peaks.json"), "peaks table")
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
POOL = "tidb_tpu_device_buffer_pool_total"
HELD = "tidb_tpu_device_resident_bytes"
Q6 = "select sum(l_extendedprice * l_discount) as revenue from lineitem"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_{name}", os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def snap(execs, metrics):
    top = {"d6": {"text": Q6, "exec_count": float(execs)},
           "dm": {"text": "select metrics_name from "
                          "information_schema.metrics_summary",
                  "exec_count": 9.0}}
    return {"top_sql": top, "metrics": dict(metrics)}


def run_of(before, after, device=V5E):
    return {"growth": counters.Growth(before, after), "device": device,
            "peaks": PEAKS}


# metrics_summary of the rehearsal (seed 2700000053; one CPU device, so
# the store holds `local` entries alone), before and after its window of
# 66 statements: 25 executions of the six before, 91 after
RECORDED_BEFORE = {(POOL, 'result="hit"'): 303.0,
                   (POOL, 'result="miss"'): 39.0,
                   (HELD, 'spec="local"'): 5548544.0,
                   ("tidb_tpu_device_resident_budget_bytes", ""): 8589934592.0}
RECORDED_AFTER = dict(RECORDED_BEFORE)
RECORDED_AFTER[(POOL, 'result="hit"')] = 1238.0


def test_recorded_warm_window():
    r = run_of(snap(25, RECORDED_BEFORE), snap(91, RECORDED_AFTER))
    assert reader("pool_misses_per_query")(r) == 0.0
    share = reader("resident_hbm_share")(r)
    assert share == pytest.approx(100.0 * 5548544 / 16e9)


def test_first_touches_in_the_window_and_four_chips():
    after = dict(RECORDED_AFTER)
    after[(POOL, 'result="miss"')] += 33.0
    after[(HELD, 'spec="sharded"')] = 3.2e9
    after[(HELD, 'spec="replicated"')] = 0.4e9
    after[(HELD, 'spec="local"')] = 0.4e9
    mesh = dict(V5E, count=4)
    r = run_of(snap(25, RECORDED_BEFORE), snap(91, after), device=mesh)
    assert reader("pool_misses_per_query")(r) == 0.5     # 33 over 66
    assert reader("resident_hbm_share")(r) == \
        pytest.approx(100.0 * 4.0e9 / 64e9)              # the end's bytes


def test_a_program_without_the_counters_reports_nothing():
    other = {("tidb_tpu_xla_cache_total", 'result="hit"'): 40.0}
    r = run_of(snap(25, other), snap(91, other))
    assert reader("pool_misses_per_query")(r) is None
    assert reader("resident_hbm_share")(r) is None


def test_no_statement_and_unknown_device_report_nothing():
    r = run_of(snap(25, RECORDED_BEFORE), snap(25, RECORDED_AFTER))
    assert reader("pool_misses_per_query")(r) is None
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    r = run_of(snap(25, RECORDED_BEFORE), snap(91, RECORDED_AFTER), cpu)
    assert reader("resident_hbm_share")(r) is None


# ---- the configurations and cells, loaded and rehearsed ----------------

BENCHMARK = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"), "b")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    bench, entry, config, traffic = run.find_cell(cell)
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert config["name"] == cfg["name"] and config["chips"] == \
        entry["chips"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    for key in ("source", "dataset", "scale_factor", "statements",
                "guarantees", "published", "shapes_kept", "assumed"):
        assert config[key], key
    assert traffic["clients"][0]["statements"] == config["statements"]
    run.load_module("datasets", config["dataset"], "data set")
    for m in run.metrics_of(bench, "per_layer", entry):
        run.load_module("layer_metrics", m["name"], "per-layer metric")


def test_sf3_states_what_sf1_states_at_three_times_the_rows():
    sf1 = run.find_cell("tpch-sf1.power")[2]
    sf3 = run.find_cell("tpch-sf3.power")[2]
    assert sf3["scale_factor"] == 3 and sf1["scale_factor"] == 1
    for same in ("guarantees", "statements", "chips", "layout",
                 "published", "assumed"):
        assert sf3[same] == sf1[same], same
    for key in ("queries", "refresh_functions", "parameters"):
        assert sf3["reduced"][key] == sf1["reduced"][key]
    assert "18,003,645" in sf3["shapes_kept"]


# ---- the SF3 cell's data set: tpch's, under a watch on host memory -----

def test_host_bound_data_set_is_tpch_name_for_name():
    sf1 = run.find_cell("tpch-sf1.power")[2]
    sf3 = run.find_cell("tpch-sf3.power")[2]
    tpch = run.load_module("datasets", sf1["dataset"], "data set")
    bound = run.load_module("datasets", sf3["dataset"], "data set")
    assert bound is not tpch
    for name, value in vars(tpch).items():
        if not name.startswith("_") and name != "generate":
            assert getattr(bound, name) is value, name
    a, b = tpch.generate(0.002, 2_700_000_061), \
        bound.generate(0.002, 2_700_000_061)
    assert a.keys() == b.keys()
    for q in tpch.STATEMENTS:
        assert bound.reference(b, q) == tpch.reference(a, q)


def test_host_watch_ends_the_run_past_its_share_and_not_under_it():
    bound = run.load_module("datasets", "tpch_host_bound", "data set")
    host = bound.HOST_BYTES
    assert host == 40 << 30 and bound.host_bytes() <= host
    assert not bound.over(int(0.89 * host), host)
    assert bound.over(int(0.91 * host), host)
    assert not bound.over(None, host)      # no /proc: nothing to hold
    readings = iter([25.6e9, 32.81e9, 38.0e9, 39.0e9, 60e9])
    ended = []
    bound._watch(host, read=lambda: next(readings),
                 end=lambda held, h: ended.append((held, h)), period=0)
    assert ended == [(39.0e9, host)]        # 38.65 GB is the line
    assert bound._highest[0] == 38.0e9
    bound._highest[0] = 0
    assert 0 < bound.resident_bytes() < bound.HOST_SHARE * host


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_and_both_readers_report(cell):
    """`rehearse.py --scale 0.01` drives every cell the same way; here
    the traced run alone, where the per-layer metrics are."""
    result = run.run_cell(cell, 2_700_000_053, 3.0, True,
                          need_chips=False, scale=0.01)
    assert result["correct"] and result["failed"] == 0
    assert result["metrics"]["pool_misses_per_query"]["value"] == 0.0
    assert result["metrics"]["upload_bytes_per_query"]["value"] == 0.0
    # no HBM is stated for the CPU backend: the share is left out there
    assert "resident_hbm_share" not in result["metrics"]
    json.dumps(result)
