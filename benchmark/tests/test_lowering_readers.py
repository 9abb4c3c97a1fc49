"""The two readers of `tidb_tpu_agg_lowering_total`
(`lowering_retries_per_query`, `sorted_lowering_share`; PR 34): exact
arithmetic on counter snapshots made by hand, then a run of a cell that
lists them, with its own files, on the CPU backend at a small scale,
under the chip's lowering policy, with one answer altered at the client.
Run: python3 -m pytest benchmark/tests/test_lowering_readers.py
(needs no chip)."""
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
# a mesh for the cell's run below, where this file is the first to
# bring jax into the process; one device runs the same files off it
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
import counters                                             # noqa: E402

LOWERING = "tidb_tpu_agg_lowering_total"
CELLS = ("tpch-sf3-mesh4.power", "tpch-sf1-mesh4.power")
REASONS = ("retry_early_compact", "retry_compact", "retry_pin_sorted",
           "retry_grow_bucket", "retry_onehot_miss", "retry_topn_unproven")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_{name}", os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def judged(site, kind, verdict):
    return (LOWERING, f'kind="{kind}",site="{site}",verdict="{verdict}"')


def snap(metrics, queries):
    """`queries` analytic statements so far, and the harness's own read
    of the counters, which no reader may count."""
    return {"metrics": dict(metrics), "top_sql": {
        "d1": {"text": "select l_returnflag from lineitem",
               "exec_count": float(queries)},
        "d2": {"text": "select metrics_name from "
               "information_schema.metrics_summary",
               "exec_count": float(queries * 3)}}}


def growth(before, after, queries=60):
    return {"growth": counters.Growth(snap(before, 12),
                                      snap(after, 12 + queries))}


def test_a_steady_window_reads_no_retry_and_no_sorted_run():
    dense = judged("fused_mpp", "dense", "stands")
    runs = judged("fused_mpp", "sort_runs", "stands")
    grown = judged("fused_mpp", "sort_runs", "retry_grow_bucket")
    # the warm-up's retry is in both snapshots: it did not grow
    run = growth({dense: 8.0, runs: 12.0, grown: 3.0},
                 {dense: 38.0, runs: 42.0, grown: 3.0})
    assert reader("lowering_retries_per_query")(run) == 0.0
    assert reader("sorted_lowering_share")(run) == 0.0


def test_each_reason_counts_once_a_thrown_away_run():
    before = {judged("fused", "posruns", "stands"): 5.0}
    after = {judged("fused", "posruns", "stands"): 65.0}
    after.update({judged("fused", "posruns", r): 1.0 for r in REASONS})
    after[judged("dag", "sort_runs", "retry_grow_bucket")] = 2.0
    run = growth(before, after, queries=60)
    assert reader("lowering_retries_per_query")(run) == \
        pytest.approx((len(REASONS) + 2) / 60)


def test_sorted_share_is_of_the_sort_family_alone():
    before = {judged("fused_mpp", "sort_runs", "stands"): 10.0,
              judged("fused_mpp", "sort_sorted", "stands"): 1.0}
    after = {judged("fused_mpp", "sort_runs", "stands"): 40.0,
             judged("fused_mpp", "sort_runs", "retry_pin_sorted"): 1.0,
             judged("fused_mpp", "sort_sorted", "stands"): 10.0,
             judged("fused", "posruns", "stands"): 20.0,
             judged("fused_mpp", "dense", "stands"): 500.0,
             judged("dag", "sort_scatter", "stands"): 7.0}
    # 9 sorted of 30 + 1 + 9 + 20 runs of the family; the dense kinds
    # and the CPU's scatter are no part of it
    assert reader("sorted_lowering_share")(growth(before, after)) == \
        pytest.approx(100.0 * 9 / 60)


def test_no_counter_or_no_run_of_the_family_reads_nothing():
    other = ("tidb_tpu_xla_cache_total", 'result="hit"')
    parent = growth({other: 3.0}, {other: 9.0})     # this PR's parent
    assert reader("lowering_retries_per_query")(parent) is None
    assert reader("sorted_lowering_share")(parent) is None
    dense = judged("fused_mpp", "dense", "stands")
    scans = growth({dense: 2.0}, {dense: 30.0})
    assert reader("sorted_lowering_share")(scans) is None
    assert reader("lowering_retries_per_query")(scans) == 0.0
    # no analytic statement in the window: nothing to divide by
    assert reader("lowering_retries_per_query")(
        growth({dense: 2.0}, {dense: 30.0}, queries=0)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_with_an_altered_answer_or_float32_sums_is_not_correct(
        monkeypatch, cell):
    """A mesh cell's own files (configuration, traffic, data set,
    readers) on the CPU backend at scale 0.02: 120,000 lines, over
    `tidb_mpp_min_rows`, so a process with four devices takes the mesh
    route; the runs policy gives the chip's kinds. One answer altered
    where the client receives it makes the run not correct, and so does
    the reference in float32 in the program's place; the two readers
    report from the run's own counters."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import checks
    import run
    import tidb_tpu.copr.agg_lowering as al
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")

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
    result = run.run_cell(cell, 3_400_000_029, 3.0, True, need_chips=False,
                          scale=0.02, client_wrapper=wrapper, keep=keep)
    assert not result["correct"] and result["failed"] == 1
    assert result["compared"]["answers_wrong"][0] == 1
    assert result["compared"]["device_degrades"][0] == 0
    metrics = result["metrics"]
    assert metrics["lowering_retries_per_query"] == \
        {"value": 0.0, "unit": "count"}
    assert metrics["sorted_lowering_share"] == {"value": 0.0, "unit": "%"}
    control = checks.compare(
        keep["dataset"], keep["tables"], keep["queries"],
        substitute=checks.control_lower_precision(keep["dataset"],
                                                  keep["tables"]))
    assert control["answers_wrong"] > 0.5 * control["answers_compared"]
