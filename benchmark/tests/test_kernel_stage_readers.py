"""The six stage readers and `dispatch_ms_per_query` (PR 36): exact
arithmetic on a hand-built run (two programs of one family that both
have a `fusion.6`, in different stages; a `while` with its body
operations nested inside it; an operation outside every module; a
statement that straddles the window's end), and the small trace
recorded on the chip by record_stage_trace.py with the catalogue the
program served after it (one TPU v5e chip; q3, q10, q6, q1 from a
60,000-row lineitem). Run: python3 -m pytest
benchmark/tests/test_kernel_stage_readers.py (needs no chip)."""
import gzip
import importlib.util
import io
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import counters                                             # noqa: E402
import kernel_stages as ks                                  # noqa: E402
import trace_reduce as tr                                   # noqa: E402

TRACE = os.path.join(HERE, "stage_trace_1chip.xplane.pb.gz")
CATALOGUE = os.path.join(HERE, "stage_trace_1chip.catalogue.json")
OFFSET = 100        # host time = device time + OFFSET
STAGE_METRICS = [f"stage_{s}_ms_per_query" for s in ks.STAGES]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_{name}", os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def sample(program, entry, stage, ops):
    labels = (f'entry="{entry}",ops="{" ".join(ops)}",'
              f'program="{program}",stage="{stage}"')
    return (ks.FAMILY, labels), float(len(ops))


def growth(samples):
    after = dict(samples)
    after[(ks.OUTCOMES, 'outcome="built"')] = 2.0
    return counters.Growth({"metrics": {}, "top_sql": {}},
                           {"metrics": after, "top_sql": {}})


def op(name, start, end):
    return (f"%{name} = s64[8]{{0}} fusion(s64[8]{{0}} %p), kind=kLoop",
            start, end)


def hand_built():
    """Window [0, 10000) on the device's clock, one device. Family
    `jit_f` has two catalogued programs: in entry 0 `fusion.6` is
    dim_probe, in entry 1 group_agg. Module 111 runs operations only
    entry 0 has, 222 one only entry 1 has, 333 only operations both
    have; a bare `fusion.6` runs outside every module, a
    `jit_tidb_mask_copy` has no entry, and module 222's second run
    straddles the window's end, as the last statement does."""
    cat = [sample("jit_f", "0", "dim_probe", ["fusion.6"]),
           sample("jit_f", "0", "scan_filter", ["fusion.1"]),
           sample("jit_f", "0", "compact", ["while.2", "fusion.3"]),
           sample("jit_f", "0", "none", ["copy.1"]),
           sample("jit_f", "1", "group_agg", ["fusion.6"]),
           sample("jit_f", "1", "topn", ["fusion.9"]),
           sample("jit_f", "1", "scan_filter", ["fusion.1"])]
    # module 111's first operation starts and its last one ends a
    # nanosecond outside the module's own event, as on the chip
    ops = [op("fusion.1", 999, 1200), op("fusion.6", 1200, 2000),
           # a body operation that starts with its `while`, listed first
           op("fusion.3", 2000, 2200),
           op("while.2", 2000, 2900), op("fusion.3", 2400, 2600),
           op("copy.1", 2900, 3001),
           op("fusion.6", 4000, 4500), op("fusion.9", 4500, 4800),
           op("fusion.1", 4800, 5000),
           op("fusion.1", 6000, 6200), op("fusion.6", 6200, 6500),
           op("fusion.6", 7000, 7100),
           op("copy", 7500, 7600),
           op("fusion.6", 9800, 10100), op("fusion.9", 10100, 10400)]
    mods = [("jit_f(111)", 1000, 3000), ("jit_f(222)", 4000, 5000),
            ("jit_f(333)", 6000, 6500),
            ("jit_tidb_mask_copy(5)", 7500, 7600),
            ("jit_f(222)", 9800, 10400)]
    host = [("bench:traced_window", 0, 10000),
            ("stmt:q3", -500, 300),         # ends inside: counted here
            ("stmt:q1", 500, 3500), ("stmt:q2", 3600, 6600),
            ("stmt:q1", 9700, 10500),       # straddles the end
            ("tidb:dispatch", 900, 1000), ("tidb:fetch", 1000, 3000),
            ("tidb:dispatch", 3900, 4000), ("tidb:fetch", 4000, 6500),
            ("tidb:dispatch", 9750, 9800)]
    trace = {"devices": {0: sorted(ops, key=lambda e: e[1])},
             "modules": {0: mods},
             "host": sorted(((n, s + OFFSET, e + OFFSET)
                             for n, s, e in host), key=lambda e: e[1])}
    busy = tr.busy(trace, 0, 10000)
    return {"growth": growth(cat),
            "trace": {"trace": trace, "lo": 0, "hi": 10000,
                      "offset_ns": OFFSET, "window_s": 1e-5,
                      "busy_s": tr.length(busy[0]) / 1e9}}


def test_six_stage_readers_exact_and_sum_to_the_busy_time():
    run = hand_built()
    ms = 1e-6 / 3           # nanoseconds over three statements, in ms
    want = {
        "dim_probe": 800,                   # module 111's fusion.6
        "group_agg": 500 + 200,             # 222's, the second clipped
        # the while's own 900 - 2 x 200, and its two body operations
        "compact": 500 + 400,
        "scan_filter": 201 + 200 + 200,     # 333's: both entries agree
        "topn": 300,
        # copy.1; 333's fusion.6 (the entries disagree); the bare
        # fusion.6; the mask copy
        "none": 101 + 300 + 100 + 100}
    for stage, ns in want.items():
        assert reader(f"stage_{stage}_ms_per_query")(run) == \
            pytest.approx(ns * ms), stage
    total = sum(reader(m)(run) for m in STAGE_METRICS)
    assert total * 3 == pytest.approx(run["trace"]["busy_s"] * 1e3)
    assert ks.view(run)["n"] == 3


def test_an_instant_is_counted_once_for_the_innermost_operation():
    ops = [("w", 0, 100), ("a", 10, 30), ("b", 30, 40), ("c", 120, 130)]
    assert ks.own_time(ops, 0, 1000) == [70, 20, 10, 10]
    assert ks.own_time(ops, 20, 125) == [60, 10, 10, 5]
    # a child that outlives its parent still counts each instant once
    assert ks.own_time([("p", 0, 50), ("q", 40, 80)], 0, 100) == [40, 40]


def test_fit_joins_a_module_run_to_the_entries_that_cover_it():
    entries = {"0": {"fusion.6": "dim_probe", "fusion.1": "scan_filter"},
               "1": {"fusion.6": "group_agg", "fusion.1": "scan_filter",
                     "fusion.9": "topn"}}
    assert ks.fit(entries, {"fusion.9", "fusion.6"}) == \
        ("1", {"fusion.9": "topn", "fusion.6": "group_agg"})
    assert ks.fit(entries, {"fusion.1", "fusion.6"}) == \
        ("0+1", {"fusion.1": "scan_filter", "fusion.6": "none"})
    assert ks.fit(entries, {"fusion.77"}) == ("?", {"fusion.77": "none"})
    # an operation of a neighbouring program inside the run's interval
    # costs itself, not the run
    assert ks.fit(entries, {"fusion.9", "fusion.6", "fusion.1",
                            "alien.1"}) == (
        "1~1", {"fusion.9": "topn", "fusion.6": "group_agg",
                "fusion.1": "scan_filter", "alien.1": "none"})
    assert ks.fit(entries, {"fusion.6", "alien.1", "alien.2"})[0] == "?"
    assert ks.fit({}, {"copy"}) == ("?", {"copy": "none"})


def test_labels_as_metrics_summary_renders_them():
    text = 'entry="0",ops="a.1 b-2",program="jit_\\"x\\\\",stage="none"'
    assert ks.labels_of(text) == {"entry": "0", "ops": "a.1 b-2",
                                  "program": 'jit_"x\\', "stage": "none"}


def test_log_tables_by_family_and_the_largest_operations():
    out = io.StringIO()
    ks.log_tables(hand_built(), out)
    lines = out.getvalue().splitlines()
    assert lines[0].startswith(
        "kernel_stages: catalogue of 7 samples, longest label ")
    assert "outcomes {'built': 2}; 3 statements end inside" in lines[0]
    assert lines[1].startswith(
        "kernel_stages: jit_f 0.000004 s (0.001 ms a statement): "
        "dim_probe 0.000001, group_agg 0.000001, compact 0.000001, ")
    assert "kernel_stages: jit_f/0/dim_probe/fusion.6 0.000001 s" in lines
    assert "kernel_stages: jit_f/0+1/none/fusion.6 0.000000 s" in lines


def test_dispatch_ms_per_query_exact():
    # program_spans counts the two statements whole inside the window
    # and the dispatch segments inside them: not the one at 9750
    assert reader("dispatch_ms_per_query")(hand_built()) == \
        pytest.approx((100 + 100) * 1e-6 / 2)


def test_no_catalogue_no_stage_metric():
    run = hand_built()
    run["growth"] = growth([])              # the parent of PR 36
    for m in STAGE_METRICS:
        assert reader(m)(run) is None
    run = hand_built()
    run["trace"] = None
    for m in STAGE_METRICS + ["dispatch_ms_per_query"]:
        assert reader(m)(run) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    pb = str(tmp_path_factory.mktemp("stage") / "trace.xplane.pb")
    with gzip.open(TRACE, "rb") as packed, open(pb, "wb") as raw:
        shutil.copyfileobj(packed, raw)
    with open(CATALOGUE) as f:
        rows = json.load(f)
    after = {(name, labels): value for name, labels, value in rows}
    return {"trace": tr.reduce(pb),
            "growth": counters.Growth({"metrics": {}, "top_sql": {}},
                                      {"metrics": after, "top_sql": {}})}


def test_recorded_stages_sum_to_the_busy_time(recorded):
    """q3, q10, q6, q1 on the chip: the six sum to the device's busy
    time over the four statements, and the join programs' time has a
    stage: `none` holds the copies and the two programs outside the
    catalogue."""
    v = ks.view(recorded)
    assert v["n"] == 4
    got = {m: reader(m)(recorded) for m in STAGE_METRICS}
    assert all(x is not None for x in got.values())
    assert sum(got.values()) * 4 == pytest.approx(
        recorded["trace"]["busy_s"] * 1e3, rel=1e-9)
    assert got["stage_dim_probe_ms_per_query"] == pytest.approx(
        0.469, abs=0.001)
    assert got["stage_group_agg_ms_per_query"] == pytest.approx(
        0.208, abs=0.001)
    assert got["stage_compact_ms_per_query"] == pytest.approx(
        0.162, abs=0.001)
    assert got["stage_none_ms_per_query"] < 0.05 * sum(got.values())
    assert reader("dispatch_ms_per_query")(recorded) > 0


def test_recorded_programs_of_one_family_are_told_apart(recorded):
    """q3's and q10's programs are both `jit_tidb_fused_posruns` and
    both have a `fusion.6`: each module is joined to its own entry by
    the operations seen in it."""
    cat = ks.catalogue(recorded["growth"].after["metrics"])
    entries = cat["jit_tidb_fused_posruns"]
    assert len(entries) == 2
    assert all("fusion.6" in ops for ops in entries.values())
    keys = [k for k in ks.view(recorded)["ns"]
            if k[0] == "jit_tidb_fused_posruns" and k[3] == "fusion.6"]
    assert sorted(k[1] for k in keys) == ["0", "1"]
    assert {k[2] for k in keys} == {"dim_probe"}
    # families outside the catalogue are `none` by name
    assert {k[2] for k in ks.view(recorded)["ns"]
            if k[0] in ("jit_tidb_mask_copy", "jit_bench_clock_probe")} \
        == {"none"}
