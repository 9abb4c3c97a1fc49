"""The five readers of the program's `tidb:` segments (PR 25): exact
arithmetic on a hand-built `run["trace"]` with two interleaved
connections, and the small trace recorded on the chip by
record_span_trace.py (one TPU v5e chip; q6, q1, q6, q1 served over the
wire from a 60,000-row lineitem). Run: python3 -m pytest
benchmark/tests/test_program_spans.py (needs no chip)."""
import importlib.util
import io
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import program_spans as ps                                  # noqa: E402
import trace_reduce as tr                                   # noqa: E402

TRACE = os.path.join(HERE, "span_trace_1chip.xplane.pb")
OFFSET = 100        # host time = device time + OFFSET


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"lm_{name}", os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def host(name, start, end):
    return (name, start + OFFSET, end + OFFSET)


def hand_built():
    """Window [0, 10000) on the device's clock. Connection A: q6 whole
    inside, then a q1 that ends after the window. Connection B: a q1
    inside, overlapping A's q6. A third thread's `bind` straddles the
    end of the counted statements."""
    a = [("command", 1100, 1200), ("statement", 1200, 1400),
         ("execute", 1400, 1500), ("bind", 1500, 1700),
         ("dispatch", 1700, 1800), ("consume", 1800, 2000),
         ("fetch", 2000, 2500), ("consume", 2500, 2600),
         ("execute", 2600, 2700), ("statement", 2700, 2750),
         ("command", 2750, 2760), ("wire_write", 2760, 2900),
         ("command", 2900, 2950),
         ("command", 9100, 9300)]              # the q1 not counted
    b = [("command", 2100, 2200), ("parse", 2200, 2500),
         ("statement", 2500, 2700), ("plan", 2700, 3200),
         ("statement", 3200, 3300), ("execute", 3300, 3400),
         ("copr", 3400, 3500), ("bind", 3500, 3600),
         ("dispatch", 3600, 3700), ("device_attempt", 3700, 3800),
         ("fetch", 3800, 5000), ("consume", 5000, 5300),
         ("execute", 5300, 5400), ("wire_write", 5500, 5800),
         ("command", 5800, 5900)]
    c = [("bind", 5900, 6300)]
    hosts = [host("bench:traced_window", 0, 10000),
             host("stmt:q6", 1000, 3000), host("stmt:q1", 2000, 6000),
             host("stmt:q1", 9000, 11000)]
    hosts += [host("tidb:" + n, s, e) for n, s, e in a + b + c]
    trace = {"devices": {0: [("%fusion.1 = s64[] fusion()", 1750, 2400),
                             ("%fusion.2 = s64[] fusion()", 3650, 4900),
                             ("%while.3 = s64[] while()", 7000, 7500)]},
             "modules": {0: []},
             "host": sorted(hosts, key=lambda e: e[1])}
    busy = tr.busy(trace, 0, 10000)
    return {"trace": {"trace": trace, "lo": 0, "hi": 10000,
                      "offset_ns": OFFSET, "window_s": 1e-5,
                      "busy_s_by_device":
                      {n: tr.length(iv) / 1e9 for n, iv in busy.items()}}}


def test_view_counts_whole_statements_and_clips_segments():
    v = ps.view(hand_built())
    assert v["n"] == 2
    assert sorted(v["statements"]) == [("q1", 2000, 6000),
                                       ("q6", 1000, 3000)]
    names = [n for n, _, _ in v["segments"]]
    assert names.count("command") == 5          # not the one at 9100
    assert ("bind", 5900, 6000) in v["segments"]    # clipped at 6000
    assert v["busy"] == [(1750, 2400), (3650, 4900), (7000, 7500)]


def test_the_four_ms_per_query_readers_exact():
    run = hand_built()
    ms = 1e-6 / 2       # nanoseconds over two statements, in ms
    assert reader("wire_ms_per_query")(run) == pytest.approx(
        (100 + 10 + 50 + 100 + 100 + 140 + 300) * ms)
    assert reader("frontend_ms_per_query")(run) == pytest.approx(
        (300 + 200 + 50 + 200 + 100 + 500) * ms)
    assert reader("executor_host_ms_per_query")(run) == pytest.approx(
        (100 + 100 + 100 + 100 + 200 + 100 + 300 + 100 + 100) * ms)
    assert reader("bind_ms_per_query")(run) == pytest.approx(
        (200 + 100 + 100) * ms)
    # every instant of a served statement is in one segment or on the
    # wire: with dispatch and fetch the segments sum to each thread's
    # time inside the counted statements
    total = ps.ms_per_query(run, ("command", "wire_write", "parse",
                                  "statement", "plan", "execute",
                                  "consume", "copr", "device_attempt",
                                  "bind", "dispatch", "fetch"))
    assert total == pytest.approx(
        ((2950 - 1100) + (5400 - 2100) + (5900 - 5500) + 100) * ms)


def test_server_idle_share_exact_and_under_device_idle_share():
    run = hand_built()
    # idle [0,1750) [2400,3650) [4900,7000) [7500,10000); a segment is
    # open over [1100,5400) [5500,6300) [9100,9300)
    held = 650 + 1250 + 500 + 800 + 200
    assert ps.server_idle(run) == (held, 10000)
    assert reader("server_idle_share")(run) == pytest.approx(34.0)
    assert reader("device_idle_share")(run) == pytest.approx(76.0)


def test_log_tables_split_idle_by_segment_and_statements_by_span():
    out = io.StringIO()
    ps.log_tables(hand_built(), out)
    lines = out.getvalue().splitlines()
    idle = dict(kv.rsplit(" ", 1) for kv in
                lines[0].split(": ", 2)[2].split(", "))
    # gap middles 875, 3025, 5950, 8750: nothing, B's plan, C's bind
    assert idle == {"no_server_segment": "0.000004",
                    "plan": "0.000001", "bind": "0.000002"}
    q6 = next(ln for ln in lines if ln.startswith("program_spans: q6 "))
    # q6's interval also holds 900 ns of connection B's segments
    assert "n=1 client 0.002 ms, segments 0.003 ms: fetch 0.001" in q6


def test_no_view_without_a_trace_or_without_program_segments():
    assert ps.view({"trace": None}) is None
    run = hand_built()
    run["trace"]["trace"]["host"] = [
        e for e in run["trace"]["trace"]["host"]
        if not e[0].startswith("tidb:")]        # the parent of PR 25
    for name in ("wire_ms_per_query", "frontend_ms_per_query",
                 "executor_host_ms_per_query", "bind_ms_per_query",
                 "server_idle_share"):
        assert reader(name)(run) is None
        assert reader(name)({"trace": None}) is None


def test_intersect():
    assert ps.intersect([(0, 5), (7, 9)], [(3, 8)]) == [(3, 5), (7, 8)]
    assert ps.intersect([(0, 1)], [(1, 2)]) == []


# ---- the trace recorded on the chip (record_span_trace.py, PR 25) ----

@pytest.fixture(scope="module")
def recorded():
    return {"trace": tr.reduce(TRACE)}


def test_recorded_trace_has_named_programs_and_flat_segments(recorded):
    t = recorded["trace"]["trace"]
    assert sorted({n.split("(")[0] for n, _, _ in t["modules"][0]}) == \
        ["jit_bench_clock_probe", "jit_tidb_fused_dense"]
    v = ps.view(recorded)
    assert [n for n, _, _ in v["statements"]] == ["q6", "q1", "q6", "q1"]
    names = {n for n, _, _ in v["segments"]}
    assert names == {"command", "statement", "execute", "device_attempt",
                     "bind", "dispatch", "consume", "fetch", "wire_write"}
    # one connection: its thread's segments never overlap
    segs = sorted((s, e) for _, s, e in v["segments"])
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(segs, segs[1:]))
    assert sum(1 for n, _, _ in v["segments"] if n == "dispatch") == 4


def test_recorded_trace_readers(recorded):
    assert reader("wire_ms_per_query")(recorded) == \
        pytest.approx(0.8696585)
    assert reader("frontend_ms_per_query")(recorded) == \
        pytest.approx(0.33445775)
    assert reader("executor_host_ms_per_query")(recorded) == \
        pytest.approx(1.753825)
    assert reader("bind_ms_per_query")(recorded) == pytest.approx(0.5738625)
    assert ps.server_idle(recorded) == (22662378, 304776852)
    share = reader("server_idle_share")(recorded)
    assert share == pytest.approx(7.4357281)
    assert share <= reader("device_idle_share")(recorded)
    # every instant of a served statement is in one segment or on the
    # wire: all segments a statement, against its latency at the client
    v = ps.view(recorded)
    by = ps.ms_by_name(v["segments"], v["n"])
    client = sum(e - s for _, s, e in v["statements"]) / v["n"] / 1e6
    assert client == pytest.approx(5.820125)
    assert sum(by.values()) == pytest.approx(5.24576675)
    assert 0.85 * client < sum(by.values()) < client
