"""The trace reduction against the small trace recorded on the chip by
record_trace.py (one TPU v5e chip; three runs of one scan program inside
spans stmt:q6, stmt:q1, stmt:q6; PR 24). Run: python3 -m pytest
benchmark/tests/test_trace_reduce.py (needs no chip)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import trace_reduce as tr                                   # noqa: E402

TRACE = os.path.join(HERE, "small_trace_1chip.xplane.pb")
# the recording's spans are its probes: each encloses one whole program
KW = {"probe_prefix": "stmt:", "probe_module": "jit__lambda"}


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(TRACE, **KW)


def test_planes_and_spans(reduced):
    t = reduced["trace"]
    assert list(t["devices"]) == [0]
    assert [tr.short(n) for n, _, _ in t["devices"][0]] == \
        ["select_reduce_fusion"] * 3
    assert [n for n, _, _ in t["host"]] == \
        ["bench:traced_window", "stmt:q6", "stmt:q1", "stmt:q6"]


def test_clock_offset_is_bounded_by_the_probes(reduced):
    # every device op starts 1.10-1.15 ms before its span and ends
    # 1.79-2.02 ms before its span's end: the offset lies between
    assert 1_150_000 <= reduced["offset_ns"] <= 1_790_000
    t = reduced["trace"]
    for (_, s, e), (_, hs, he) in zip(t["devices"][0], tr.spans(t, "stmt:")):
        assert hs <= s + reduced["offset_ns"] and \
            e + reduced["offset_ns"] <= he


def test_busy_idle_and_ops(reduced):
    ops = reduced["trace"]["devices"][0]
    total = sum(e - s for _, s, e in ops) / 1e9
    assert reduced["busy_s"] == pytest.approx(total)
    assert reduced["busy_s"] == pytest.approx(37.883e-6)
    assert reduced["window_s"] == pytest.approx(0.063466091)
    assert reduced["device_ops"] == [("select_reduce_fusion",
                                      pytest.approx(total))]
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])
    assert reduced["idle_gaps"][0][0] == "between statements"


def test_busy_inside_statement_spans(reduced):
    t = reduced["trace"]
    n, s = tr.busy_inside(t, "stmt:q6", reduced["lo"], reduced["hi"],
                          reduced["offset_ns"])
    assert (n, s) == (2, pytest.approx((12623 + 12636) / 1e9))
    n, s = tr.busy_inside(t, "stmt:q1", reduced["lo"], reduced["hi"],
                          reduced["offset_ns"])
    assert (n, s) == (1, pytest.approx(12624 / 1e9))


def test_intervals():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert tr.clip([(1, 4), (5, 7)], 2, 6) == [(2, 4), (5, 6)]
    assert tr.length([(2, 4), (5, 6)]) == 3
