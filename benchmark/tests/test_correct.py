"""What decides `correct`, shown to fail (needs no chip; the engine runs
on the CPU backend at SF0.01, the harness's look for a chip skipped):

  * the control comes out as not correct: the reference in float32
    accumulation in the program's place;
  * the rest of a run, driven with the timed path broken underneath (an
    answer altered where the client receives it), reports `correct`
    false.

Run: JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_correct.py
"""
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import checks                                               # noqa: E402
import run                                                  # noqa: E402

SEED = 2_400_000_029
SCALE = 0.01


def drive(cell, wrapper=None, seconds=3.0):
    keep = {}
    result = run.run_cell(cell, SEED, seconds, False, need_chips=False,
                          scale=SCALE, client_wrapper=wrapper, keep=keep)
    return result, keep


@pytest.fixture(scope="module")
def power():
    return drive("tpch-sf1.power")


def test_sound_run_is_correct(power):
    result, _ = power
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["answers_compared"][0] > 0


def test_lower_precision_control_is_not_correct(power):
    k = power[1]
    got = checks.compare(
        k["dataset"], k["tables"], k["queries"],
        substitute=checks.control_lower_precision(k["dataset"], k["tables"]))
    assert got["answers_wrong"] > 0.5 * got["answers_compared"]


def test_altered_answer_is_not_correct():
    def wrapper(clients):
        c = clients[0]
        inner = c.wire.rows
        state = {"n": 0}

        def rows(sql):
            out = inner(sql)
            state["n"] += c.deadline != float("inf") and \
                not sql.startswith("show")
            if state["n"] == 3 and out:      # one answer, inside the window
                out[0] = out[0][:-1] + (out[0][-1] + "1",)
                state["n"] += 1
            return out
        c.wire.rows = rows
    result, _ = drive("tpch-sf1.power", wrapper)
    assert not result["correct"]
    assert result["compared"]["answers_wrong"][0] == 1
    assert result["failed"] == 1


def test_seeded_sizes_differ_only_in_size():
    """`shape_seed` draws lines per order and quantities from another
    seed: the handle by which the program's sensitivity to sizes (a
    segfault at some row counts, Q18's bucket: PERF.md) stays visible."""
    ds = run.load_module("datasets", "tpch", "data set")
    pinned = ds.generate(SCALE, SEED)
    again = ds.generate(SCALE, SEED + 1)
    seeded = ds.generate(SCALE, SEED, shape_seed=SEED)
    rows = [len(t["lineitem"]["l_orderkey"]) for t in (pinned, again, seeded)]
    assert rows[0] == rows[1] == round(ds.LINEITEM_ROWS_SF1 * SCALE)
    assert rows[2] != rows[0]
    assert (pinned["lineitem"]["l_quantity"] ==
            again["lineitem"]["l_quantity"]).all()
    assert (pinned["lineitem"]["l_partkey"] !=
            again["lineitem"]["l_partkey"]).any()
