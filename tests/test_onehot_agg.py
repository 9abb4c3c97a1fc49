"""One-hot MXU segment-aggregation lowering (copr/agg_lowering
onehot_agg_body): a host-learned slot table + int8 limb matmuls replace
the device argsort for small group domains under the TPU segment
policy. Exactness guards: miss detection on new/out-of-span keys,
zero-slot drop for deletes, arbitrary-precision limb recombination.
Forced on here through the module's two test seams: the runs policy
and the one-hot kind on the CPU backend (which would otherwise take
its scatter impl and skip it)."""
import numpy as np
import pytest

import tidb_tpu.copr.agg_lowering as al
from tidb_tpu.testkit import TestKit


@pytest.fixture()
def tk(monkeypatch):
    monkeypatch.setattr(al, "_FORCE_SEGMENT_IMPL", "runs")
    monkeypatch.setattr(al, "_FORCE_ONEHOT", True)
    tk = TestKit()
    tk.must_exec("create table f (id bigint primary key, g bigint, "
                 "h bigint, v bigint, w bigint)")
    rng = np.random.RandomState(7)
    rows = []
    for i in range(30000):
        rows.append(
            f"({i},{int(rng.randint(0, 40)) * 977},"
            f"{int(rng.randint(0, 5))},"
            f"{int(rng.randint(-1000000, 1000000))},"
            f"{int(rng.randint(0, 1 << 40))})")
    tk.must_exec("insert into f values " + ",".join(rows))
    return tk


Q = ("select g, h, count(*), sum(v), sum(w), avg(v) from f "
     "where v > -900000 group by g, h order by g, h")


def test_onehot_learns_and_matches(tk):
    r1 = tk.must_query(Q).rs.rows          # learns from sorted/runs
    m0 = tk.domain.metrics.get("fused_onehot_agg", 0)
    r2 = tk.must_query(Q).rs.rows          # one-hot path
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > m0
    assert len(r1) == len(r2) == 200
    for a, b in zip(r1, r2):
        assert list(a) == list(b)


def test_onehot_miss_invalidates(tk):
    tk.must_query(Q)
    tk.must_query(Q)
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > 0
    # a brand-new group key must be a miss -> exact fallback + relearn
    tk.must_exec("insert into f values (100000, 99991, 9, 5, 5)")
    r3 = tk.must_query(Q).rs.rows
    assert len(r3) == 201
    r4 = tk.must_query(Q).rs.rows
    assert [list(x) for x in r3] == [list(x) for x in r4]


def test_onehot_zero_slot_drop(tk):
    tk.must_query(Q)
    tk.must_query(Q)
    tk.must_exec("delete from f where g = 0")
    r = tk.must_query(Q).rs.rows
    assert 0 not in {x[0] for x in r}
    assert len(r) == 195 or len(r) == 196      # 5 h-groups under g=0


def test_onehot_negative_and_wide_sums(tk):
    # sums with negatives (sign-bit limb) and 40-bit values must be
    # bit-exact vs the host oracle
    dev = tk.must_query("select g, sum(v), sum(w) from f group by g "
                        "order by g").rs.rows
    dev2 = tk.must_query("select g, sum(v), sum(w) from f group by g "
                         "order by g").rs.rows
    tk.domain.copr.use_device = False
    host = tk.must_query("select g, sum(v), sum(w) from f group by g "
                         "order by g").rs.rows
    tk.domain.copr.use_device = True
    assert [list(x) for x in dev] == [list(x) for x in host]
    assert [list(x) for x in dev2] == [list(x) for x in host]


def test_onehot_pipelined_miss_on_one_partition(tk, monkeypatch):
    """A new key whose rows land in only ONE partition: the sibling
    pipelined partition consumes its dispatched one-hot state cleanly
    while the miss pops the cache — must fall back, not crash."""
    tk.domain.copr.device_rows = 8192      # ~4 partitions
    tk.must_query(Q)
    tk.must_query(Q)
    assert tk.domain.metrics.get("fused_onehot_agg", 0) > 0
    # key 99991*977 only ever lands in the last partition
    tk.must_exec("insert into f values (100001, 97661207, 0, 1, 1)")
    r = tk.must_query(Q).rs.rows
    assert len(r) == 201
    r2 = tk.must_query(Q).rs.rows
    assert [list(x) for x in r] == [list(x) for x in r2]


def test_onehot_delta_fold_zero_rebuilds_on_append(tk):
    """ISSUE 15 satellite (ROADMAP item #5 learned-structure tail):
    an in-bucket append — existing keys AND a brand-new in-span key —
    extends the learned slot table at bind time through the
    version-advance/delta contract, with ZERO dispatch-time
    miss-pop-relearns; the one-hot path keeps serving and stays
    host-identical."""
    tk.must_query(Q)
    tk.must_query(Q)
    m = tk.domain.metrics
    served0 = m.get("fused_onehot_agg", 0)
    assert served0 > 0
    # 500 is inside the learned span (keys are 977-multiples in
    # [0, 38103]) but not a learned key -> a genuinely new slot
    tk.must_exec("insert into f values (100000, 500, 3, 7, 7), "
                 "(100001, 977, 0, 1, 1)")
    r = tk.must_query(Q).rows
    assert m.get("fused_onehot_miss", 0) == 0
    assert m.get("fused_onehot_rebuild", 0) == 0
    assert m.get("fused_onehot_delta_fold", 0) == 1
    assert m.get("fused_onehot_agg", 0) > served0   # still one-hot
    assert len(r) == 201
    r2 = tk.must_query(Q).rows
    assert [list(x) for x in r] == [list(x) for x in r2]
    # host oracle
    tk.domain.copr.use_device = False
    host = tk.must_query(Q).rows
    tk.domain.copr.use_device = True
    assert [list(x) for x in r2] == [list(x) for x in host]


def test_onehot_delta_fold_out_of_span_relearns(tk):
    """A key the learned packing cannot represent still relearns
    cleanly (the only rebuild left) and stays correct."""
    tk.must_query(Q)
    tk.must_query(Q)
    m = tk.domain.metrics
    tk.must_exec("insert into f values (100002, 99999977, 3, 1, 1)")
    r = tk.must_query(Q).rows
    assert m.get("fused_onehot_rebuild", 0) == 1
    assert len(r) == 201
    r2 = tk.must_query(Q).rows
    assert [list(x) for x in r] == [list(x) for x in r2]


def test_onehot_full_range_keys_rejected(tk):
    # key spans beyond the 61-bit pack budget must be rejected BEFORE
    # packing (no OverflowError), falling back to the exact lowering
    tk.must_exec("create table wide (id bigint primary key, g bigint, "
                 "v int)")
    tk.must_exec(f"insert into wide values (1, {-(1 << 62)}, 1), "
                 f"(2, {1 << 62}, 2), (3, 0, 3)")
    q = "select g, sum(v) from wide group by g order by g"
    r1 = tk.must_query(q).rs.rows
    r2 = tk.must_query(q).rs.rows
    assert [list(x) for x in r1] == [list(x) for x in r2]
    assert len(r1) == 3
