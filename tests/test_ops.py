"""Segment-aggregation lowerings (sorted / runs) vs the scatter oracle."""
import numpy as np
import pytest


@pytest.mark.slow          # ~50s: keeps tier-1 inside its wall budget
def test_dense_agg_sorted_matches_scatter():
    """The TPU lowering of dense_agg_states (shared argsort + segmented
    scans, no scatter) must match the scatter lowering state-for-state:
    sums/counts exactly, min/max/first_row, NULL args, empty slots."""
    import jax
    import jax.numpy as jnp
    import tidb_tpu.copr.agg_lowering as al
    from tidb_tpu.expression import EvalCtx
    from tidb_tpu.expression.expr import Column
    from tidb_tpu.types.field_type import new_bigint_type, new_double_type

    rng = np.random.RandomState(7)
    cap = 4096
    nslots = 11
    mask = rng.rand(cap) < 0.7
    slot = np.where(mask, rng.randint(0, nslots - 2, cap), nslots)
    # slot nslots-2 and nslots-1 stay EMPTY
    ints = rng.randint(-50, 50, cap).astype(np.int64)
    flts = rng.randn(cap)
    fnull = rng.rand(cap) < 0.2

    class A:
        def __init__(self, name, args):
            self.name, self.args, self.distinct = name, args, False
    ci = Column(0, new_bigint_type())
    cf = Column(1, new_double_type())
    aggs = [A("count", []), A("sum", [ci]), A("avg", [cf]),
            A("min", [ci]), A("max", [cf]), A("first_row", [ci]),
            A("count", [cf])]
    cols = {0: (jnp.asarray(ints), None, None),
            1: (jnp.asarray(flts), jnp.asarray(fnull), None)}
    ctx = EvalCtx(jnp, cap, cols, host=False)
    jm = jnp.asarray(mask)
    js = jnp.asarray(slot)

    outs = {}
    for impl in ("scatter", "sorted", "runs"):
        # "runs" at nslots=11 exercises the broadcast-compare lowering
        al._FORCE_SEGMENT_IMPL = impl
        try:
            r = al.dense_agg_states(ctx, jm, aggs, js, nslots, cap)
        finally:
            al._FORCE_SEGMENT_IMPL = None
        outs[impl] = jax.device_get(r)
    a = outs["scatter"]
    assert a["present"][nslots - 1] == 0 and a["present"][nslots - 2] == 0
    for other in ("sorted", "runs"):
        b = outs[other]
        np.testing.assert_array_equal(a["present"], b["present"])
        for st_a, st_b, agg in zip(a["states"], b["states"], aggs):
            for s_a, s_b in zip(st_a, st_b):
                if s_a.dtype.kind == "f":
                    np.testing.assert_allclose(s_a, s_b, rtol=1e-12)
                else:
                    np.testing.assert_array_equal(s_a, s_b)


@pytest.mark.parametrize("shape", ["keyed", "global", "wide_keys"])
def test_sort_agg_sorted_matches_scatter(shape):
    """sort_agg_body's TPU lowering (segmented scans over the already
    sorted rows) must match the scatter lowering: packed and multisort
    key branches, null group keys, masked rows, all agg kinds."""
    import jax
    import jax.numpy as jnp
    import tidb_tpu.copr.agg_lowering as al
    from tidb_tpu.expression import EvalCtx
    from tidb_tpu.expression.expr import Column
    from tidb_tpu.types.field_type import new_bigint_type, new_double_type

    rng = np.random.RandomState(11)
    cap = 2048
    group_bucket = 64
    mask = rng.rand(cap) < 0.8
    gvals = rng.randint(0, 9, cap).astype(np.int64)
    if shape == "wide_keys":
        # keys spanning ~2^62 force the multisort lax.cond branch
        gvals = np.where(gvals < 4, gvals - (1 << 61), gvals + (1 << 61))
    gnull = rng.rand(cap) < 0.15
    ints = rng.randint(-100, 100, cap).astype(np.int64)
    flts = rng.randn(cap)
    fnull = rng.rand(cap) < 0.2

    class A:
        def __init__(self, name, args):
            self.name, self.args, self.distinct = name, args, False
    ci = Column(1, new_bigint_type())
    cf = Column(2, new_double_type())
    aggs = [A("count", []), A("sum", [ci]), A("avg", [cf]),
            A("min", [cf]), A("max", [ci]), A("first_row", [ci]),
            A("count", [cf])]
    group_items = [] if shape == "global" else [Column(0, new_bigint_type())]
    cols = {0: (jnp.asarray(gvals), jnp.asarray(gnull), None),
            1: (jnp.asarray(ints), None, None),
            2: (jnp.asarray(flts), jnp.asarray(fnull), None)}
    ctx = EvalCtx(jnp, cap, cols, host=False)
    jm = jnp.asarray(mask)

    outs = {}
    for impl in ("scatter", "sorted"):
        al._FORCE_SEGMENT_IMPL = impl
        try:
            r = al.sort_agg_body(ctx, jm, group_items, aggs, cap,
                                 group_bucket)
        finally:
            al._FORCE_SEGMENT_IMPL = None
        outs[impl] = jax.device_get(r)
    a, b = outs["scatter"], outs["sorted"]
    ng = int(a["ngroups"])
    assert ng == int(b["ngroups"])
    for ka, kb in zip(a["keys"], b["keys"]):
        np.testing.assert_array_equal(ka[:ng], kb[:ng])
    for st_a, st_b in zip(a["states"], b["states"]):
        for s_a, s_b in zip(st_a, st_b):
            if s_a.dtype.kind == "f":
                np.testing.assert_allclose(s_a[:ng], s_b[:ng], rtol=1e-12)
            else:
                np.testing.assert_array_equal(s_a[:ng], s_b[:ng])


def _merge_partials(res, aggs, nkeys):
    """Fold a sort-layout agg result into {key_tuple: merged_states} —
    the host-side merge the executor applies across partitions, used
    here to compare group orders and duplicate-key partials (the runs
    lowering emits one partial per contiguous run)."""
    ng = int(res["ngroups"])
    groups = {}
    for j in range(ng):
        key = tuple(
            (bool(res["key_nulls"][i][j]),
             None if res["key_nulls"][i][j] else int(res["keys"][i][j]))
            for i in range(nkeys))
        st = groups.get(key)
        if st is None:
            groups[key] = [[s[j] for s in stt] for stt in res["states"]]
            continue
        for (acc, stt, a) in zip(st, res["states"], aggs):
            cnt_new = stt[-1][j] if len(stt) > 1 else stt[0][j]
            if a.name == "count":
                acc[0] += stt[0][j]
            elif a.name in ("sum", "avg"):
                acc[0] += stt[0][j]
                acc[1] += stt[1][j]
            elif a.name == "min":
                if cnt_new > 0:
                    acc[0] = min(acc[0], stt[0][j]) if acc[1] > 0 \
                        else stt[0][j]
                acc[1] += cnt_new
            elif a.name == "max":
                if cnt_new > 0:
                    acc[0] = max(acc[0], stt[0][j]) if acc[1] > 0 \
                        else stt[0][j]
                acc[1] += cnt_new
            elif a.name == "first_row":
                if acc[1] == 0 and cnt_new > 0:
                    acc[0] = stt[0][j]
                acc[1] += cnt_new
    return groups


@pytest.mark.parametrize("shape", ["clustered", "unclustered", "global"])
def test_runs_agg_matches_scatter(shape):
    """The runs lowering (contiguous-run partials: cumsum + boundary
    gathers, no sort, no scatter) must agree with the scatter oracle
    after the host partial merge — clustered keys (one run per group),
    unclustered keys (many duplicate-key partials), NULL keys, masked
    runs, all agg kinds."""
    import jax
    import jax.numpy as jnp
    import tidb_tpu.copr.agg_lowering as al
    from tidb_tpu.expression import EvalCtx
    from tidb_tpu.expression.expr import Column
    from tidb_tpu.types.field_type import new_bigint_type, new_double_type

    rng = np.random.RandomState(23)
    cap = 2048
    mask = rng.rand(cap) < 0.75
    gvals = rng.randint(0, 40, cap).astype(np.int64)
    gnull = rng.rand(cap) < 0.1
    if shape == "clustered":
        order = np.lexsort((gvals, gnull))
        gvals, gnull = gvals[order], gnull[order]

    class A:
        def __init__(self, name, args):
            self.name, self.args, self.distinct = name, args, False
    ci = Column(1, new_bigint_type())
    cf = Column(2, new_double_type())
    aggs = [A("count", []), A("sum", [ci]), A("avg", [cf]),
            A("min", [cf]), A("max", [ci]), A("first_row", [ci]),
            A("count", [cf])]
    group_items = [] if shape == "global" else \
        [Column(0, new_bigint_type())]
    nkeys = len(group_items)
    ints = rng.randint(-100, 100, cap).astype(np.int64)
    flts = rng.randn(cap)
    fnull = rng.rand(cap) < 0.2
    cols = {0: (jnp.asarray(gvals), jnp.asarray(gnull), None),
            1: (jnp.asarray(ints), None, None),
            2: (jnp.asarray(flts), jnp.asarray(fnull), None)}
    ctx = EvalCtx(jnp, cap, cols, host=False)
    jm = jnp.asarray(mask)

    outs = {}
    for impl in ("scatter", "runs"):
        bucket = cap if impl == "runs" else 64
        al._FORCE_SEGMENT_IMPL = impl
        try:
            r = al.sort_agg_body(ctx, jm, group_items, aggs, cap, bucket)
        finally:
            al._FORCE_SEGMENT_IMPL = None
        outs[impl] = jax.device_get(r)
    if shape == "clustered":
        # one run per group: no duplicate partials even pre-merge
        assert int(outs["runs"]["ngroups"]) == \
            int(outs["scatter"]["ngroups"])
    ga = _merge_partials(outs["scatter"], aggs, nkeys)
    gb = _merge_partials(outs["runs"], aggs, nkeys)
    assert set(ga) == set(gb)
    for key, st_a in ga.items():
        st_b = gb[key]
        for sa, sb, a in zip(st_a, st_b, aggs):
            for x, y in zip(sa, sb):
                if getattr(x, "dtype", np.int64) == np.float64 or \
                        isinstance(x, float):
                    np.testing.assert_allclose(x, y, rtol=1e-9)
                else:
                    assert int(x) == int(y), (key, a.name)
