"""The stage catalogue (utils/kernel_stages.py): programs that ran
under a profiler session are noted at the dispatch seam, catalogued by
the first metrics read after the session, and served as two families;
a process never profiled notes nothing and serves neither."""
import jax
import jax.numpy as jnp
import pytest

from tidb_tpu.bench.tpch import Q1, Q3, Q6, load_tpch
from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import kernel_stages as ks
from tidb_tpu.utils import metrics, phase

FAMILIES = ("tidb_tpu_kernel_stage_ops",
            "tidb_tpu_kernel_stage_catalogue_total")
SUMMARY = ("select metrics_name, labels, sum_value from "
           "information_schema.metrics_summary")


@pytest.fixture(autouse=True)
def _fresh_catalogue():
    ks.reset()
    yield
    ks.reset()


@pytest.fixture(scope="module")
def tpch():
    tk = TestKit()
    load_tpch(tk, sf=0.003, seed=11)
    for q in (Q6, Q3, Q1):
        tk.must_query(q)                        # programs built
    return tk


def served(tk):
    """-> [(family, {label: value}, value)] of the two families, as
    metrics_summary answers."""
    from tidb_tpu.utils.metrics import _parse_labelset
    return [(name, _parse_labelset(labels, [], 0), float(value))
            for name, labels, value in tk.must_query(SUMMARY).rows
            if name in FAMILIES]


def xla_cache():
    """tidb_tpu_xla_cache_total's two samples (0 where none yet)."""
    return [metrics.XLA_CACHE.labels(r).value for r in ("hit", "miss")]


def outcomes(rows):
    return {lb["outcome"]: int(v) for name, lb, v in rows
            if name == FAMILIES[1]}


def test_no_session_notes_nothing_and_serves_neither(tpch):
    for _ in range(17):
        for q in (Q6, Q3, Q1):
            tpch.must_query(q)
    assert ks.noted() == 0
    assert served(tpch) == []
    page = metrics.REGISTRY.expose()
    assert not any(f in page for f in FAMILIES)


def test_noted_under_a_session_catalogued_by_the_first_read_after(
        tpch, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    builds = "select sum(kernel_builds) from information_schema.tidb_top_sql"

    def built():
        return float(tpch.must_query(builds).rows[0][0] or 0)
    built_before = built()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):                      # once a signature
            for q in (Q6, Q3, Q1):
                tpch.must_query(q)
        noted = ks.noted()
        # a read inside the session materialises nothing
        assert served(tpch) == [] and ks.noted() == noted
    finally:
        jax.profiler.stop_trace()
    assert noted >= 3                           # one a statement at least
    xla = xla_cache()
    rows = served(tpch)                         # the first read after
    assert ks.noted() == 0
    got = outcomes(rows)
    assert got.pop("built") == noted
    assert got.pop("cache_miss", 0) in (0, noted)  # in memory, or not
    assert got == {}
    # the look-ups are not the statements' work
    assert xla_cache() == xla
    assert built() == built_before
    ops = [(lb, v) for name, lb, v in rows if name == FAMILIES[0]]
    assert all(lb["program"].startswith("jit_tidb_") for lb, _ in ops)
    for lb, v in ops:                           # value = instructions
        assert v == len(lb["ops"].split()) > 0
    # Q3's program: a filter on the fact table, a probed dimension, a
    # grouped aggregate
    by_entry = {}
    for lb, _ in ops:
        by_entry.setdefault((lb["program"], lb["entry"]),
                            set()).add(lb["stage"])
    assert any({"scan_filter", "dim_probe", "group_agg"} <= stages
               for stages in by_entry.values()), by_entry
    # the second read finds nothing new to build
    assert outcomes(served(tpch)) == outcomes(rows)


def test_a_look_up_that_compiles_is_counted_and_metered_apart():
    """A program jax no longer holds in memory is looked up in the
    persistent cache and, on a miss, compiled: `cache_miss`, with
    tidb_tpu_xla_cache_total as it was."""
    def tidb_never_run(x):
        with jax.named_scope("group_agg"):
            return jnp.cumsum(x) * 3
    jitted = jax.jit(tidb_never_run)
    before = xla_cache()
    ks._catalogue("test", jitted,
                  (jax.ShapeDtypeStruct((1024,), jnp.int64),), {})
    snap = metrics.REGISTRY.snapshot()
    assert xla_cache() == before
    assert snap['tidb_tpu_kernel_stage_catalogue_total{outcome="built"}'] \
        == 1
    assert snap[
        'tidb_tpu_kernel_stage_catalogue_total{outcome="cache_miss"}'] == 1
    ops = [lb for _n, lb, _v in ks.STAGE_OPS.sample_rows()]
    assert {lb["program"] for lb in ops} == {"jit_tidb_never_run"}
    assert "group_agg" in {lb["stage"] for lb in ops}


def test_a_lowering_that_raises_is_counted_and_the_read_answers(
        monkeypatch):
    class NoLowering:
        def __call__(self, x):
            return x

        def lower(self, *a, **kw):
            raise RuntimeError("no lowering for this one")

    tk = TestKit()
    kern = phase.timed_kernel("broken", NoLowering())
    good = phase.timed_kernel("fine", jax.jit(lambda x: x + 1))
    monkeypatch.setattr(ks, "session_active", lambda: True)
    kern(jnp.zeros(8)), good(jnp.zeros(8)), good(jnp.zeros(8))
    assert ks.noted() == 2
    good(jnp.zeros(16))                         # another signature
    assert ks.noted() == 3
    monkeypatch.setattr(ks, "session_active", lambda: False)
    got = outcomes(served(tk))
    assert got["lower_failed"] == 1 and got["built"] == 2
    assert ks.noted() == 0


def test_over_budget_programs_are_counted_and_dropped(monkeypatch):
    kern = phase.timed_kernel("fine", jax.jit(lambda x: x + 1))
    monkeypatch.setattr(ks, "session_active", lambda: True)
    for n in (8, 16, 32):
        kern(jnp.zeros(n))
    monkeypatch.setattr(ks, "session_active", lambda: False)
    monkeypatch.setattr(ks, "BUDGET_S", -1.0)
    ks.materialise()
    assert ks.noted() == 0
    assert ks.CATALOGUE.labels("over_budget").value == 3
    assert list(ks.STAGE_OPS.sample_rows()) == []


# a compiled module's text as XLA:TPU prints it (PR 36's chip run, cut
# down by hand to one instruction of each kind)
RECORDED = '''\
HloModule jit_tidb_fused_posruns, is_scheduled=true, entry_computation_layout={(s64[4194304]{0:T(1024)})->(s64[]{:T(128)})}, frontend_attributes={xla.sdy.meshes={empty_mesh = #sdy.mesh<[]>}}

%fused_computation.81 (param_0.1: u32[4194304], param_1.2: pred[4194304]) -> pred[4194304] {
  %param_0.1 = u32[4194304]{0:T(1024)S(1)} parameter(0)
  %param_1.2 = pred[4194304]{0:T(1024)(128)(4,1)} parameter(1)
  %compare.5 = pred[4194304]{0:T(1024)(128)(4,1)} compare(%param_0.1, %param_0.1), direction=LT, metadata={op_name="jit(tidb_fused_posruns)/scan_filter/lt" stack_frame_id=12}
  %and.3 = pred[4194304]{0:T(1024)(128)(4,1)} and(%compare.5, %param_1.2), metadata={op_name="jit(tidb_fused_posruns)/scan_filter/and" stack_frame_id=13}
  ROOT %convert.9 = pred[4194304]{0:T(1024)(128)(4,1)} convert(%and.3), metadata={op_name="jit(tidb_fused_posruns)/compact/convert_element_type" stack_frame_id=14}
}

%fused_computation.9 (param_0.7: u32[4194304]) -> u32[4194304] {
  %param_0.7 = u32[4194304]{0:T(1024)} parameter(0)
  ROOT %gather.2 = u32[4194304]{0:T(1024)S(1)} gather(%param_0.7, %param_0.7), offset_dims={}, metadata={op_name="jit(tidb_fused_posruns)/dim_probe/gather" stack_frame_id=40}
}

%wide.region_6.18.clone.sunk (wide.arg_tuple.2: (u32[], s32[1024])) -> (u32[], s32[1024]) {
  %wide.arg_tuple.2 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) parameter(0)
  %get-tuple-element.1 = u32[]{:T(128)} get-tuple-element(%wide.arg_tuple.2), index=0
  %fusion.121 = s32[1024]{0:T(1024)S(1)} fusion(%get-tuple-element.1), kind=kCustom, calls=%fused_computation.9, metadata={op_name="jit(tidb_fused_posruns)/group_agg/jit(searchsorted)/while/body/gather" stack_frame_id=73}
  ROOT %tuple.3 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) tuple(%get-tuple-element.1, %fusion.121)
}

%wide.region_7.19.clone (wide.arg_tuple.15: (u32[], s32[1024])) -> pred[] {
  %wide.arg_tuple.15 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) parameter(0)
  %get-tuple-element.4 = u32[]{:T(128)} get-tuple-element(%wide.arg_tuple.15), index=0
  ROOT %compare.7 = pred[]{:T(128)} compare(%get-tuple-element.4, %get-tuple-element.4), direction=LT
}

ENTRY %main.26 (fjc_20__0_.1: s64[4194304]) -> (s64[]) {
  %fjc_20__0_.1 = s64[4194304]{0:T(1024)} parameter(0)
  %custom-call.4 = u32[4194304]{0:T(1024)} custom-call(%fjc_20__0_.1), custom_call_target="X64SplitLow"
  %copy-start.11 = (u32[4194304]{0:T(1024)}, u32[4194304]{0:T(1024)S(1)}, u32[]{:S(2)}) copy-start(%custom-call.4)
  %copy-done.11 = u32[4194304]{0:T(1024)} copy-done(%copy-start.11)
  %fusion.6 = u32[4194304]{0:T(1024)S(1)} fusion(%copy-done.11), kind=kCustom, calls=%fused_computation.9, metadata={op_name="jit(tidb_fused_posruns)/dim_probe/gather" stack_frame_id=40}
  %convert_bitcast_fusion = pred[4194304]{0:T(1024)(128)(4,1)} fusion(%copy-done.11, %fusion.6), kind=kLoop, calls=%fused_computation.81, backend_config={"flag_configs":[],"window_config":{"kernel_window_bounds":[],"output_window_bounds":["256"]}}
  %tuple.309 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) tuple(%custom-call.4, %fusion.6)
  %while.15 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) while(%tuple.309), condition=%wide.region_7.19.clone, body=%wide.region_6.18.clone.sunk, backend_config={"known_trip_count":{"n":"10"}}
  %while.16 = (u32[]{:T(128)}, s32[1024]{0:T(1024)S(1)}) while(%while.15), condition=%wide.region_7.19.clone, body=%wide.region_6.18.clone.sunk, metadata={op_name="jit(tidb_fused_posruns)/compact/jit(searchsorted)/vmap()/while" stack_frame_id=73}
  %get-tuple-element.9 = u32[]{:T(128)} get-tuple-element(%while.16), index=0
  %bitcast.2 = s64[]{:T(128)} bitcast(%get-tuple-element.9)
  ROOT %tuple.400 = (s64[]{:T(128)}) tuple(%bitcast.2)
}

FileNames
1 "/root/repo/tidb_tpu/copr/pipeline.py"
'''


def test_parser_on_a_recorded_text():
    module, stages = ks.parse_stages(RECORDED)
    assert module == "jit_tidb_fused_posruns"
    assert stages == {
        "custom-call.4": "none",            # no scope, calls nothing
        "copy-start.11": "none",
        "copy-done.11": "none",
        "fusion.6": "dim_probe",            # its own op_name
        # no op_name: two of its computation's three are scan_filter's
        "convert_bitcast_fusion": "scan_filter",
        # no op_name: its body's one named instruction
        "while.15": "group_agg",
        "while.16": "compact",              # its own, not its body's
        "fusion.121": "group_agg",          # runs inside the whiles
        "compare.7": "none"}
