"""The four readers that came with `tpch-sf1-set2.power` (PR 38):
`searched_probe_share`, `matdim_ms_per_query`,
`dict_filter_ms_per_query` and `set2_bytes_roofline`: exact arithmetic
on counter snapshots and a traced window made by hand, what each
returns for a program that lacks its counter or span (the parent of
PR 38: nothing, and no exception), and `UNAVOIDABLE_BYTES` of the data
set over generated tables. Run: python3 -m pytest
benchmark/tests/test_set2_readers.py (needs no chip)."""
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import counters                                             # noqa: E402
import kernel_stages as ks                                  # noqa: E402
import trace_reduce as tr                                   # noqa: E402

OFFSET = 100        # host time = device time + OFFSET
PROBES = "tidb_tpu_fused_dim_probe_total"
PEAK = 819e9


def load(package, name):
    spec = importlib.util.spec_from_file_location(
        f"set2_{package}_{name}", os.path.join(BENCH, package, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(name):
    return load("layer_metrics", name).read


def probes(join, mode):
    return (PROBES, f'join="{join}",mode="{mode}"')


def growth(before, after):
    return counters.Growth({"metrics": dict(before), "top_sql": {}},
                           {"metrics": dict(after), "top_sql": {}})


def test_searched_probe_share_is_the_search_modes_share_of_the_growth():
    before = {probes("inner", "direct"): 100.0, probes("inner", "search"): 7.0}
    after = {probes("inner", "direct"): 160.0,      # + 60
             probes("inner", "folded"): 20.0,       # + 20
             probes("inner", "search"): 17.0,       # + 10
             probes("semi", "search"): 10.0,        # + 10
             probes("left", "matdim"): 0.0,
             ("tidb_tpu_dim_fold_total", 'outcome="folded"'): 99.0}
    read = reader("searched_probe_share")
    assert read({"growth": growth(before, after)}) == 100.0 * 20 / 100
    # nothing probed in the window, or a program without the counter
    assert read({"growth": growth(after, after)}) is None
    assert read({"growth": growth({}, {})}) is None


def host(name, start, end):
    return (name, start + OFFSET, end + OFFSET)


def op(name, start, end):
    return (f"%{name} = s64[8]{{0}} fusion(s64[8]{{0}} %p), kind=kLoop",
            start, end)


class Tables(dict):
    """What `UNAVOIDABLE_BYTES` is handed: anything."""


class DataSet:
    @staticmethod
    def UNAVOIDABLE_BYTES(tables):
        return {"q17": 819_000, "q9": 2 * 819_000}   # 1,000 and 2,000 ns


def traced(metrics):
    """Window [0, 10000) on the device's clock, one device: a q17 whole
    inside (a `matdim` of 300 ns that holds a `dict_filter` of 100, so
    200 of self time), a q9 whole inside (`matdim` 50), a q9 that ends
    after the window (its `matdim` is not counted). The device is busy
    5,000 ns inside the window."""
    a = [("command", 1000, 1100), ("execute", 1100, 1200),
         ("matdim", 1200, 1300), ("dict_filter", 1300, 1400),
         ("matdim", 1400, 1500), ("bind", 1500, 1600),
         ("fetch", 1600, 3000),
         ("command", 4000, 4100), ("matdim", 4100, 4150),
         ("fetch", 4150, 6000),
         ("command", 9000, 9100), ("matdim", 9100, 9300)]
    hosts = [host("bench:traced_window", 0, 10000),
             host("stmt:q17", 1000, 3000), host("stmt:q9", 4000, 6000),
             host("stmt:q9", 9000, 11000)]
    hosts += [host("tidb:" + n, s, e) for n, s, e in a]
    trace = {"devices": {0: [op("fusion.1", 1600, 2900),       # 1,300
                             op("fusion.2", 4200, 5900),       # 1,700
                             op("fusion.2", 9200, 12000)]},    # 800 inside
             "modules": {0: [("jit_f(1)", 1590, 2910),
                             ("jit_f(1)", 4190, 5910),
                             ("jit_f(1)", 9190, 12010)]},
             "host": sorted(hosts, key=lambda e: e[1])}
    busy = tr.busy(trace, 0, 10000)
    after = {(ks.FAMILY, 'entry="0",ops="fusion.1 fusion.2",'
              'program="jit_f",stage="dim_probe"'): 2.0}
    after.update(metrics)
    return {"trace": {"trace": trace, "lo": 0, "hi": 10000,
                      "offset_ns": OFFSET, "window_s": 1e-5,
                      "busy_s_by_device":
                      {n: tr.length(iv) / 1e9 for n, iv in busy.items()}},
            "growth": growth({}, after), "dataset": DataSet,
            "tables": Tables(),
            "device": {"kind": "TPU v5 lite", "count": 1},
            "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": PEAK}}}


MATDIM = ("tidb_tpu_matdim_total", 'outcome="hit"')
DICT = ("tidb_tpu_dict_filter_total", 'outcome="build"')


def test_matdim_and_dict_filter_self_time_of_the_counted_statements():
    run = traced({MATDIM: 3.0, DICT: 1.0})
    # two statements lie whole inside: (100 + 100 + 50) ns of `matdim`
    assert reader("matdim_ms_per_query")(run) == 250 / 2 / 1e6
    assert reader("dict_filter_ms_per_query")(run) == 100 / 2 / 1e6


def test_a_window_without_a_dict_filter_span_reads_zero_not_nothing():
    """Every predicate sat under a cached fold or aggregate dimension:
    the program has the span and the window none of it."""
    run = traced({MATDIM: 3.0, DICT: 0.0})
    run["trace"]["trace"]["host"] = [
        e for e in run["trace"]["trace"]["host"]
        if e[0] != "tidb:dict_filter"]
    assert reader("dict_filter_ms_per_query")(run) == 0.0


def test_a_program_without_the_counters_reports_nothing():
    run = traced({})
    assert reader("matdim_ms_per_query")(run) is None
    assert reader("dict_filter_ms_per_query")(run) is None
    assert reader("searched_probe_share")(run) is None


def test_set2_bytes_roofline_over_the_statements_that_end_inside():
    run = traced({})
    # q17 and the first q9 end inside: 1,000 + 2,000 ns of need over
    # the window's 1,300 + 1,700 + 800 ns of device time
    assert abs(reader("set2_bytes_roofline")(run) -
               100.0 * 3000 / 3800) < 1e-9


def test_set2_bytes_roofline_reads_nothing_where_it_has_nothing_to_read():
    read = reader("set2_bytes_roofline")
    run = traced({})
    run["dataset"] = object()               # a data set without the bytes
    assert read(run) is None
    run = traced({})
    run["growth"] = growth({}, {})          # no stage catalogue: no view
    assert read(run) is None
    run = traced({})
    run["trace"] = None                     # an untraced run
    assert read(run) is None
    run = traced({})
    run["trace"]["trace"]["host"].append(host("stmt:q1", 7000, 8000))
    assert read(run) is None                # another data set's statement


def test_unavoidable_bytes_are_the_read_columns_over_the_rows():
    ds = load("datasets", "tpch_set2")
    tables = ds.generate(0.01, 38)
    need = ds.UNAVOIDABLE_BYTES(tables)
    assert set(need) == set(ds.STATEMENTS)
    rows = {t: len(next(iter(c.values()))) for t, c in tables.items()
            if t != ds.DICTIONARIES}
    assert need["q17"] == rows["lineitem"] * 24 + rows["part"] * 16
    assert need["q13"] == rows["customer"] * 8 + rows["orders"] * 12
    assert need["q9"] == max(need.values())
    # a function of the data alone: another seed, the same sizes
    assert ds.UNAVOIDABLE_BYTES(ds.generate(0.01, 39)) == need
