"""Unified metrics registry + Top SQL (utils/metrics): typed labeled
instruments, Prometheus text exposition via the status port, strict
parser + histogram invariants, per-digest device-time attribution, and
the recording-overhead microbench (the fast mode of
scripts/metrics_smoke.py)."""
import time

import pytest

from tidb_tpu.testkit import TestKit
from tidb_tpu.utils import metrics, failpoint


# ---- registry unit ---------------------------------------------------

def test_counter_labels_and_snapshot():
    r = metrics.Registry()
    c = r.counter("t_requests_total", "requests", ("kind",))
    c.labels("read").inc()
    c.labels("read").inc(2)
    c.labels("write").inc()
    snap = r.snapshot()
    assert snap['t_requests_total{kind="read"}'] == 3
    assert snap['t_requests_total{kind="write"}'] == 1
    # get-or-create returns the same instrument; kind clash raises
    assert r.counter("t_requests_total") is c
    with pytest.raises(ValueError):
        r.gauge("t_requests_total")
    with pytest.raises(ValueError):
        c.labels("a", "b")                  # label arity enforced
    with pytest.raises(ValueError):
        c.labels("read").inc(-1)            # counters only go up
    r.reset()
    assert r.snapshot() == {}


def test_gauge_set_inc_dec():
    r = metrics.Registry()
    g = r.gauge("t_depth", "queue depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert r.snapshot()["t_depth"] == 4


def test_histogram_buckets_and_exposition_invariants():
    r = metrics.Registry()
    h = r.histogram("t_lat_seconds", "latency", ("op",),
                    buckets=[0.001, 0.01, 0.1])
    for v in (0.0005, 0.005, 0.05, 0.5, 0.0005):
        h.labels("get").observe(v)
    fams, errs = metrics.parse_text(r.expose())
    assert not errs, errs
    fam = fams["t_lat_seconds"]
    assert fam["type"] == "histogram"
    by = {(n, lb.get("le")): v for n, lb, v in fam["samples"]}
    assert by[("t_lat_seconds_bucket", "0.001")] == 2
    assert by[("t_lat_seconds_bucket", "0.01")] == 3
    assert by[("t_lat_seconds_bucket", "0.1")] == 4
    assert by[("t_lat_seconds_bucket", "+Inf")] == 5
    assert by[("t_lat_seconds_count", None)] == 5
    assert abs(by[("t_lat_seconds_sum", None)] - 0.556) < 1e-9


def test_disabled_registry_records_nothing():
    r = metrics.Registry()
    c = r.counter("t_n", "")
    r.enabled = False
    c.inc(7)
    r.histogram("t_h", "").observe(1.0)
    r.enabled = True
    assert r.snapshot().get("t_n", 0) == 0


def test_name_sanitization():
    assert metrics.sanitize_name("lsm flushes/total") == \
        "lsm_flushes_total"
    assert metrics.sanitize_name("9lives") == "_9lives"
    assert metrics.sanitize_name("ok_name:x") == "ok_name:x"


def test_exponential_buckets():
    assert metrics.exponential_buckets(1, 2, 4) == [1, 2, 4, 8]
    with pytest.raises(ValueError):
        metrics.exponential_buckets(0, 2, 4)


def test_parser_rejects_malformed():
    bad = "\n".join([
        "# TYPE ok counter",
        "ok 1",
        "bad-name 2",                        # invalid charset
        'ok{unterminated="x 3',              # malformed labels
        "no_type_declared 4",                # sample without TYPE
        "ok 5",                              # duplicate series
    ])
    _, errs = metrics.parse_text(bad)
    assert len(errs) >= 4


def test_parser_catches_histogram_invariant_violation():
    text = "\n".join([
        "# TYPE h histogram",
        'h_bucket{le="1"} 5',
        'h_bucket{le="+Inf"} 4',             # decreasing cumulative
        "h_sum 1.0",
        "h_count 9",                         # != +Inf bucket
    ])
    _, errs = metrics.parse_text(text)
    assert any("decrease" in e for e in errs)
    assert any("_count" in e for e in errs)


def test_scrape_races_recording_without_tearing():
    """A /metrics scrape must survive concurrent first-use label
    creation and mid-observe histogram state (the strict parser treats
    a torn _count != +Inf bucket as a violation)."""
    import threading
    r = metrics.Registry()
    h = r.histogram("t_race_seconds", "", ("op",), buckets=[0.01, 0.1])
    c = r.counter("t_race_total", "", ("op",))
    stop = threading.Event()

    def hammer(i):
        n = 0
        while not stop.is_set():
            h.labels(f"op{n % 50}_{i}").observe(0.05)
            c.labels(f"op{n % 50}_{i}").inc()
            n += 1

    threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
               for i in range(3)]
    for t in threads:
        t.start()
    try:
        deadline = time.time() + 0.5
        while time.time() < deadline:
            _, errs = metrics.parse_text(r.expose())
            assert not errs, errs[:5]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


def test_top_sql_ring_bounded_eviction():
    ring = metrics.TopSQL(capacity=2)
    ring.record("d1", "q1", 10.0, {"dispatch_s": 100.0})
    ring.record("d2", "q2", 10.0, {"dispatch_s": 1.0})
    ring.record("d3", "q3", 10.0, {"dispatch_s": 50.0})  # evicts d2
    digests = {e["digest"] for e in ring.rows()}
    assert digests == {"d1", "d3"}
    assert ring.rows()[0]["digest"] == "d1"  # ordered by device time


# ---- end to end through the SQL/HTTP surfaces ------------------------

@pytest.fixture(scope="module")
def mtk():
    tk = TestKit()
    tk.must_exec("create table mt (a int, b int)")
    tk.must_exec("insert into mt values " +
                 ",".join(f"({i},{i % 7})" for i in range(512)))
    return tk


def _scrape(domain):
    import urllib.request
    from tidb_tpu.server.status import start_status_server
    st = start_status_server(domain, port=0)
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{st.bound_port}/metrics", timeout=10)
        return resp.headers.get("Content-Type"), resp.read().decode()
    finally:
        st.shutdown()


def test_metrics_endpoint_prometheus_exposition(mtk):
    for _ in range(2):
        mtk.must_query("select sum(a) from mt where b > 1")
    mtk.domain.inc_metric("weird name+chars/1", 2)   # must be sanitized
    ctype, body = _scrape(mtk.domain)
    assert ctype == "text/plain; version=0.0.4"
    fams, errs = metrics.parse_text(body)
    assert not errs, errs[:10]
    # the labeled statement-latency histogram with consistent series
    fam = fams["tidb_tpu_query_duration_seconds"]
    assert fam["type"] == "histogram"
    sel = [(n, lb, v) for n, lb, v in fam["samples"]
           if lb.get("stmt_type") == "select"]
    assert sel, "no stmt_type=select series"
    count = next(v for n, lb, v in sel if n.endswith("_count"))
    inf = next(v for n, lb, v in sel if lb.get("le") == "+Inf")
    assert count == inf and count >= 2
    # sanitized legacy name, scrapable page
    assert "tidb_tpu_weird_name_chars_1 2" in body
    # runtime gauges sampled at scrape time
    assert fams["tidb_tpu_connections"]["samples"][0][2] >= 1
    assert fams["tidb_tpu_uptime_seconds"]["samples"][0][2] > 0


def test_top_sql_device_attribution(mtk):
    for _ in range(3):
        mtk.must_query("select sum(a), count(*) from mt where b > 2")
    rows = mtk.must_query(
        "select sql_text, exec_count, sum_device_ms, sum_host_ms, "
        "dispatches from information_schema.tidb_top_sql "
        "order by sum_device_ms desc").rows
    mine = [r for r in rows if "count ( * ) from mt" in r[0]]
    assert mine, rows[:5]
    text, cnt, dev_ms, host_ms, dispatches = mine[0]
    assert cnt >= 3
    # CPU backend still dispatches XLA kernels: device time (or the
    # host twin's time) must be attributed, never silently dropped
    assert dev_ms > 0 or host_ms > 0
    assert dev_ms + host_ms <= 1e7          # sane magnitude (ms)


def test_copr_and_kernel_cache_instruments(mtk):
    mtk.must_query("select max(a) from mt where b = 3")
    snap = metrics.REGISTRY.snapshot()
    backends = [k for k in snap
                if k.startswith("tidb_tpu_copr_dispatch_seconds_count")]
    assert backends, "copr dispatch histogram never observed"
    hits = snap.get('tidb_tpu_kernel_cache_total{result="hit"}', 0)
    misses = snap.get('tidb_tpu_kernel_cache_total{result="miss"}', 0)
    assert hits + misses > 0


def test_device_fallback_labeled_and_per_digest(mtk):
    failpoint.enable("device_guard/copr/agg", "error:grant_lost")
    failpoint.enable("device_guard/copr/filter", "error:grant_lost")
    try:
        r = mtk.must_query("select sum(b) from mt where a > 5")
        assert r.rows[0][0] is not None
    finally:
        failpoint.disable_all()
    snap = metrics.REGISTRY.snapshot()
    labeled = {k: v for k, v in snap.items()
               if k.startswith("tidb_tpu_device_fallback_total{")}
    assert any('family="copr"' in k and 'error_class="grant_lost"' in k
               for k in labeled), snap
    # per-digest attribution: the fallback lands on the statement
    rows = mtk.must_query(
        "select fallback_count from information_schema"
        ".statements_summary where digest_text like "
        "'select sum ( b ) from mt%'").rows
    assert rows and rows[0][0] >= 1
    rows = mtk.must_query(
        "select fallback_count from information_schema.tidb_top_sql "
        "where sql_text like 'select sum ( b ) from mt%'").rows
    assert rows and rows[0][0] >= 1


def test_slow_query_digest_joins_statements_summary(mtk):
    mtk.must_exec("set @@tidb_slow_log_threshold = 0")
    try:
        mtk.must_query("select min(a) from mt")
    finally:
        mtk.must_exec("set @@tidb_slow_log_threshold = 300")
    rows = mtk.must_query(
        "select s.digest, s.is_internal, m.exec_count "
        "from information_schema.slow_query s "
        "join information_schema.statements_summary m "
        "on s.digest = m.digest where s.query like '%min(a)%'").rows
    assert rows, "slow_query rows do not join statements_summary"
    digest, is_internal, exec_count = rows[-1]
    assert digest and is_internal == 0 and exec_count >= 1


def test_metrics_summary_exposes_registry_samples(mtk):
    mtk.must_query("select count(*) from mt")
    rows = mtk.must_query(
        "select metrics_name, labels from information_schema"
        ".metrics_summary where metrics_name = "
        "'tidb_tpu_query_duration_seconds_count'").rows
    assert any("stmt_type=" in lb for _n, lb in rows)


def test_concurrent_statements_both_attributed(mtk):
    """Phase state is thread-local: two overlapping statements on
    different connections must BOTH land in the duration histogram and
    Top SQL, each under its own digest."""
    import threading
    tks = [mtk.new_session() for _ in range(2)]
    barrier = threading.Barrier(2)
    errs = []

    def run(i, tk):
        try:
            barrier.wait(timeout=10)
            for _ in range(3):
                tk.must_query(
                    f"select sum(a + {i}), min(b) from mt where b > {i}")
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i, tk))
               for i, tk in enumerate(tks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs, errs
    rows = mtk.must_query(
        "select sql_text, exec_count from information_schema"
        ".tidb_top_sql where sql_text like "
        "'select sum ( a + ? )%'").rows
    assert len(rows) == 1 and rows[0][1] == 6, rows


def test_plan_feedback_and_drift_histogram(mtk):
    """Fast mode of the metrics_smoke plan-feedback gate: after real
    queries, tidb_plan_feedback is non-empty with finite drift, the
    cardinality-drift histogram observed, and tidb_top_sql carries the
    digest-level drift summary."""
    for _ in range(2):
        mtk.must_query("select b, sum(a) from mt group by b order by b")
    rows = mtk.must_query(
        "select op, calls, avg_act_rows, max_drift, mean_drift, route "
        "from information_schema.tidb_plan_feedback "
        "where sql_text like '%group by%'").rows
    assert rows, mtk.must_query(
        "select * from information_schema.tidb_plan_feedback").rows
    for _op, calls, act, mx, mean, _route in rows:
        assert int(calls) >= 2
        assert 1.0 <= float(mx) < 1e9           # finite, >= 1
        assert 1.0 <= float(mean) <= float(mx) + 1e-9
    assert any(float(r[2]) > 0 for r in rows)   # actuals recorded
    snap = metrics.REGISTRY.snapshot()
    drift_counts = [v for k, v in snap.items()
                    if k.startswith("tidb_tpu_cardinality_drift_count")]
    assert drift_counts and sum(drift_counts) > 0, \
        "cardinality-drift histogram never observed"
    top = mtk.must_query(
        "select max_drift, mean_drift from information_schema"
        ".tidb_top_sql where sql_text like '%group by%'").rows
    assert top and float(top[0][0]) >= 1.0, top


# ---- recording overhead ----------------------------------------------

# what a warm `select 1` may record: 8 today (AST and plan cache
# look-ups, the statement's duration and its spans); twice that is a
# regression to look at, not noise
RECORDING_OPS_CEILING = 16


def test_recording_is_a_bounded_count_of_operations(monkeypatch):
    """What recording costs a statement, as a count and not as a ratio
    of host clocks (which a loaded machine moves: ROADMAP D14): one warm
    `select 1` performs at most RECORDING_OPS_CEILING registry
    operations (`inc` / `observe` / `set` / `dec` on a labelled child,
    each one short-held lock and an add), and with the registry
    disabled it performs none: every sample stays as it was."""
    recorded = []

    def counting(cls, name):
        orig = getattr(cls, name)

        def method(self, *a, **kw):
            if self._reg.enabled:
                recorded.append((cls.__name__, name))
            return orig(self, *a, **kw)
        monkeypatch.setattr(cls, name, method)

    for cls, names in ((metrics._CounterChild, ("inc",)),
                       (metrics._GaugeChild, ("set", "inc", "dec")),
                       (metrics._HistogramChild, ("observe",))):
        for name in names:
            counting(cls, name)
    tk = TestKit()
    for _ in range(50):                  # warm plan/AST caches
        tk.must_exec("select 1")
    del recorded[:]
    tk.must_exec("select 1")
    assert 0 < len(recorded) <= RECORDING_OPS_CEILING, recorded
    del recorded[:]
    before = metrics.REGISTRY.snapshot()
    monkeypatch.setattr(metrics.REGISTRY, "enabled", False)
    tk.must_exec("select 1")
    assert recorded == []
    assert metrics.REGISTRY.snapshot() == before


def test_replica_instruments_exposed_and_parse():
    """Fast mode of the replica_smoke observability leg: after one
    replica-routed statement the route counter has a labeled sample,
    reading tidb_replica_freshness refreshes the per-replica state/lag
    gauges, and the exposition stays strict-parser clean."""
    tk = TestKit()
    tk.must_exec("create table rt (a int primary key, b int)")
    tk.must_exec("insert into rt values " +
                 ",".join(f"({i},{i % 5})" for i in range(64)))
    dom = tk.sess.domain
    reps = dom.replicas.provision(1)
    deadline = time.time() + 15
    while time.time() < deadline and reps[0].state != "serving":
        time.sleep(0.02)
    assert reps[0].state == "serving"
    tk.must_exec("set @@tidb_tpu_analytic_read_mode = 'resolved'")
    deadline = time.time() + 15
    while time.time() < deadline:
        tk.must_query("select b, count(*) from rt group by b")
        if metrics.REPLICA_ROUTE.labels("replica").value > 0:
            break
    assert metrics.REPLICA_ROUTE.labels("replica").value > 0
    tk.must_query("select replica, state from information_schema"
                  ".tidb_replica_freshness where replica = '0'")
    snap = metrics.REGISTRY.snapshot()
    assert snap.get('tidb_tpu_replica_state{replica="0"}') == 1.0
    assert 'tidb_tpu_replica_lag_seconds{replica="0"}' in snap
    ctype, body = _scrape(dom)
    assert ctype.startswith("text/plain")
    _, errs = metrics.parse_text(body)
    assert not errs, errs[:3]
    assert "tidb_tpu_replica_route_total" in body
    dom.close()
