"""Span tracing + flight recorder + error catalog + structured log
(VERDICT r2 observability gaps; reference pkg/util/tracing,
pkg/util/traceevent, pkg/errno + errors.toml, pkg/util/logutil) —
extended with distributed trace propagation, sampling, and the
per-digest plan-feedback surface (docs/OBSERVABILITY.md)."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from tidb_tpu.testkit import TestKit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_trace_events_ring_and_slow_trigger():
    tk = TestKit()
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    tk.must_exec("create table tr (a int)")
    tk.must_exec("insert into tr values (1),(2),(3)")
    tk.must_query("select sum(a) from tr")
    spans = [r for r in tk.must_query(
        "select depth, span, attrs from "
        "information_schema.tidb_trace_events").rows]
    names = {s[1] for s in spans}
    # the statement stage tree: statement -> plan/execute -> copr
    assert {"statement", "plan", "execute", "copr"} <= names, names
    copr = [s for s in spans if s[1] == "copr" and "table=tr" in s[2]]
    assert copr and any("backend=" in s[2] for s in copr), spans
    # nesting depths recorded
    assert any(int(s[0]) == 2 for s in copr), copr
    # flight-recorder trigger: slow statements tag their spans
    tk.must_exec("set tidb_slow_log_threshold = 0")
    tk.must_query("select count(*) from tr")
    tagged = tk.must_query(
        "select count(*) from information_schema.tidb_trace_events "
        "where attrs like '%slow=1%'").rows
    assert int(tagged[0][0]) >= 1


def test_trace_ids_link_statement_tree():
    """Every flushed span carries (trace_id, span_id, parent_id) and the
    statement's children parent-link into one tree under one trace_id."""
    tk = TestKit()
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    tk.must_exec("create table tl (a int)")
    tk.must_exec("insert into tl values (1),(2)")
    tk.must_query("select sum(a) from tl")
    evs = [e for e in tk.domain.tracer.recorder.events()
           if e.name == "statement" and "SelectStmt" in e.attrs]
    assert evs, tk.domain.tracer.recorder.events()
    root = evs[-1]
    assert root.trace_id and root.span_id and root.parent_id == ""
    tree = [e for e in tk.domain.tracer.recorder.events()
            if e.trace_id == root.trace_id]
    assert len(tree) >= 3                      # statement + plan + execute
    ids = {e.span_id for e in tree}
    assert len(ids) == len(tree), tree         # span ids unique
    for e in tree:
        if e is not root:
            assert e.parent_id in ids, e       # no orphans in the tree


def test_sampling_default_off_keeps_ring_empty():
    """Default tidb_tpu_trace_sample_rate = 0: fast statements never
    touch the recorder ring (the OLTP fast path pays buffering only)."""
    tk = TestKit()
    tk.domain.tracer.recorder.clear()
    tk.must_exec("create table sm (a int)")
    tk.must_exec("insert into sm values (1),(2)")
    tk.must_query("select sum(a) from sm")
    assert tk.domain.tracer.recorder.events() == []
    # slow statements upgrade retroactively even at rate 0
    tk.must_exec("set tidb_slow_log_threshold = 0")
    tk.must_query("select count(*) from sm")
    evs = tk.domain.tracer.recorder.events()
    assert evs and any("slow=1" in e.attrs for e in evs), evs


def test_trace_statement_renders_tree():
    """TRACE <stmt> is always-on regardless of the sample rate and
    renders the span tree with per-span timing and worker column."""
    tk = TestKit()
    tk.must_exec("create table tt (a int)")
    tk.must_exec("insert into tt values (1),(2),(3)")
    rs = tk.must_query("trace select sum(a) from tt")
    assert rs.names == ["operation", "start_ms", "duration_ms",
                        "worker", "attrs"]
    rows = rs.rows
    assert rows and rows[0][0].startswith("statement (trace_id="), rows
    ops = "\n".join(r[0] for r in rows)
    assert "plan" in ops and "execute" in ops, rows
    # children are indented below the root
    assert any(r[0].lstrip().startswith("└─") for r in rows[1:]), rows
    # the forced trace also lands in the ring for later inspection
    flushed = tk.must_query(
        "select count(*) from information_schema.tidb_trace_events "
        "where span = 'statement'").rows
    assert int(flushed[0][0]) >= 1


def test_trace_survives_device_guard_retry():
    """A retried device dispatch shows one span per attempt, the failed
    attempt tagged with its err_class — inside the same trace."""
    from tidb_tpu.utils import failpoint
    tk = TestKit()
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    tk.must_exec("create table dg (a int primary key, b int, c int)")
    tk.must_exec("insert into dg values " + ",".join(
        f"({i}, {i % 7}, {i % 13})" for i in range(400)))
    tk.domain.tracer.recorder.clear()
    failpoint.enable("device_guard/copr/agg", "nth:1->error:grant_lost")
    try:
        tk.must_query("select b, sum(c) from dg group by b order by b")
    finally:
        failpoint.disable_all()
    evs = tk.domain.tracer.recorder.events()
    attempts = [e for e in evs if e.name == "device_attempt"
                and "site=copr/agg" in e.attrs]
    assert len(attempts) >= 2, evs
    assert any("err_class=grant_lost" in e.attrs for e in attempts)
    # every attempt belongs to the statement's trace
    stmts = [e for e in evs if e.name == "statement"]
    tids = {e.trace_id for e in stmts}
    assert all(e.trace_id in tids for e in attempts), (attempts, stmts)


def test_flight_recorder_ring_bounds():
    from tidb_tpu.utils.tracing import FlightRecorder, SpanEvent
    fr = FlightRecorder(cap=64)
    for i in range(500):
        fr.record(SpanEvent(time.time(), 1, 0, f"s{i}", 0.1, ""))
    evs = fr.events()
    assert len(evs) == 64
    assert evs[-1].name == "s499"              # newest kept


def test_tag_recent_reach_back_bounded():
    """tag_recent never walks past TAG_REACH_BACK slots: with 1000
    fresh matching events only the newest 512 are tagged."""
    from tidb_tpu.utils.tracing import FlightRecorder, SpanEvent
    fr = FlightRecorder(cap=2048)
    now = time.time()
    for i in range(1000):
        fr.record(SpanEvent(now, 7, 0, f"s{i}", 0.1, ""))
    fr.tag_recent(7, since=now - 10.0)
    tagged = [e for e in fr.events() if "slow=1" in e.attrs]
    assert len(tagged) == FlightRecorder.TAG_REACH_BACK
    # and the early stop: events older than `since` stay untouched
    fr2 = FlightRecorder(cap=64)
    fr2.record(SpanEvent(now - 100.0, 7, 0, "old", 0.1, ""))
    fr2.record(SpanEvent(now, 7, 0, "new", 0.1, ""))
    fr2.tag_recent(7, since=now - 1.0)
    byname = {e.name: e for e in fr2.events()}
    assert "slow=1" in byname["new"].attrs
    assert "slow=1" not in byname["old"].attrs


def test_concurrent_record_and_tag_recent_race():
    """Regression: tag_recent rewrites ring slots while other threads
    append — the old positional ev[5] surgery raced deque rotation;
    the SpanEvent._replace form must stay exception-free and bounded."""
    from tidb_tpu.utils.tracing import FlightRecorder, SpanEvent
    fr = FlightRecorder(cap=128)
    stop = threading.Event()
    errs = []

    def writer():
        try:
            while not stop.is_set():
                fr.record(SpanEvent(time.time(), 1, 0, "w", 0.1, ""))
        except Exception as e:          # noqa: BLE001
            errs.append(e)

    def tagger():
        try:
            while not stop.is_set():
                fr.tag_recent(1, since=0.0)
        except Exception as e:          # noqa: BLE001
            errs.append(e)
    ts = [threading.Thread(target=writer) for _ in range(2)] + \
         [threading.Thread(target=tagger) for _ in range(2)]
    for t in ts:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in ts:
        t.join()
    assert not errs, errs
    assert len(fr.events()) <= 128
    assert any("slow=1" in e.attrs for e in fr.events())


def test_qerror_and_feedback_store_eviction():
    from tidb_tpu.executor.plan_feedback import PlanFeedback, qerror
    assert qerror(10, 10) == 1.0
    assert qerror(100, 10) == 10.0
    assert qerror(10, 100) == 10.0              # symmetric
    assert qerror(0, 0) == 1.0                  # floored, never inf
    assert qerror(1000, 0) == 1000.0
    pf = PlanFeedback(capacity=2)
    for _ in range(3):
        pf.record("d1", "q1", [("TableReader", 10.0, 20, "device", 1.0)],
                  "device")
    pf.record("d2", "q2", [("HashAgg", 5.0, 5, "host", 1.0)], "host")
    pf.record("d3", "q3", [("Sort", 8.0, 2, "host", 1.0)], "host")
    digs = {r[0] for r in pf.rows()}
    assert "d1" in digs and len(digs) == 2      # least-executed evicted
    mx, mean = pf.digest_drift("d1")
    assert mx == 2.0 and mean == 2.0
    assert pf.digest_drift("gone") is None
    pf.clear()
    assert pf.rows() == []


def test_plan_feedback_surface_and_topsql_drift():
    """information_schema.tidb_plan_feedback carries per-op drift after
    a statement runs; tidb_top_sql gains the digest-level summary."""
    tk = TestKit()
    tk.must_exec("create table pf (a int primary key, b int)")
    tk.must_exec("insert into pf values " + ",".join(
        f"({i}, {i % 5})" for i in range(1, 201)))
    for _ in range(2):
        tk.must_query("select b, count(*) from pf group by b order by b")
    rows = tk.must_query(
        "select op, exec_count, calls, avg_act_rows, max_drift, "
        "mean_drift, route from information_schema.tidb_plan_feedback "
        "where sql_text like '%group by%'").rows
    assert rows, tk.must_query(
        "select * from information_schema.tidb_plan_feedback").rows
    for op, execs, calls, act, mx, mean, route in rows:
        assert int(execs) == 2
        assert int(calls) >= 2
        assert float(mx) >= 1.0 and float(mean) >= 1.0
        assert float(mx) < 1e9                  # finite
    assert any(float(r[3]) > 0 for r in rows)   # actuals recorded
    top = tk.must_query(
        "select max_drift, mean_drift from information_schema."
        "tidb_top_sql where sql_text like '%group by%'").rows
    assert top and float(top[0][0]) >= 1.0, top


def test_wait_attribution_columns():
    """commit_wait_ms / admission_wait_ms flow into slow_query and
    statements_summary (satellite: wait attribution)."""
    tk = TestKit()
    tk.must_exec("set tidb_slow_log_threshold = 0")
    tk.must_exec("create table wa (a int primary key, b int)")
    tk.must_exec("insert into wa values (1, 1), (2, 2)")
    rows = tk.must_query(
        "select query, commit_wait_ms, admission_wait_ms from "
        "information_schema.slow_query").rows
    ins = [r for r in rows if "insert" in r[0]]
    assert ins, rows
    # the insert waited on WAL group commit: attribution is recorded
    # (>= 0; the wait is real time so only non-negativity is stable)
    assert all(float(r[1]) >= 0 and float(r[2]) >= 0 for r in ins)
    srows = tk.must_query(
        "select digest_text, sum_commit_wait_ms, sum_admission_wait_ms "
        "from information_schema.statements_summary").rows
    sins = [r for r in srows if "insert" in r[0]]
    assert sins and all(float(r[1]) >= 0 for r in sins), srows


def test_wal_group_commit_span_role(tmp_path):
    """A traced committing statement shows its wal_group_commit span
    with the leader/follower role attribute (durable store: the wait
    only exists when a WAL backs the commit)."""
    from tidb_tpu.session import new_store
    tk = TestKit(new_store(str(tmp_path / "dd")))
    tk.must_exec("set tidb_tpu_trace_sample_rate = 1")
    tk.must_exec("create table wg (a int primary key)")
    tk.domain.tracer.recorder.clear()
    tk.must_exec("insert into wg values (1)")
    evs = tk.domain.tracer.recorder.events()
    wal = [e for e in evs if e.name == "wal_group_commit"]
    assert wal and any("role=" in e.attrs for e in wal), evs


def test_error_catalog_unique_codes():
    from tidb_tpu.errors import catalog
    cat = catalog()
    assert len(cat) > 25
    codes = [c for _n, c, _s in cat]
    assert len(codes) == len(set(codes)), "duplicate error codes"
    tk = TestKit()
    rows = tk.must_query("select error, code, sqlstate from "
                         "information_schema.tidb_errors "
                         "where error = 'DuplicateKeyError'").rows
    assert rows == [("DuplicateKeyError", 1062, "23000")]


def test_structured_log_redacts_literals(tmp_path, monkeypatch):
    from tidb_tpu.utils import logutil
    assert logutil.redact_sql(
        "select * from t where secret = 'hunter2' and id = 42"
    ).count("hunter2") == 0
    # slow query logs the NORMALIZED statement, never raw literals;
    # pin the sink to a private file (another test's durable store may
    # have redirected the process-wide sink)
    sink = open(tmp_path / "log.jsonl", "a", buffering=1)
    monkeypatch.setattr(logutil, "_SINK", sink)
    tk = TestKit()
    tk.must_exec("create table lg (a int, s varchar(20))")
    tk.must_exec("set tidb_slow_log_threshold = 0")
    tk.must_query("select * from lg where s = 'topsecretvalue'")
    sink.flush()
    recs = [json.loads(l) for l in
            open(tmp_path / "log.jsonl").read().splitlines()
            if l.startswith("{")]
    slow = [r for r in recs if r.get("event") == "slow_query"]
    assert slow, recs
    assert all("topsecretvalue" not in json.dumps(r) for r in slow)
    assert any("?" in r.get("sql", "") for r in slow)


def test_slow_log_carries_phase_counters():
    """A slow statement's record attributes its backend time (dispatch/
    upload/host counters from utils/phase.py) without a rerun."""
    from tidb_tpu.testkit import TestKit
    tk = TestKit()
    tk.must_exec("create table ph (a int primary key, b int)")
    tk.must_exec("insert into ph values " + ",".join(
        f"({i}, {i % 7})" for i in range(1, 3001)))
    tk.must_exec("set @@tidb_slow_log_threshold = 0")
    tk.must_query("select b, count(*) from ph group by b order by b")
    entry = tk.domain.slow_log[-1]
    assert isinstance(entry.get("phases"), dict)
    # the group-by ran a backend: at least one counter is present
    assert entry["phases"], entry


def test_trace_sample_rate_sysvar_validated():
    tk = TestKit()
    from tidb_tpu.errors import WrongValueForVarError
    tk.must_exec("set tidb_tpu_trace_sample_rate = 0.5")
    assert float(tk.sess.vars.get("tidb_tpu_trace_sample_rate")) == 0.5
    with pytest.raises(WrongValueForVarError):
        tk.must_exec("set tidb_tpu_trace_sample_rate = 1.5")
    with pytest.raises(WrongValueForVarError):
        tk.must_exec("set tidb_tpu_trace_sample_rate = -1")


def test_cross_worker_span_propagation():
    """Tentpole end-to-end: a coordinator statement's trace context
    crosses the supervised RPC seam, both workers record spans under
    the coordinator's trace_id, and the piggybacked events land in the
    coordinator's ring as one renderable tree."""
    procs, ports = [], []
    env = dict(os.environ, TIDB_TPU_PLATFORM="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))

    def spawn():
        p = subprocess.Popen(
            [sys.executable, "-m", "tidb_tpu.cluster.worker", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, cwd=REPO, text=True)
        line = p.stdout.readline().strip()
        assert line.startswith("WORKER_READY"), line
        procs.append(p)
        return int(line.split()[1])
    for _ in range(2):
        ports.append(spawn())
    from tidb_tpu.cluster import Cluster
    cl = Cluster(ports)
    try:
        cl.ddl("create table ct (id int primary key, v int)")
        cl.workers[0].call({"op": "load_sql", "sqls": [
            "insert into ct values (1, 1), (2, 2)"]})
        cl.workers[1].call({"op": "load_sql", "sqls": [
            "insert into ct values (3, 3), (4, 4)"]})
        got = cl.query_agg("select sum(v), count(*) from ct")
        assert int(float(got[0][0])) == 10 and int(got[0][1]) == 4
        evs = cl.domain.tracer.recorder.events()
        roots = [e for e in evs if e.name == "query_agg"]
        assert roots, evs
        root = roots[-1]
        assert root.trace_id.startswith("t-c-")
        tree = [e for e in evs if e.trace_id == root.trace_id]
        # both workers contributed spans, correlated by trace_id
        wspans = [e for e in tree if e.worker]
        assert len({e.worker for e in wspans}) == 2, tree
        assert all(e.span_id.startswith("s-w") for e in wspans)
        # the worker-side op roots parent-link to the coordinator span
        wroots = [e for e in wspans if e.name == "worker_op"]
        assert wroots, tree
        assert all(e.parent_id == root.span_id for e in wroots), \
            (root, wroots)
        # the rendered surface sees the same tree
        qr = cl.sess.execute(
            "select count(*) from information_schema.tidb_trace_events "
            f"where trace_id = '{root.trace_id}' and worker != ''")
        assert int(qr.rows[0][0]) >= 2
    finally:
        cl.stop()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()


# ---- one span API, three sinks: over the wire, on the profiler's clock ----

@pytest.fixture(scope="module")
def served():
    """A small TPC-H store behind the wire server, one sampled client."""
    from tidb_tpu.bench.tpch import load_tpch
    from tidb_tpu.server import Server
    from tidb_tpu.testkit import MiniClient
    tk = TestKit()
    load_tpch(tk, sf=0.003, seed=11)
    srv = Server(tk.domain, port=0).start()
    client = MiniClient(srv.port, db="test")
    yield tk, client
    client.close()
    srv.shutdown()


def _tree(events, root):
    """{span_id: event} of root's subtree, checked to be one tree."""
    by_parent = {}
    for e in events:
        by_parent.setdefault(e.parent_id, []).append(e)
    out, stack = {}, [root]
    while stack:
        e = stack.pop()
        out[e.span_id] = e
        stack.extend(by_parent.get(e.span_id, []))
    return out


def _path(tree, ev):
    names = [ev.name]
    while ev.parent_id in tree:
        ev = tree[ev.parent_id]
        names.append(ev.name)
    return names[::-1]


def _self_ms(events):
    """{name: summed self time} of a span tree: each span's duration
    minus what its children cover."""
    kids = {}
    for e in events:
        kids[e.parent_id] = kids.get(e.parent_id, 0.0) + e.dur_ms
    out = {}
    for e in events:
        out[e.name] = out.get(e.name, 0.0) + e.dur_ms - \
            kids.get(e.span_id, 0.0)
    return out


def test_wire_statement_tree_reaches_dispatch_and_wire_write(served):
    """Hole 2 of ISSUE 25: a fused TPC-H query over the wire is one tree
    command > statement > execute > device_attempt > bind/dispatch/
    consume (+ fetch wherever the device->host seam was crossed), with
    parse and wire_write under the same root."""
    from tidb_tpu.bench.tpch import Q3
    tk, c = served
    c.query("set tidb_tpu_trace_sample_rate = 1")
    tk.domain.ast_cache.clear()
    tk.domain.tracer.recorder.clear()
    c.query(Q3)
    c.query("set tidb_tpu_trace_sample_rate = 0")
    rows = tk.must_query(
        "select span from information_schema.tidb_trace_events").rows
    assert {"command", "wire_write", "dispatch"} <= {r[0] for r in rows}
    evs = tk.domain.tracer.recorder.events()
    roots = [e for e in evs if e.name == "command" and not e.parent_id]
    q3 = next(t for t in (_tree(evs, r) for r in roots)
              if any(e.name == "dispatch" for e in t.values()))
    paths = {tuple(_path(q3, e)) for e in q3.values()}
    assert ("command", "parse") in paths
    assert ("command", "wire_write") in paths
    fused = ("command", "statement", "execute", "device_attempt")
    for leaf in ("bind", "dispatch", "consume"):
        assert fused + (leaf,) in paths, sorted(paths)
    assert len({e.trace_id for e in q3.values()}) == 1
    by_name = {}
    for e in q3.values():
        by_name.setdefault(e.name, []).append(e)
    assert "kind=fused" in by_name["dispatch"][0].attrs
    assert "retries=0" in by_name["consume"][0].attrs
    assert all("upload_bytes=" in e.attrs and "pool_hits=" in e.attrs
               for e in by_name["bind"])
    ww = by_name["wire_write"][0]
    assert "rows=10" in ww.attrs and "bytes=" in ww.attrs
    assert "cmd=3" in by_name["command"][0].attrs
    # the CPU backend may alias buffers without crossing the fetch seam
    # (tests/test_phase_fetch.py pins the span at the seam itself)
    for e in by_name.get("fetch", []):
        assert "bytes=" in e.attrs and \
            _path(q3, e)[:2] == ["command", "statement"]


def test_mesh_statement_tree_has_the_route_span(served):
    """On a mesh the fused route opens `mpp_dispatch` inside the
    supervised attempt: command > statement > execute > device_attempt
    > mpp_dispatch > bind/dispatch/consume, the route's attributes on
    it and what the host merged on `consume`."""
    import jax
    from tidb_tpu.bench.tpch import Q6
    from tidb_tpu.parallel import make_mesh
    by_order = ("select l_orderkey, l_partkey, count(*) from lineitem "
                "group by l_orderkey, l_partkey order by 3 desc, 1, 2 limit 3")
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for the mesh")
    tk, c = served
    was = tk.domain.copr._get_mesh()
    tk.domain.copr._mesh = make_mesh(4)
    try:
        c.query("set tidb_mpp_min_rows = 0")
        c.query("set tidb_tpu_trace_sample_rate = 1")
        found = {}
        for name, sql in (("sorted", by_order), ("q6", Q6)):
            tk.domain.tracer.recorder.clear()
            c.query(sql)
            evs = tk.domain.tracer.recorder.events()
            roots = [e for e in evs if e.name == "command" and
                     not e.parent_id]
            found[name] = next(
                t for t in (_tree(evs, r) for r in roots)
                if any(e.name == "dispatch" for e in t.values()))
    finally:
        c.query("set tidb_tpu_trace_sample_rate = 0")
        c.query("set tidb_mpp_min_rows = 65536")
        tk.domain.copr._mesh = was if was is not None else False
    route = ("command", "statement", "execute", "device_attempt",
             "mpp_dispatch")
    for name, tree in found.items():
        paths = {tuple(_path(tree, e)) for e in tree.values()}
        for leaf in ("bind", "dispatch", "consume"):
            assert route + (leaf,) in paths, (name, sorted(paths))
    so = {e.name: e.attrs for e in found["sorted"].values()}
    q6 = {e.name: e.attrs for e in found["q6"].values()}
    for attrs in (so["mpp_dispatch"], q6["mpp_dispatch"]):
        assert "ndev=4" in attrs and "exchange=passthrough" in attrs
    assert "kind=sort" in so["mpp_dispatch"]
    assert "kind=dense" in q6["mpp_dispatch"]
    # the sort layout's per-shard partials are merged on the host,
    # q6's sums on the mesh
    merged = [e.attrs for e in found["sorted"].values()
              if e.name == "consume" and "shards=4" in e.attrs]
    assert len(merged) == 1 and "merged_groups=0" not in merged[0], \
        [e.attrs for e in found["sorted"].values() if e.name == "consume"]
    assert "shards=1" in q6["consume"] and "merged_groups=0" in q6["consume"]


def test_profiler_segments_are_flat_self_time(served, tmp_path):
    """Under a profiler session the host plane holds tidb:<span>
    segments that match the benchmark's name filter, never overlap
    within a thread, and sum, name by name, to the ring's self times."""
    import glob
    import re
    import jax
    from tidb_tpu.bench.tpch import Q1, Q3, Q6
    tk, c = served
    for q in (Q6, Q3, Q1):
        c.query(q)                              # programs built
    c.query("set tidb_tpu_trace_sample_rate = 1")
    tk.domain.tracer.recorder.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for q in (Q6, Q3, Q1):
            c.query(q)
    finally:
        jax.profiler.stop_trace()
    c.query("set tidb_tpu_trace_sample_rate = 0")
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(pb[0])
    name_ok = re.compile(r"^[a-z_]+:[A-Za-z0-9_.\-]+$")  # trace_reduce.load
    seg_ms = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            segs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith("tidb:"))
            for (_, end, _), (start, _, _) in zip(segs, segs[1:]):
                assert end <= start, (line.name, segs)
            for s, e, n in segs:
                assert name_ok.match(n), n
                seg_ms[n[5:]] = seg_ms.get(n[5:], 0.0) + (e - s) / 1e6
    evs = [e for e in tk.domain.tracer.recorder.events()
           if e.name != "statement" or "SetStmt" not in e.attrs]
    traced = {e.trace_id for e in evs if e.name == "dispatch"}
    self_ms = _self_ms([e for e in evs if e.trace_id in traced])
    assert {"command", "statement", "execute", "bind", "dispatch",
            "consume", "wire_write"} <= set(seg_ms), seg_ms
    for name, ms in self_ms.items():
        assert seg_ms.get(name, 0.0) == pytest.approx(ms, abs=1.0), \
            (name, seg_ms, self_ms)


def test_unsampled_statement_feeds_only_the_histogram(served):
    """No profiler session, sampling off: a statement leaves the ring
    empty and tidb_tpu_span_seconds{span="statement"} one larger."""
    from tidb_tpu.bench.tpch import Q6
    from tidb_tpu.utils import metrics

    def count(span):
        return sum(v for n, lb, v in metrics.SPAN_SECONDS.sample_rows()
                   if n.endswith("_count") and lb["span"] == span)

    tk, c = served
    c.query(Q6)
    tk.domain.tracer.recorder.clear()
    before = {s: count(s) for s in ("statement", "command", "execute")}
    c.query(Q6)
    assert tk.domain.tracer.recorder.events() == []
    assert {s: count(s) for s in before} == \
        {s: n + 1 for s, n in before.items()}
    assert "tidb_tpu_span_seconds_bucket" in metrics.REGISTRY.expose()


def test_watchdog_worker_keeps_the_statements_trace(served):
    """tidb_tpu_device_dispatch_timeout_ms > 0 moves the dispatch onto
    a watchdog worker thread: its bind/dispatch spans still land under
    the statement's device_attempt, in the statement's trace."""
    from tidb_tpu.bench.tpch import Q6
    tk, c = served
    c.query("set tidb_tpu_device_dispatch_timeout_ms = 60000")
    c.query("set tidb_tpu_trace_sample_rate = 1")
    tk.domain.tracer.recorder.clear()
    try:
        c.query(Q6)
    finally:
        c.query("set tidb_tpu_trace_sample_rate = 0")
        c.query("set tidb_tpu_device_dispatch_timeout_ms = 0")
    evs = tk.domain.tracer.recorder.events()
    disp = [e for e in evs if e.name == "dispatch"]
    assert disp, evs
    root = next(e for e in evs if e.name == "command" and
                e.trace_id == disp[0].trace_id)
    tree = _tree(evs, root)
    assert _path(tree, disp[0]) == ["command", "statement", "execute",
                                    "device_attempt", "dispatch"]
    assert disp[0].depth == 4 and disp[0].conn_id == root.conn_id


def test_every_cached_kernel_is_a_named_program(served):
    """Every callable stored through the copr and mpp kernel caches has
    a jit name tidb_<family>, and the family depends on nothing of the
    run: the same query in a fresh cache gets the same names."""
    import inspect
    from tidb_tpu.bench.tpch import Q1, Q3, Q5, Q6
    from tidb_tpu.copr.dag_exec import _KernelCache
    from tidb_tpu.mpp import exec as mpp_exec
    tk, c = served
    copr = tk.domain.copr

    def names(cache):
        return sorted(inspect.unwrap(k).__name__ for k in cache.values())

    def run():
        copr._kernel_cache = _KernelCache()
        with mpp_exec._KERN_MU:
            mpp_exec._KERN_CACHE.clear()
        for q in (Q6, Q1, Q3, Q5, "select l_orderkey from lineitem "
                  "where l_quantity > 4900 order by l_extendedprice "
                  "limit 3"):
            tk.must_query(q)
        return names(copr._kernel_cache), names(mpp_exec._KERN_CACHE)

    first, second = run(), run()
    assert first == second
    assert first[0] and all(n.startswith("tidb_") for n in
                            first[0] + first[1]), first
    assert any(n.startswith("tidb_fused_") or
               n.startswith("tidb_mpp_fused_") for n in first[0]), first
