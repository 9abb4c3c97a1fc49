"""INFORMATION_SCHEMA virtual tables (reference pkg/infoschema/cluster.go +
pkg/executor/infoschema_reader.go, slow_query.go, stmtsummary).

Each virtual table = (columns, generator(domain) -> row tuples). Reads
materialize on demand and then flow through the normal host copr path, so
filters/joins/aggregation all work over them."""
from __future__ import annotations

import threading

from ..models import TableInfo, ColumnInfo
from ..types.field_type import (new_bigint_type,
                                new_double_type,
                                new_string_type)

_VIRTUAL_ID = {}
_next_vid = [-1000]


def _vt(name, cols, gen):
    # import-time registration only (every _vt call is a module-level
    # statement in this file): single-threaded by construction
    # tpulint: disable=shared-state-race
    _next_vid[0] -= 1
    VIRTUAL_TABLES[name] = (cols, gen)  # tpulint: disable=shared-state-race


VIRTUAL_TABLES: dict = {}


def _gen_schemata(domain):
    for db in domain.infoschema().all_schemas():
        yield ("def", db.name, db.charset, db.collate, None)


def _gen_tables(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            ctab = domain.columnar.tables.get(t.id)
            rows = ctab.live_count() if ctab else 0
            ttype = "VIEW" if t.view_select else "BASE TABLE"
            yield ("def", db.name, t.name, ttype, "InnoDB", t.id,
                   rows, t.comment)


def _gen_columns(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            for i, c in enumerate(t.public_columns()):
                yield ("def", db.name, t.name, c.name, i + 1,
                       c.ft.default_value if c.ft.has_default else None,
                       "NO" if c.ft.not_null else "YES",
                       c.ft.tp, c.ft.sql_string(), c.comment)


def _gen_statistics(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            if t.pk_is_handle:
                yield (db.name, t.name, 0, "PRIMARY", 1, t.pk_col_name)
            for idx in t.indexes:
                for seq, col in enumerate(idx.columns):
                    yield (db.name, t.name, 0 if idx.unique else 1,
                           idx.name, seq + 1, col)


def _gen_slow_query(domain):
    for e in domain.slow_log:
        ph = e.get("phases") or {}
        yield (e.get("time", 0.0), e.get("time_ms", 0.0) / 1000.0,
               e.get("sql", ""), e.get("db", ""), e.get("conn", 0),
               1 if e.get("success") else 0,
               e.get("digest", ""), int(e.get("is_internal", 0)),
               int(e.get("mem_max", 0)),
               # wait attribution: phase snap() keys are already ms
               ph.get("commit_wait_s", 0.0),
               ph.get("admission_wait_s", 0.0),
               # replica-routing outcome ("replica-<rid>",
               # "leader_fallback", "degraded_midstmt", ""=leader)
               e.get("replica", ""))


def _gen_stmt_summary(domain):
    for s in domain.stmt_summary_map.values():
        cnt = max(s["exec_count"], 1)
        yield (s["digest"], s["normalized"], s["exec_count"],
               s["sum_ms"] / 1000.0, s["max_ms"] / 1000.0,
               s["sum_ms"] / cnt / 1000.0, s["errors"],
               s.get("sum_device_ms", 0.0), s.get("fallback_count", 0),
               int(s.get("mem_max", 0)),
               s.get("sum_commit_wait_ms", 0.0),
               s.get("sum_admission_wait_ms", 0.0))


def _gen_memory_usage(domain):
    """Live memory-tracker tree (docs/ROBUSTNESS.md "Memory safety"):
    one 'global' row for the root (quota = the server memory limit, -1
    when unlimited), one 'session' row per live connection, one
    'statement' row per live statement tracker (its quota = the
    effective tidb_mem_quota_query / MEMORY_QUOTA hint, plus the
    statement's oom action), and one 'device_pool' row for the
    device-resident store (HBM bytes charged, high-water mark, byte
    budget; action 'evict'). The instance-level analog of the
    reference's information_schema.memory_usage."""
    root = getattr(domain, "mem_root", None)
    if root is None:
        return
    ctl = getattr(domain, "mem_controller", None)
    lim = ctl.limit_bytes() if ctl is not None else 0
    yield (0, "global", root.label, root.consumed, root.max_consumed,
           lim if lim else -1, "")
    # resident device buffers belong to the pool, not to the statement
    # that faulted them in: charged to the store's own budget, shed by
    # its LRU and the pressure protocol, never by ER 8175
    copr = getattr(domain, "copr", None)
    store = getattr(copr, "_dev_store", None)
    if store is not None:
        st = store.stats()
        yield (0, "device_pool", "device-resident store", st["bytes"],
               st["max_bytes"], st["budget"], "evict")
    # snapshot both registries: connections register / statements
    # start concurrently with this read, and iterating the live dicts
    # would die on "changed size during iteration" exactly under the
    # load this table exists to inspect
    for cid, ref in sorted(list(getattr(domain, "sessions",
                                        {}).items())):
        s = ref()
        if s is None:
            continue
        tr = getattr(s, "mem_tracker", None)
        if tr is None:
            continue
        yield (cid, "session", tr.label, tr.consumed, tr.max_consumed,
               -1, "")
    for cid, lst in sorted(list(domain._live_execs.items())):
        for ectx in list(lst):
            tr = getattr(ectx, "mem_tracker", None)
            if tr is None or tr.closed:
                continue
            yield (cid, "statement", tr.label, tr.consumed,
                   tr.max_consumed, tr.quota,
                   tr.oom_action or "cancel")


def _gen_cluster_health(domain):
    """Cluster supervision view (docs/ROBUSTNESS.md "Cluster fault
    tolerance"): one row per worker slot from the coordinator's
    heartbeat monitor — state machine position (up/suspect/down), the
    worker's cluster epoch, its role (primary / fenced / follower /
    deposed), heartbeat lag, in-flight handler count and dedup-window
    hits. Empty on a domain that isn't a cluster coordinator."""
    mon = getattr(domain, "cluster_monitor", None)
    if mon is None:
        return
    for row in mon.snapshot():
        yield row


def _gen_metrics(domain):
    """Flat per-store counters + every typed registry sample (labels
    rendered `k="v"`), one SQL-queryable surface for both."""
    from ..utils import metrics as metrics_util
    for k, v in sorted(domain.metrics.items()):
        yield (k, "", float(v))
    metrics_util.update_runtime_gauges(domain)
    for name, labels, value in metrics_util.REGISTRY.samples(
            include_compat=False):
        yield (name, metrics_util.render_labels(labels), float(value))


def _gen_errors(domain):
    from ..errors import catalog
    for name, code, sqlstate in catalog():
        yield (name, code, sqlstate)


def _gen_trace_events(domain):
    """Flight-recorder ring (reference pkg/util/traceevent dumped on
    triggers; here queryable directly): recent spans with nesting depth,
    duration, attributes, and the distributed trace identity
    (trace_id/span_id/parent_id/worker) that joins a mesh query's
    coordinator and worker halves — slow statements tag theirs slow=1."""
    for ev in domain.flight_recorder.events():
        yield (ev.ts, ev.conn_id, ev.depth, ev.name, ev.dur_ms,
               ev.attrs, ev.trace_id, ev.span_id, ev.parent_id,
               ev.worker)


def _gen_plan_feedback(domain):
    """Per-(digest, plan-operator-class) estimate-vs-actual feedback
    folded at statement end (executor/plan_feedback.py) — the
    instrumentation input for the feedback-driven cost model (ROADMAP
    #1). Drift is the symmetric q-error max(est/act, act/est), floored
    at one row on both sides so it is always finite and >= 1."""
    for row in domain.plan_feedback.rows():
        yield row


def _gen_top_sql(domain):
    """Per-digest device-time attribution (reference TopSQL's CPU
    attribution, surfaced as a table instead of the dashboard agent):
    each statement's phase snapshot (utils/phase) — device dispatch ms,
    XLA compile ms, host-path ms, fetch ms, kernel builds, upload/fetch
    bytes, device fallbacks — folded into a bounded ring by
    Session._observe. `ORDER BY sum_device_ms DESC` answers "what is
    the TPU doing"."""
    for e in domain.top_sql.rows():
        cnt = max(e["exec_count"], 1)
        yield (e["digest"], e["normalized"], e["exec_count"],
               e["sum_ms"], e["sum_ms"] / cnt,
               e["sum_device_ms"], e["sum_compile_ms"],
               e["sum_host_ms"], e["sum_fetch_ms"], e["sum_upload_ms"],
               e["kernel_builds"], e["dispatches"],
               e["upload_bytes"], e["fetch_bytes"],
               e["fallback_count"], e["sum_errors"],
               e.get("delta_applies", 0), e.get("delta_bytes", 0),
               round(e.get("max_drift", 0.0), 4),
               round(e.get("sum_drift", 0.0) /
                     max(e.get("drift_execs", 0), 1), 4),
               e.get("replica_reads", 0), e.get("leader_fallbacks", 0),
               e.get("degraded_midstmt", 0))


def _gen_deadlocks(domain):
    """Deadlock history ring (reference information_schema.deadlocks,
    pkg/deadlockhistory): one row per wait-for edge of each detected
    cycle, sharing a deadlock_id. try_lock_trx_id is the waiter's
    start_ts, trx_holding_lock the holder it waited on; the victim is
    the cycle's youngest txn (max start_ts)."""
    for (did, wall, retryable, waiter, key_hex, holder) in \
            domain.storage.mvcc.waits.history_rows():
        yield (did, wall, retryable, waiter, key_hex, holder)


def _gen_data_lock_waits(domain):
    """Live lock-wait queue (reference information_schema.data_lock_waits):
    which TRANSACTION is blocked on which key held by whom, right now.
    Like the reference, only txn (write/FOR UPDATE) waits appear —
    blocked snapshot readers hold no locks, take no wait-for edge, and
    resolve without queueing."""
    for key, waiter, holder in \
            domain.storage.mvcc.waits.current_waits():
        yield (key.hex(), waiter, holder)


def _gen_changefeeds(domain):
    """Live changefeed registry (reference TiCDC `cdc cli changefeed
    list`, surfaced as a table): state, sink, checkpoint/resolved ts,
    resolved-ts lag in seconds, delivery counters, last error."""
    mgr = getattr(domain, "cdc", None)
    if mgr is None:
        return
    for name in sorted(mgr.feeds):
        f = mgr.feeds.get(name)    # racing ADMIN CHANGEFEED REMOVE
        if f is None or f.state == "removed":
            continue
        lag = f.resolved_lag_seconds()
        yield (f.name, f.state, f.sink_uri, f.start_ts, f.checkpoint_ts,
               f.resolved, round(lag, 6) if lag is not None else None,
               f.emitted_txns, f.emitted_rows, f.error or "")


def _gen_vector_indexes(domain):
    """One row per PUBLIC vector index (tidb_tpu/vector/): the durable
    meta joined with the live IVF runtime state — centroid count, rows
    folded into posting lists, rows committed since the last fold
    (the delta-path backlog), and the last (re)train time. An index
    that has never served a search shows centroids/rows 0 (lazy
    build)."""
    rt = getattr(domain, "vector", None)
    if rt is None:
        return
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        if db.name.lower() in ("mysql", "information_schema"):
            continue
        for t in ischema.tables_in_schema(db.name):
            for idx in t.indexes:
                if not getattr(idx, "vector", False):
                    continue
                inst = rt.index_for(t, idx.columns[0]) \
                    if idx.columns else None
                st = inst.stats() if inst is not None else {}
                yield (db.name, t.name, idx.name,
                       idx.columns[0] if idx.columns else "",
                       st.get("centroids", 0), st.get("rows", 0),
                       rt.pending_rows(t.id),
                       float(st.get("last_train_ts", 0.0)))


def _gen_tidb_models(domain):
    """One row per PUBLIC model (tidb_tpu/ml/, docs/ML.md): the durable
    meta (uri, parsed shape params, weight bytes, create time) joined
    with live serving state — device-resident weight bytes (0 until the
    first device-path statement uploads them) and the predict()/embed()
    call + row counters accumulated by this process."""
    ml = getattr(domain, "ml", None)
    if ml is None:
        return
    import json
    for h in ml.handles():
        yield (h.name, h.info.uri, h.kind,
               json.dumps(h.info.params, sort_keys=True),
               h.info.nbytes, h.version, float(h.info.created_ts) / 1e6,
               ml.device_nbytes(h.id), h.predict_calls, h.predict_rows)


def _gen_replica_freshness(domain):
    """Per-table analytic-replica freshness (incremental HTAP,
    docs/PERFORMANCE.md): the resolved-ts read view every resolved-mode
    analytic statement would snapshot at RIGHT NOW, its wallclock lag,
    and the rows committed since the delta maintainer last reconciled
    the table's device-resident buffers. One row per user table with a
    columnar image, replica="leader". PLUS one row per replica domain
    of the read-replica fabric (replica="<rid>", table columns empty):
    its health state, applied watermark + lag, sorter backlog, and how
    many statements it has served. Reading the table also refreshes
    the leader lag gauge and the per-replica state/lag gauges."""
    delta = getattr(domain.copr, "delta", None)
    if delta is None or delta._domain is None:
        return
    from ..utils import metrics as metrics_util
    resolved = delta.resolved_ts()
    lag_ms = delta.lag_ms(resolved)
    metrics_util.REPLICA_LAG_SECONDS.set(lag_ms / 1000.0)
    stats = delta.table_stats()
    mode = domain.global_vars.get("tidb_tpu_analytic_read_mode")
    if mode is None:
        from ..session.sysvars import get_sysvar
        mode = get_sysvar("tidb_tpu_analytic_read_mode").default
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        if db.name.lower() in ("mysql", "information_schema"):
            continue
        for t in ischema.tables_in_schema(db.name):
            ctab = domain.columnar.tables.get(t.id)
            if ctab is None:
                continue
            pend = stats.get(t.id, (0, 0, 0))[0]
            yield (db.name, t.name, resolved, round(lag_ms, 3), pend,
                   str(mode), "leader", "serving", 0)
    rm = getattr(domain, "replicas", None)
    if rm is None or not rm.replicas:
        return
    rm.refresh_gauges()
    for (rid, state, applied, rlag_ms, pending,
         routed) in rm.snapshot():
        yield ("", "", applied, rlag_ms, pending, str(mode),
               str(rid), state, routed)


def _gen_ddl_jobs(domain):
    """Durable online-DDL job queue + recent history (reference ADMIN
    SHOW DDL JOBS / mysql.tidb_ddl_job, owner/ddl_runner.py): live
    jobs first (a running reorg shows its checkpoint handle and rows
    done/total), then terminal history newest-first."""
    runner = getattr(domain, "ddl_jobs", None)
    if runner is None:
        return
    from ..session.ddl import schema_state_name
    for j in runner.list_jobs():
        yield (j.id, j.type, j.state,
               schema_state_name(j.schema_state), j.db_name,
               j.table_name, j.table_id, j.row_done, j.row_total,
               j.checkpoint_handle, j.start_wall or None,
               j.error or "")


def _gen_backup_jobs(domain):
    """Backup runs + restore jobs (tidb_tpu/br): backup runs are
    in-memory records on the domain (a backup is driven by its
    session, not the job queue); restore jobs are the durable
    TYPE_RESTORE rows from the DDL job queue/history, with their
    phase/checkpoint pulled out of job.args."""
    for r in getattr(domain, "_br_runs", []):
        yield (int(r["id"]), r["kind"], r["phase"], r["state"],
               int(r["backup_ts"]), int(r["bytes"]),
               str(r["checkpoint"] or ""), str(r["error"] or ""))
    runner = getattr(domain, "ddl_jobs", None)
    if runner is None:
        return
    from ..models.job import TYPE_RESTORE
    for j in runner.list_jobs():
        if j.type != TYPE_RESTORE:
            continue
        a = j.args or {}
        ckpt = "tables=%d replay_ts=%d" % (
            len(a.get("tables_done", [])), int(a.get("replay_ts") or 0))
        yield (j.id, "restore", str(a.get("phase", "")), j.state,
               int(a.get("backup_ts") or 0), int(a.get("bytes") or 0),
               ckpt, j.error or "")


def _gen_resource_groups(domain):
    for g in domain.resource_groups.groups.values():
        limit = ""
        if g.exec_elapsed_ms:
            limit = (f"EXEC_ELAPSED='{g.exec_elapsed_ms}ms', "
                     f"ACTION={g.query_limit_action.upper()}")
        yield (g.name,
               -1 if g.ru_per_sec is None else int(g.ru_per_sec),
               "MEDIUM",
               "YES" if g.burstable else "NO",
               limit,
               round(g.consumed_ru, 3),
               g.throttled_stmts)


def _gen_placement_policies(domain):
    """Policies from mysql.placement_policies + the tables attached to
    each (reference information_schema.placement_policies)."""
    isc = domain.infoschema()
    mysql_db = isc.table_by_name("mysql", "placement_policies") \
        if isc.has_table("mysql", "placement_policies") else None
    if mysql_db is None:
        return
    ctab = domain.columnar.tables.get(mysql_db.id)
    if ctab is None:
        return
    attached: dict = {}
    for db in isc.all_schemas():
        for t in isc.tables_in_schema(db.name):
            if t.placement_policy:
                attached.setdefault(t.placement_policy.lower(), []) \
                    .append(f"{db.name}.{t.name}")
    valid = ctab.valid_at()
    import numpy as np
    cols = mysql_db.columns
    for i in np.nonzero(valid)[0].tolist():
        name = ctab.column_for(cols[0]).get_datum(i).to_py()
        settings = ctab.column_for(cols[1]).get_datum(i).to_py()
        yield (name, settings,
               ",".join(sorted(attached.get(str(name).lower(), []))))


def _gen_engines(domain):
    yield ("InnoDB", "DEFAULT", "TPU-native columnar + MVCC row engine",
           "YES", "YES", "YES")


def _gen_collations(domain):
    yield ("utf8mb4_bin", "utf8mb4", 46, "", "Yes", 1)
    yield ("utf8mb4_general_ci", "utf8mb4", 45, "", "Yes", 1)


def _gen_character_sets(domain):
    yield ("utf8mb4", "utf8mb4_bin", "UTF-8 Unicode", 4)


def _gen_tidb_indexes(domain):
    yield from _gen_statistics(domain)


def _gen_cluster_info(domain):
    yield ("tidb-tpu", "127.0.0.1:4000", "127.0.0.1:10080", "0.1.0", "none")


def _gen_processlist(domain):
    for cid, ref in sorted(domain.sessions.items()):
        s = ref()
        if s is None:
            continue
        busy = bool(domain._live_execs.get(cid))
        yield (cid, s.user, "localhost", s.vars.current_db or None,
               "Query" if busy else "Sleep", 0, "")


def _gen_key_column_usage(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            if t.pk_is_handle:
                yield ("def", db.name, "PRIMARY", db.name, t.name,
                       t.pk_col_name, 1, None, None, None)
            for idx in t.indexes:
                if idx.primary or idx.unique:
                    for seq, c in enumerate(idx.columns):
                        yield ("def", db.name,
                               "PRIMARY" if idx.primary else idx.name,
                               db.name, t.name, c, seq + 1, None, None, None)
            for fk in t.foreign_keys:
                for seq, c in enumerate(fk["cols"]):
                    yield ("def", db.name, fk["name"] or "fk", db.name,
                           t.name, c, seq + 1, fk["ref_db"],
                           fk["ref_table"], fk["ref_cols"][seq])


def _gen_referential_constraints(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            for fk in t.foreign_keys:
                yield ("def", db.name, fk["name"] or "fk", db.name,
                       fk["on_delete"].upper(), t.name, fk["ref_table"])


def _gen_views(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            if t.view_select:
                yield (db.name, t.name, t.view_select)


def _gen_partitions(domain):
    ischema = domain.infoschema()
    for db in ischema.all_schemas():
        for t in ischema.tables_in_schema(db.name):
            if t.partitions:
                for p in t.partitions["parts"]:
                    yield (db.name, t.name, p["name"])


_S = new_string_type
_I = new_bigint_type
_F = new_double_type


def _cols(*specs):
    return [(name, ft) for name, ft in specs]


VIRTUAL_DEFS = {
    "schemata": (_cols(("catalog_name", _S()), ("schema_name", _S()),
                       ("default_character_set_name", _S()),
                       ("default_collation_name", _S()),
                       ("sql_path", _S())), _gen_schemata),
    "tables": (_cols(("table_catalog", _S()), ("table_schema", _S()),
                     ("table_name", _S()), ("table_type", _S()),
                     ("engine", _S()), ("tidb_table_id", _I()),
                     ("table_rows", _I()), ("table_comment", _S())),
               _gen_tables),
    "columns": (_cols(("table_catalog", _S()), ("table_schema", _S()),
                      ("table_name", _S()), ("column_name", _S()),
                      ("ordinal_position", _I()), ("column_default", _S()),
                      ("is_nullable", _S()), ("data_type", _S()),
                      ("column_type", _S()), ("column_comment", _S())),
                _gen_columns),
    "statistics": (_cols(("table_schema", _S()), ("table_name", _S()),
                         ("non_unique", _I()), ("index_name", _S()),
                         ("seq_in_index", _I()), ("column_name", _S())),
                   _gen_statistics),
    "slow_query": (_cols(("time", _F()), ("query_time", _F()),
                         ("query", _S()), ("db", _S()), ("conn_id", _I()),
                         ("succ", _I()), ("digest", _S()),
                         ("is_internal", _I()), ("mem_max", _I()),
                         ("commit_wait_ms", _F()),
                         ("admission_wait_ms", _F()),
                         ("replica", _S())),
                   _gen_slow_query),
    "statements_summary": (_cols(("digest", _S()), ("digest_text", _S()),
                                 ("exec_count", _I()),
                                 ("sum_latency", _F()), ("max_latency", _F()),
                                 ("avg_latency", _F()), ("sum_errors", _I()),
                                 ("sum_device_ms", _F()),
                                 ("fallback_count", _I()),
                                 ("mem_max", _I()),
                                 ("sum_commit_wait_ms", _F()),
                                 ("sum_admission_wait_ms", _F())),
                           _gen_stmt_summary),
    "metrics_summary": (_cols(("metrics_name", _S()), ("labels", _S()),
                              ("sum_value", _F())),
                        _gen_metrics),
    "tidb_errors": (_cols(("error", _S()), ("code", _I()),
                          ("sqlstate", _S())), _gen_errors),
    "tidb_trace_events": (_cols(("time", _F()), ("conn_id", _I()),
                                ("depth", _I()), ("span", _S()),
                                ("duration_ms", _F()), ("attrs", _S()),
                                ("trace_id", _S()), ("span_id", _S()),
                                ("parent_id", _S()), ("worker", _S())),
                          _gen_trace_events),
    "tidb_plan_feedback": (_cols(("sql_digest", _S()), ("sql_text", _S()),
                                 ("op", _S()), ("exec_count", _I()),
                                 ("calls", _I()),
                                 ("avg_est_rows", _F()),
                                 ("avg_act_rows", _F()),
                                 ("max_drift", _F()),
                                 ("mean_drift", _F()),
                                 ("backends", _S()), ("route", _S()),
                                 ("sum_device_ms", _F()),
                                 ("sum_host_ms", _F()),
                                 ("sum_op_ms", _F())),
                           _gen_plan_feedback),
    "tidb_top_sql": (_cols(("sql_digest", _S()), ("sql_text", _S()),
                           ("exec_count", _I()),
                           ("sum_ms", _F()), ("avg_ms", _F()),
                           ("sum_device_ms", _F()),
                           ("sum_compile_ms", _F()),
                           ("sum_host_ms", _F()),
                           ("sum_fetch_ms", _F()),
                           ("sum_upload_ms", _F()),
                           ("kernel_builds", _I()),
                           ("dispatches", _I()),
                           ("upload_bytes", _I()),
                           ("fetch_bytes", _I()),
                           ("fallback_count", _I()),
                           ("sum_errors", _I()),
                           ("delta_applies", _I()),
                           ("delta_bytes", _I()),
                           ("max_drift", _F()),
                           ("mean_drift", _F()),
                           ("replica_reads", _I()),
                           ("leader_fallbacks", _I()),
                           ("degraded_midstmt", _I())), _gen_top_sql),
    "deadlocks": (_cols(("deadlock_id", _I()), ("occur_time", _F()),
                        ("retryable", _I()), ("try_lock_trx_id", _I()),
                        ("key", _S()), ("trx_holding_lock", _I())),
                  _gen_deadlocks),
    "data_lock_waits": (_cols(("key", _S()), ("trx_id", _I()),
                              ("current_holding_trx_id", _I())),
                        _gen_data_lock_waits),
    "tidb_changefeeds": (_cols(("changefeed", _S()), ("state", _S()),
                               ("sink", _S()), ("start_ts", _I()),
                               ("checkpoint_ts", _I()),
                               ("resolved_ts", _I()),
                               ("resolved_ts_lag_s", _F()),
                               ("emitted_txns", _I()),
                               ("emitted_rows", _I()),
                               ("error", _S())), _gen_changefeeds),
    "tidb_replica_freshness": (_cols(("table_schema", _S()),
                                     ("table_name", _S()),
                                     ("resolved_ts", _I()),
                                     ("lag_ms", _F()),
                                     ("pending_delta_rows", _I()),
                                     ("mode", _S()),
                                     ("replica", _S()),
                                     ("state", _S()),
                                     ("routed_queries", _I())),
                               _gen_replica_freshness),
    "tidb_vector_indexes": (_cols(("table_schema", _S()),
                                  ("table_name", _S()),
                                  ("index_name", _S()),
                                  ("column_name", _S()),
                                  ("centroids", _I()),
                                  ("rows", _I()),
                                  ("pending_delta_rows", _I()),
                                  ("last_train_ts", _F())),
                            _gen_vector_indexes),
    "tidb_models": (_cols(("model_name", _S()),
                          ("uri", _S()),
                          ("kind", _S()),
                          ("params", _S()),
                          ("weight_bytes", _I()),
                          ("version", _I()),
                          ("created_ts", _F()),
                          ("device_resident_bytes", _I()),
                          ("predict_calls", _I()),
                          ("predict_rows", _I())),
                    _gen_tidb_models),
    "ddl_jobs": (_cols(("job_id", _I()), ("job_type", _S()),
                       ("state", _S()), ("schema_state", _S()),
                       ("db_name", _S()), ("table_name", _S()),
                       ("table_id", _I()), ("row_count", _I()),
                       ("total_rows", _I()),
                       ("checkpoint_handle", _I()),
                       ("start_time", _F()), ("error", _S())),
                 _gen_ddl_jobs),
    "tidb_backup_jobs": (_cols(("job_id", _I()), ("kind", _S()),
                               ("phase", _S()), ("state", _S()),
                               ("backup_ts", _I()), ("bytes", _I()),
                               ("checkpoint", _S()), ("error", _S())),
                         _gen_backup_jobs),
    "placement_policies": (_cols(("policy_name", _S()),
                                 ("settings", _S()),
                                 ("attached_tables", _S())),
                           _gen_placement_policies),
    "resource_groups": (_cols(("name", _S()), ("ru_per_sec", _I()),
                              ("priority", _S()), ("burstable", _S()),
                              ("query_limit", _S()),
                              ("consumed_ru", _F()),
                              ("throttled_statements", _I())),
                        _gen_resource_groups),
    "engines": (_cols(("engine", _S()), ("support", _S()), ("comment", _S()),
                      ("transactions", _S()), ("xa", _S()),
                      ("savepoints", _S())), _gen_engines),
    "collations": (_cols(("collation_name", _S()), ("character_set_name", _S()),
                         ("id", _I()), ("is_default", _S()),
                         ("is_compiled", _S()), ("sortlen", _I())),
                   _gen_collations),
    "character_sets": (_cols(("character_set_name", _S()),
                             ("default_collate_name", _S()),
                             ("description", _S()), ("maxlen", _I())),
                       _gen_character_sets),
    "tidb_indexes": (_cols(("table_schema", _S()), ("table_name", _S()),
                           ("non_unique", _I()), ("key_name", _S()),
                           ("seq_in_index", _I()), ("column_name", _S())),
                     _gen_tidb_indexes),
    "processlist": (_cols(("id", _I()), ("user", _S()), ("host", _S()),
                          ("db", _S()), ("command", _S()), ("time", _I()),
                          ("info", _S())), _gen_processlist),
    "cluster_info": (_cols(("type", _S()), ("instance", _S()),
                           ("status_address", _S()), ("version", _S()),
                           ("git_hash", _S())), _gen_cluster_info),
    "views": (_cols(("table_schema", _S()), ("table_name", _S()),
                    ("view_definition", _S())), _gen_views),
    "key_column_usage": (_cols(
        ("constraint_catalog", _S()), ("constraint_schema", _S()),
        ("constraint_name", _S()), ("table_schema", _S()),
        ("table_name", _S()), ("column_name", _S()),
        ("ordinal_position", _I()), ("referenced_table_schema", _S()),
        ("referenced_table_name", _S()), ("referenced_column_name", _S())),
        _gen_key_column_usage),
    "referential_constraints": (_cols(
        ("constraint_catalog", _S()), ("constraint_schema", _S()),
        ("constraint_name", _S()), ("unique_constraint_schema", _S()),
        ("delete_rule", _S()), ("table_name", _S()),
        ("referenced_table_name", _S())), _gen_referential_constraints),
    "partitions": (_cols(("table_schema", _S()), ("table_name", _S()),
                         ("partition_name", _S())), _gen_partitions),
    # duplicate-resolution report for IMPORT INTO ... on_duplicate=skip
    # (reference lightning conflict detection: skipped rows are
    # queryable, not silently dropped)
    "tidb_import_conflicts": (_cols(
        ("table_name", _S()), ("source", _S()), ("handle", _I()),
        ("conflict", _S()), ("row_preview", _S()), ("time", _F())),
        lambda domain: list(getattr(domain, "_import_conflicts", []))),
    "memory_usage": (_cols(("conn_id", _I()), ("scope", _S()),
                           ("label", _S()), ("consumed", _I()),
                           ("max_consumed", _I()), ("quota", _I()),
                           ("oom_action", _S())), _gen_memory_usage),
    "cluster_health": (_cols(("worker_id", _I()), ("addr", _S()),
                             ("state", _S()), ("epoch", _I()),
                             ("role", _S()),
                             ("heartbeat_lag_ms", _F()),
                             ("inflight", _I()),
                             ("dedup_hits", _I())),
                       _gen_cluster_health),
}

_VIRT_INFO_CACHE: dict = {}
_VIRT_INFO_MU = threading.Lock()  # info reads race from any connection


def virtual_table_info(name: str) -> TableInfo | None:
    name = name.lower()
    d = VIRTUAL_DEFS.get(name)
    if d is None:
        return None
    ti = _VIRT_INFO_CACHE.get(name)     # lockless fast path
    if ti is not None:
        return ti
    cols_spec, _ = d
    vid = -(1000 + list(VIRTUAL_DEFS.keys()).index(name))
    cols = [ColumnInfo(id=i + 1, name=cn, offset=i, ft=ft)
            for i, (cn, ft) in enumerate(cols_spec)]
    ti = TableInfo(id=vid, name=name, columns=cols)
    with _VIRT_INFO_MU:
        return _VIRT_INFO_CACHE.setdefault(name, ti)


def virtual_rows(domain, table_info) -> list:
    _, gen = VIRTUAL_DEFS[table_info.name.lower()]
    return list(gen(domain))
