"""System variable registry (reference pkg/sessionctx/variable/sysvar.go +
vardef/tidb_vars.go). Scopes: GLOBAL / SESSION / both. The TPU toggle
`tidb_enable_tpu_exec` follows the reference's
`tidb_enable_vectorized_expression` pattern (vardef/tidb_vars.go:672)."""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable

from ..errors import UnknownSystemVariableError, WrongValueForVarError

SCOPE_GLOBAL = 1
SCOPE_SESSION = 2
SCOPE_BOTH = 3


@dataclass
class SysVar:
    name: str
    scope: int
    default: object
    type: str = "str"          # str | int | bool | float | enum
    min_val: int | None = None
    max_val: int | None = None
    enum_vals: list = field(default_factory=list)
    validate: Callable | None = None

    def coerce(self, value):
        if self.type == "bool":
            if isinstance(value, bool):
                return value
            s = str(value).lower()
            if s in ("1", "on", "true", "yes"):
                return True
            if s in ("0", "off", "false", "no"):
                return False
            raise WrongValueForVarError(
                "Variable '%s' can't be set to the value of '%s'", self.name, value)
        if self.type == "int":
            try:
                v = int(value)
            except (TypeError, ValueError):
                raise WrongValueForVarError(
                    "Variable '%s' can't be set to the value of '%s'", self.name, value)
            if self.min_val is not None:
                v = max(v, self.min_val)
            if self.max_val is not None:
                v = min(v, self.max_val)
            return v
        if self.type == "float":
            try:
                v = float(value)
            except (TypeError, ValueError):
                raise WrongValueForVarError(
                    "Variable '%s' can't be set to the value of '%s'", self.name, value)
            if self.validate is not None and not self.validate(v):
                raise WrongValueForVarError(
                    "Variable '%s' can't be set to the value of '%s'", self.name, value)
            return v
        if self.type == "enum":
            s = str(value).lower()
            if s not in self.enum_vals:
                raise WrongValueForVarError(
                    "Variable '%s' can't be set to the value of '%s'", self.name, value)
            return s
        return str(value)


from ..utils import env_int as _env_int  # shared with storage lock knobs


def _jax_cache_dir_default() -> str:
    """The persistent-cache directory IN FORCE ('' = degraded). Read
    from jaxcfg when it is already loaded — its persistent_cache_dir
    is None when setup failed (unwritable directory), and SHOW
    VARIABLES must report that reality. Via
    sys.modules only: this module stays jax-import-free. When jaxcfg
    loads later it publishes the real outcome into this var itself
    (jaxcfg._publish_cache_sysvar)."""
    import sys
    jc = sys.modules.get("tidb_tpu.utils.jaxcfg")
    if jc is not None:
        return getattr(jc, "persistent_cache_dir", None) or ""
    # jaxcfg not loaded yet: report the resolution; the publish hook
    # overwrites it with the configured outcome at jaxcfg import
    from ..utils import resolve_jax_cache_dir
    return resolve_jax_cache_dir()


def _env_oom_action() -> str:
    """TIDB_TPU_OOM_ACTION seed for the quota-breach action sysvar
    (smoke harnesses configure child processes before a session
    exists); anything but 'log' means the strict 'cancel' default."""
    import os
    v = os.environ.get("TIDB_TPU_OOM_ACTION", "cancel").lower()
    return v if v in ("cancel", "log") else "cancel"


def _env_read_mode() -> str:
    """TIDB_TPU_ANALYTIC_READ_MODE seed for the analytic read-mode
    sysvar (bench/smoke harnesses flip it per process); anything but
    'resolved' means the strict default."""
    import os
    v = os.environ.get("TIDB_TPU_ANALYTIC_READ_MODE", "leader").lower()
    return v if v in ("leader", "resolved") else "leader"


_REGISTRY: dict[str, SysVar] = {}
# plugins register sysvars after startup, concurrently with sessions
# resolving them; reads stay lockless (GIL-atomic dict get)
_REGISTRY_MU = threading.Lock()


def register(var: SysVar):
    with _REGISTRY_MU:
        _REGISTRY[var.name.lower()] = var


def get_sysvar(name: str) -> SysVar:
    v = _REGISTRY.get(name.lower())
    if v is None:
        raise UnknownSystemVariableError("Unknown system variable '%s'", name)
    return v


def all_sysvars():
    return dict(_REGISTRY)


for _v in [
    SysVar("tidb_enable_tpu_exec", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_enable_vectorized_expression", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_max_chunk_size", SCOPE_BOTH, 1 << 17, "int", 32, 1 << 24),
    SysVar("tidb_init_chunk_size", SCOPE_BOTH, 32, "int", 1, 32768),
    SysVar("tidb_mem_quota_query", SCOPE_BOTH, 1 << 30, "int", 128 << 10, None),
    SysVar("tidb_executor_concurrency", SCOPE_BOTH, 8, "int", 1, 256),
    SysVar("tidb_distsql_scan_concurrency", SCOPE_BOTH, 8, "int", 1, 256),
    SysVar("tidb_opt_agg_push_down", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_enable_mpp", SCOPE_BOTH, True, "bool"),
    # memo-based join search (reference cascades dispatch
    # optimizer.go:335-341); default off like the reference
    SysVar("tidb_enable_cascades_planner", SCOPE_BOTH, False, "bool"),
    SysVar("tidb_mpp_min_rows", SCOPE_BOTH, 1 << 16, "int", 0, None),
    # hash-exchange frame capacity FIRST GUESS (slots per (sender,
    # destination) peer) for the all_to_all shuffle join. 0 = auto:
    # balanced-load estimate, corrected by the device-computed exact
    # bound with one re-trace on overflow (mpp/exec.py). A nonzero pin
    # seeds the guess only — overflow is still detected and re-traced,
    # so a too-small pin can never drop rows.
    SysVar("tidb_tpu_mpp_shuffle_cap", SCOPE_BOTH,
           _env_int("TIDB_TPU_MPP_SHUFFLE_CAP", 0), "int", 0, 1 << 24),
    # vector search (tidb_tpu/vector/, docs/VECTOR.md): IVF partitions
    # probed per ANN query — the recall/speed trade. 0 disables the
    # index path entirely (ORDER BY vec_*_distance LIMIT k runs the
    # exact single-dispatch scan).
    SysVar("tidb_tpu_vector_nprobe", SCOPE_BOTH,
           _env_int("TIDB_TPU_VECTOR_NPROBE", 8), "int", 0, 1 << 10),
    SysVar("tidb_join_exec", SCOPE_BOTH, "auto", "enum",
           enum_vals=["auto", "host", "device"]),
    SysVar("last_plan_from_binding", SCOPE_SESSION, False, "bool"),
    SysVar("tidb_read_staleness", SCOPE_SESSION, 0, "int", -86400, 0),
    SysVar("version_comment", SCOPE_BOTH, "tidb-tpu (MXU-native TiDB)",
           "str"),
    SysVar("max_execution_time", SCOPE_BOTH, 0, "int", 0, None),
    SysVar("tidb_allow_mpp", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_broadcast_join_threshold_size", SCOPE_BOTH, 100 << 20, "int", 0, None),
    SysVar("tidb_broadcast_join_threshold_count", SCOPE_BOTH, 10240 * 100, "int", 0, None),
    SysVar("tidb_device_batch_rows", SCOPE_BOTH, 1 << 22, "int", 1 << 10, 1 << 26),
    SysVar("tidb_txn_mode", SCOPE_BOTH, "pessimistic", "enum",
           enum_vals=["optimistic", "pessimistic"]),
    # commit fast paths (reference vardef/tidb_vars.go:815
    # TiDBEnableAsyncCommit / TiDBEnable1PC + the async-commit caps)
    SysVar("block_encryption_mode", SCOPE_BOTH, "aes-128-ecb", "enum",
           enum_vals=["aes-128-ecb", "aes-192-ecb", "aes-256-ecb",
                      "aes-128-cbc", "aes-192-cbc", "aes-256-cbc",
                      "aes-128-ofb", "aes-192-ofb", "aes-256-ofb",
                      "aes-128-cfb128", "aes-192-cfb128",
                      "aes-256-cfb128"]),
    SysVar("tidb_enable_table_lock", SCOPE_BOTH, False, "bool"),
    SysVar("tidb_enable_async_commit", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_enable_1pc", SCOPE_BOTH, True, "bool"),
    SysVar("tidb_async_commit_keys_limit", SCOPE_BOTH, 256, "int",
           1, None),
    SysVar("tidb_async_commit_total_key_size_limit", SCOPE_BOTH,
           4 << 10, "int", 1, None),
    SysVar("tidb_retry_limit", SCOPE_BOTH, 10, "int", 0, 100),
    SysVar("autocommit", SCOPE_BOTH, True, "bool"),
    SysVar("sql_mode", SCOPE_BOTH, "STRICT_TRANS_TABLES", "str"),
    SysVar("time_zone", SCOPE_BOTH, "SYSTEM", "str"),
    SysVar("max_allowed_packet", SCOPE_BOTH, 67108864, "int", 1024, 1 << 30),
    SysVar("div_precision_increment", SCOPE_BOTH, 4, "int", 0, 30),
    SysVar("tidb_slow_log_threshold", SCOPE_BOTH, 300, "int", -1, None),
    SysVar("tidb_enable_collect_execution_info", SCOPE_BOTH, True, "bool"),
    # device supervision (utils/device_guard; env seeds the defaults so
    # harnesses configure child processes before any session exists; a
    # malformed env value falls back rather than killing the import)
    SysVar("tidb_tpu_device_retry_limit", SCOPE_BOTH,
           _env_int("TIDB_TPU_DEVICE_RETRY_LIMIT", 2), "int", 0, 64),
    SysVar("tidb_tpu_device_dispatch_timeout_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_DEVICE_DISPATCH_TIMEOUT_MS", 0),
           "int", 0, 3_600_000),
    SysVar("tidb_tpu_device_breaker_threshold", SCOPE_BOTH,
           _env_int("TIDB_TPU_DEVICE_BREAKER_THRESHOLD", 8),
           "int", 1, 1 << 20),
    # transaction lock lifecycle (storage/lock_resolver): TTL on locks a
    # txn creates (heartbeat-extended per statement), how long a blocked
    # statement waits on a foreign lock before ER 1205, and the wait
    # queue's poll backoff. Env seeds mirror lock_resolver defaults.
    SysVar("tidb_tpu_lock_ttl_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_LOCK_TTL_MS", 3000), "int", 50, 3_600_000),
    SysVar("tidb_tpu_lock_wait_timeout_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_LOCK_WAIT_MS", 1000), "int",
           0, 3_600_000),
    SysVar("tidb_tpu_lock_wait_backoff_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_LOCK_WAIT_BACKOFF_MS", 10), "int", 1, 1000),
    # changefeed worker poll cadence (tidb_tpu/cdc): how often each
    # feed advances its resolved-ts watermark and drains to its sink
    SysVar("tidb_tpu_cdc_poll_interval_ms", SCOPE_GLOBAL,
           _env_int("TIDB_TPU_CDC_POLL_INTERVAL_MS", 50), "int",
           1, 60_000),
    # fragment selection (copr/dag_exec, docs/PERFORMANCE.md): a
    # filter/top-n-only copr fragment below this many rows runs the
    # host twin instead of paying a whole host<->device round trip for
    # microseconds of kernel work; 0 dispatches every fragment
    SysVar("tidb_tpu_fragment_min_rows", SCOPE_BOTH,
           _env_int("TIDB_TPU_FRAGMENT_MIN_ROWS", 1 << 21), "int",
           0, 1 << 40),
    # OLTP serving fast path (session/fastpath.py): digest-keyed
    # point-get/batch-point-get plan templates served without the
    # planner or an executor tree. SET ... = 0 falls back to the full
    # statement pipeline (debugging / plan-behavior A-B tests).
    SysVar("tidb_tpu_plan_fastpath", SCOPE_BOTH,
           _env_int("TIDB_TPU_PLAN_FASTPATH", 1) != 0, "bool"),
    # admission control (session/resource_group.py): how many ANALYTIC
    # statements one resource group runs concurrently (the OLAP half of
    # the OLAP-vs-OLTP dispatch split; point ops never queue). 0
    # disables the queue. Default: half the cores — analytics keep
    # real parallelism while point ops always find the interpreter.
    SysVar("tidb_tpu_olap_admission_slots", SCOPE_BOTH,
           _env_int("TIDB_TPU_OLAP_ADMISSION_SLOTS",
                    max(2, (__import__("os").cpu_count() or 4) // 2)),
           "int", 0, 4096),
    # incremental HTAP read routing (docs/PERFORMANCE.md "Incremental
    # HTAP"): 'resolved' snapshots analytic (olap-classified)
    # statements at the replica's resolved-ts floor — committed-data
    # freshness with no OLTP lock contention and no dirty-overlay
    # rescans, but NOT read-your-own-uncommitted-writes (an explicit
    # opt-in, like tidb_read_staleness); 'leader' (default) keeps the
    # strict leader path.
    SysVar("tidb_tpu_analytic_read_mode", SCOPE_BOTH,
           _env_read_mode(), "enum",
           enum_vals=["leader", "resolved"]),
    # staleness bound for resolved-mode reads: when the resolved floor
    # lags wallclock by more than this (a long-open transaction holds
    # it down), the statement falls back to the strict leader path
    # instead of serving arbitrarily stale rows. 0 = no bound.
    SysVar("tidb_tpu_analytic_max_staleness_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_ANALYTIC_MAX_STALENESS_MS", 5000),
           "int", 0, 1 << 31),
    # read-replica routing SLA (tidb_tpu/replica): an olap resolved
    # read is served by a replica domain only when the replica's
    # applied watermark lags wallclock by at most this; otherwise the
    # statement transparently degrades to the leader. 0 = any serving
    # replica qualifies regardless of lag.
    SysVar("tidb_tpu_replica_max_lag_ms", SCOPE_BOTH,
           _env_int("TIDB_TPU_REPLICA_MAX_LAG_MS", 5000),
           "int", 0, 1 << 31),
    # delta fold ceiling (copr/delta.py): a per-entry delta larger
    # than this many rows drops the buffer for a full re-upload
    # instead of patching (past a point the patch costs more than the
    # upload it avoids).
    SysVar("tidb_tpu_delta_max_rows", SCOPE_BOTH,
           _env_int("TIDB_TPU_DELTA_MAX_ROWS", 1 << 20),
           "int", 0, 1 << 40),
    # online-DDL reorg batch size (owner/ddl_runner.py): rows per
    # backfill transaction = the checkpoint granularity. Each batch
    # commits through the normal 2PC path and then persists the
    # high-water handle in the job record, so a crashed reorg resumes
    # at the recorded handle range (the reference
    # tidb_ddl_reorg_batch_size).
    SysVar("tidb_tpu_ddl_reorg_batch_size", SCOPE_BOTH,
           _env_int("TIDB_TPU_DDL_REORG_BATCH", 2048),
           "int", 16, 1 << 20),
    # memory-governance action chain (utils/memory.py,
    # docs/ROBUSTNESS.md "Memory safety"): what the quota-breach chain
    # does AFTER logging and after every registered operator spill has
    # been armed — 'cancel' kills the statement with ER 8175 (the
    # reference tidb_mem_oom_action=CANCEL), 'log' records the breach
    # and lets the statement proceed.
    SysVar("tidb_tpu_oom_action", SCOPE_BOTH,
           _env_oom_action(), "enum", enum_vals=["cancel", "log"]),
    # server-level memory limit in bytes (the tidb_server_memory_limit
    # analog): when the GLOBAL tracker root exceeds it, the controller
    # cancels the single largest-consumer statement with ER 8175 —
    # shed one query, never wedge or die. 0 disables.
    SysVar("tidb_tpu_server_memory_limit", SCOPE_GLOBAL,
           _env_int("TIDB_TPU_SERVER_MEMORY_LIMIT", 0), "int",
           0, 1 << 50),
    # WAL group commit (storage/wal.py): leader/follower batched
    # flush+fsync across concurrently committing sessions. Process
    # config read at store open (env TIDB_TPU_WAL_GROUP_COMMIT seeds
    # it); surfaced GLOBAL for SHOW VARIABLES/dashboards — a changed
    # value applies at the next store open, not mid-flight.
    SysVar("tidb_tpu_wal_group_commit", SCOPE_GLOBAL,
           _env_int("TIDB_TPU_WAL_GROUP_COMMIT", 1) != 0, "bool"),
    # persistent XLA compilation cache (utils/jaxcfg): the directory
    # warmup compiles amortize into across processes. Surfaced as a
    # GLOBAL sysvar (SHOW VARIABLES / dashboards), resolved with the
    # same precedence jaxcfg applies at import time (without importing
    # jax here); '' means disabled. Process-global jax config: a
    # changed value applies via jaxcfg at the next process start, not
    # mid-session.
    SysVar("tidb_tpu_jax_cache_dir", SCOPE_GLOBAL,
           _jax_cache_dir_default(), "str"),
    # fraction of statements whose trace flushes to the flight
    # recorder. 0.0 keeps the OLTP fast path out of the ring entirely;
    # TRACE <stmt> and slow statements are always captured regardless.
    SysVar("tidb_tpu_trace_sample_rate", SCOPE_BOTH, 0.0, "float",
           validate=lambda v: 0.0 <= float(v) <= 1.0),
]:
    register(_v)


class SessionVars:
    """Per-session variable values over the registry defaults + globals."""

    def __init__(self, global_vars: dict | None = None):
        self._globals = global_vars if global_vars is not None else {}
        self._session: dict[str, object] = {}
        self.current_db = ""
        self.in_txn = False
        self.last_insert_id = 0
        self.affected_rows = 0
        self.found_rows = 0
        self.last_affected = 0
        self.warnings: list = []

    def get(self, name: str):
        key = name.lower()
        if key in self._session:
            return self._session[key]
        if key in self._globals:
            return self._globals[key]
        return get_sysvar(name).default

    def set(self, name: str, value, is_global=False):
        var = get_sysvar(name)
        v = var.coerce(value)
        if is_global:
            if not var.scope & SCOPE_GLOBAL:
                raise WrongValueForVarError(
                    "Variable '%s' is a SESSION variable", name)
            self._globals[name.lower()] = v
        else:
            if not var.scope & SCOPE_SESSION:
                raise WrongValueForVarError(
                    "Variable '%s' is a GLOBAL variable", name)
            self._session[name.lower()] = v

    # convenience accessors for hot flags
    @property
    def tpu_exec(self) -> bool:
        return bool(self.get("tidb_enable_tpu_exec"))

    @property
    def max_chunk_size(self) -> int:
        return int(self.get("tidb_max_chunk_size"))

    @property
    def mem_quota_query(self) -> int:
        return int(self.get("tidb_mem_quota_query"))

    @property
    def div_precision_increment(self) -> int:
        return int(self.get("div_precision_increment"))

    @property
    def autocommit(self) -> bool:
        return bool(self.get("autocommit"))
