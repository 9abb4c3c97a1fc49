"""In-process coprocessor: executes a pushed-down CoprDAG on device
(reference role: TiKV coprocessor handling tipb.DAGRequest —
unistore/cophandler/closure_exec.go:167; re-designed TPU-first).

One partition = one jit call. The kernel fuses:
    scan columns -> filter conjuncts -> validity mask
    -> either per-row outputs (mask returned, host gathers from numpy)
    -> or partial aggregation (sort-based grouping + segment reduce)

Static shapes via bucketed padding; kernel cache keyed by
(dag fingerprint, bucket, dtypes, dict versions, group bucket).
NULL-aware throughout (masks). Strings ride as dict codes.
"""
from __future__ import annotations

import functools
import os
import time

import numpy as np

from ..utils import jaxcfg
import jax
import jax.numpy as jnp

from ..expression import EvalCtx, eval_expr, eval_bool_mask
from ..expression.vec import materialize_nulls
from ..utils import env_int
from ..utils.fetch import prefetch, host_array, host_int
from .residency import DeviceResidentStore
from . import agg_lowering as _al
from .agg_lowering import (PartialAggResult, capture_agg_dicts, dense_strides,
                           dense_agg_body, sort_agg_body, psum_dense_result,
                           compact_dense, host_partial_agg)
from ..utils import phase
from ..utils import device_guard
from ..utils import metrics as _metrics
from ..utils import tracing as _tracing
from ..errors import TiDBError
from ..chunk.device import shape_bucket, shard_lanes
from ..chunk.column import Column
from ..chunk.chunk import Chunk

_I64_MAX = np.iinfo(np.int64).max


# what _try_execute_mpp answers where the mesh has no lowering for the
# aggregation: the caller runs it single-chip (None is a degraded run's)
_NO_DENSE_LAYOUT = object()

# _bind_keys' record of a row block's visibility mask, beside the
# columns' (theirs are under the plan column's idx)
_MASK = "valid"


@jax.jit
def tidb_mask_copy(vv):
    """A fresh device buffer holding `vv`: what a program that donates
    its mask operand gets of a resident mask (_mask_operand). The
    `def` carries the program's name (jaxcfg.name_program)."""
    return jnp.copy(vv)


class _KernelCache(dict):
    """Compiled-kernel cache with hit/miss counters (reference
    coprocessor_cache.go metrics; surfaced per-operator by
    EXPLAIN ANALYZE's backend column). Every inserted kernel is
    wrapped with phase accounting (utils/phase.py): dispatch counts
    and enqueue time, and the `dispatch` span with the key's kind."""

    def __init__(self):
        super().__init__()
        self.hits = 0
        self.misses = 0

    def __setitem__(self, key, fn):
        kind = key[0] if isinstance(key, tuple) and key and \
            isinstance(key[0], str) else "kern"
        dict.__setitem__(self, key, phase.timed_kernel(kind, fn))

    def put(self, key, fn):
        """Insert and return the phase-wrapped kernel — call sites must
        dispatch the returned callable, not the raw one, or the first
        (compiling) call vanishes from the phase stats."""
        self[key] = fn
        return dict.__getitem__(self, key)

    def get(self, key, default=None):
        v = super().get(key, default)
        if v is None:
            self.misses += 1
            _metrics.KERNEL_CACHE.labels("miss").inc()
        else:
            self.hits += 1
            _metrics.KERNEL_CACHE.labels("hit").inc()
        return v


def _as_row_block(run):
    """A partition runner that takes `part=(i, n)` and runs as row block
    i of n (phase.row_block). Opened inside the runner, not round the
    guarded call: the watchdog may move the dispatch onto a worker
    thread, and the block is the thread's."""
    @functools.wraps(run)
    def runner(self, *args, part):
        with phase.row_block(*part):
            return run(self, *args)
    return runner


class CoprExecutor:
    """Executes CoprDAGs against ColumnarTables; caches compiled kernels."""

    def __init__(self, engine, device_rows=None, use_device=True,
                 dev_cache_bytes=8 << 30):
        self.engine = engine            # ColumnarEngine
        if device_rows is None:
            # partition size (rows per jit call): every partition
            # costs a fixed dispatch + fetch, so fewer/bigger
            # partitions win until HBM pressure. 4,194,304 rows is what
            # every chip run since PR 24 measured (SF1 as two blocks,
            # scale 3 as five: PERF.md sections 5 and 6); no other size
            # has been (ROADMAP D2, S4). It bounds a one-chip program
            # only: the mesh route takes a shard whole
            # (chunk.device.shard_lanes), 5,242,880 lanes at scale 3 on
            # four chips (PR 34; docs/PERFORMANCE.md "Processes and
            # chips")
            device_rows = int(os.environ.get("TIDB_TPU_DEVICE_ROWS",
                                             str(1 << 22)))
        self.device_rows = device_rows
        self.use_device = use_device
        # fragment selection (docs/PERFORMANCE.md): a filter/top-n-only
        # fragment below this many rows runs the host twin — its kernel
        # computes in µs what the host↔device round trip costs, so
        # dispatching it can only lose. Aggregation fragments always
        # dispatch: their partials shrink the fetch to group
        # cardinality, which is the thesis. The 2M floor has not been
        # measured on this chip (ROADMAP D2, S5).
        self.fragment_min_rows = env_int("TIDB_TPU_FRAGMENT_MIN_ROWS",
                                         1 << 21)
        self._kernel_cache = _KernelCache()
        self.last_backend = ""          # backend of the latest execute()
        # device-resident columnar store: column buffers stay in HBM
        # across statements, keyed by (table, ..., version, ...) and
        # eagerly invalidated when a DML commit bumps the version —
        # the "per-query device buffer pool" of SURVEY.md §5
        # generalized to cross-statement residency (copr/residency.py)
        self._dev_store = DeviceResidentStore(dev_cache_bytes)
        # HBM pressure protocol (utils/device_guard): a
        # RESOURCE_EXHAUSTED dispatch sheds cold resident entries from
        # this pool before retrying; weakly registered so discarded
        # test/mirror domains stay collectable
        device_guard.register_pressure_store(self._dev_store)
        # incremental HTAP (copr/delta.py): folds committed deltas
        # into resident buffers at bind time instead of letting the
        # version sweep drop-and-reupload them whole; also the
        # freshness bookkeeping behind tidb_replica_freshness
        from .delta import DeltaMaintainer
        self.delta = DeltaMaintainer(self)
        # host-side per-version metadata: dim sort orders, learned group
        # bucket sizes (so the regrow loop doesn't re-run every query)
        self._host_cache: dict = {}

    def _upload_padded(self, arr_np, cap, pad_fill=0, mesh=None,
                       spec="local"):
        """THE upload tail shared by every resident-store seam: pad to
        ``cap``, place by spec (local jnp / row-sharded / replicated),
        account upload phases and the Broadcast exchange. -> (dev,
        ndev). Fixes to upload accounting or placement live here
        once. Every caller places the buffer in the resident store,
        where it outlives the statement: its bytes are charged to the
        store's budget (DeviceResidentStore.put), never to the memory
        tracker of the statement that happened to fault it in — a
        first touch of a large table is not that statement's to be
        cancelled for (ER 8175), and HBM pressure is the store's to
        relieve (LRU, device_guard's pressure protocol)."""
        import jax
        t0 = time.perf_counter()
        arr = arr_np
        if len(arr) != cap:
            arr = np.concatenate(
                [arr, np.full(cap - len(arr), pad_fill,
                              dtype=arr.dtype)])
        ndev = 1
        if mesh is None or spec == "local":
            dev = jnp.asarray(arr)
            moved = dev.size * dev.dtype.itemsize
        elif spec == "sharded":
            from ..parallel import row_sharding
            dev = jax.device_put(arr, row_sharding(mesh))
            ndev = int(mesh.devices.size)
            moved = dev.size * dev.dtype.itemsize
        else:
            from ..parallel import replicated_sharding
            dev = jax.device_put(arr, replicated_sharding(mesh))
            ndev = int(mesh.devices.size)
            moved = dev.size * dev.dtype.itemsize * ndev
            _metrics.MPP_EXCHANGE.labels("broadcast").inc()
            _metrics.MPP_EXCHANGE_BYTES.labels("broadcast").inc(moved)
        phase.add("upload_s", time.perf_counter() - t0)
        phase.add("upload_bytes", moved)
        phase.inc("uploads")
        return dev, ndev

    def _dev_put(self, key, arr_np, pad_fill=0, uid=None, version=None):
        """Upload (padded) into the resident store; returns the device
        array. uid/version feed eager invalidation (defaults: key[0] is
        the table uid by every caller's key layout; version None means
        LRU/uid-wide eviction only)."""
        hit = self._dev_store.get(key)
        if hit is not None:
            phase.inc("upload_hits")
            _metrics.DEV_BUFFER_POOL.labels("hit").inc()
            return hit
        _metrics.DEV_BUFFER_POOL.labels("miss").inc()
        dev, _ndev = self._upload_padded(arr_np, key[-1],
                                         pad_fill=pad_fill)
        self._dev_store.put(key, dev, dev.size * dev.dtype.itemsize,
                            uid=key[0] if uid is None else uid,
                            version=version)
        return dev

    # ---- public -------------------------------------------------------
    def execute(self, dag, overlay=None, read_ts=None, use_mpp=False,
                mpp_min_rows=1 << 16, ectx=None) -> list:
        """-> list of host Chunks (schema = dag.cols, or partial agg layout:
        [group_keys..., group_nullflags..., agg_states...]).

        overlay: {handle: row_datums|None} from the session's dirty txn
        memBuffer — UnionScan semantics (reference executor/builder.go:1473):
        deleted/updated committed rows are masked out, buffered rows are
        appended before filters run."""
        # reset per call: empty-snapshot / virtual-table paths return
        # early without running a backend — a stale tag from the
        # previous execute must not leak into EXPLAIN ANALYZE
        self.last_backend = ""
        dom = getattr(self, "domain", None)
        t0 = time.perf_counter()
        try:
            if dom is not None:
                with dom.tracer.span("copr",
                                     table=dag.table_info.name):
                    return self._execute_inner(dag, overlay, read_ts,
                                               use_mpp, mpp_min_rows, ectx)
            return self._execute_inner(dag, overlay, read_ts, use_mpp,
                                       mpp_min_rows, ectx)
        finally:
            # labeled by the backend that actually served the DAG
            # ("none" = early return: empty snapshot / virtual table)
            _metrics.COPR_DISPATCH_SECONDS.labels(
                self.last_backend or "none").observe(
                time.perf_counter() - t0)

    def _execute_inner(self, dag, overlay, read_ts, use_mpp,
                       mpp_min_rows, ectx=None):
        if dag.table_info.id <= -1000:      # INFORMATION_SCHEMA virtual
            tbl = self._materialize_virtual(dag.table_info)
            read_ts = None
        else:
            tbl = self.engine.table(dag.table_info)
            if dag.table_info.id < 0:
                read_ts = None              # session temp table: read latest
        with phase.bind_span():
            if dag.table_info.id > -1000:
                # incremental HTAP (copr/delta.py): fold committed
                # deltas into the resident buffers FIRST — patched
                # entries advance their version in place and survive
                # the sweep below — then drop whatever is still stale
                # (derived entries: validity masks, luts; and
                # unpatchable buffers). Without the fold this sweep was
                # a full drop-and-reupload per DML commit.
                self.delta.refresh(tbl, ectx)
                self._dev_store.invalidate(tbl.uid, tbl.version)
            arrays, valid = tbl.snapshot(
                [cid for cid in (self._cid(dag, sc) for sc in dag.cols)
                 if cid != -1], read_ts)
        n = len(valid)          # snapshot length, not live tbl.n
        if overlay:
            arrays, valid, n = self._apply_overlay(dag, tbl, arrays, valid,
                                                   n, overlay)
        if n == 0:
            return []
        handles = tbl.handle_array()
        if len(handles) > n and not overlay:
            handles = handles[:n]       # concurrent append after snapshot
        elif n != len(handles):
            handles = np.concatenate([handles[:n - len(self._overlay_handles)]
                                      if len(handles) + len(self._overlay_handles) != n
                                      else handles,
                                      self._overlay_handles])
        if not self.use_device or dag.table_info.id <= -1000 or \
                not _dag_device_ready(dag):
            if dag.table_info.id > -1000:
                self._bump("copr_host_exec")
            return self._execute_host(dag, tbl, arrays, valid, n, handles)
        if not dag.filters and not dag.host_filters and not dag.aggs \
                and not dag.group_items and dag.topn is None:
            # pure scan: there is no compute to offload — the device
            # "filter" kernel would upload every column to produce an
            # identity mask and fetch it back (q2's full-partsupp scan
            # feeding a host hash join paid ~200ms for nothing). The
            # columnar arrays already live host-side; materialize there.
            self._bump("copr_host_exec")
            return self._execute_host(dag, tbl, arrays, valid, n, handles)
        frag_min = self.fragment_min_rows
        if ectx is not None:
            try:
                frag_min = int(ectx.sv.get("tidb_tpu_fragment_min_rows"))
            except Exception:               # noqa: BLE001
                pass
        if not dag.aggs and not dag.group_items and n < frag_min:
            # fragment selection: a filter/top-n-only fragment this
            # small computes in µs what its dispatch round trip costs
            # in ms, and its output (a row subset) is consumed by a
            # host operator anyway — whole-query single-dispatch keeps
            # the device program budget for the fragments that shrink
            # data (aggregations). docs/PERFORMANCE.md.
            _metrics.FRAGMENT_ROUTING.labels("host_small").inc()
            dom = getattr(self, "domain", None)
            if dom is not None:
                dom.inc_metric("copr_fragment_gated")
            self._bump("copr_host_exec")
            return self._execute_host(dag, tbl, arrays, valid, n, handles)
        _metrics.FRAGMENT_ROUTING.labels("device").inc()
        # why a dispatch that could see a mesh stays on one chip
        # (tidb_tpu_mesh_route_total; one device counts nothing)
        mesh = self._get_mesh()
        off_mesh = None if mesh is None else \
            "ineligible_no_aggregation" if not (
                dag.aggs or dag.group_items) else \
            "mpp_off" if not use_mpp else \
            "delta_overlay" if overlay else \
            "ineligible_host_filter" if dag.host_filters else \
            "min_rows" if n < mpp_min_rows else "ok"
        if off_mesh == "ok":
            try:
                # supervised mesh dispatch: retryable classes retry with
                # backoff, anything else degrades to None so the
                # single-chip path (which always works) takes over
                ndev = int(mesh.devices.size)
                with _tracing.span("mpp_dispatch",
                                   table=dag.table_info.name, rows=n,
                                   ndev=ndev, exchange="passthrough",
                                   kind="dense",
                                   lanes=shard_lanes(n, ndev)[1]):
                    res = device_guard.guarded_dispatch(
                        lambda: self._try_execute_mpp(dag, tbl, arrays,
                                                      valid, n, handles,
                                                      read_ts),
                        site="copr/mpp", ectx=ectx,
                        domain=getattr(self, "domain", None),
                        host_fallback=lambda: None,
                        fallback_is_host=False)
                    if res is None:
                        _tracing.tag(degraded=1)
            except TiDBError:
                raise                       # kill/quota: statement error
            except Exception:               # noqa: BLE001
                res = None                  # single-chip path always works
            if res is not None and res is not _NO_DENSE_LAYOUT:
                _metrics.MESH_ROUTE.labels("mesh", "ok").inc()
                self._bump("copr_mpp_exec")
                return res
            off_mesh = "degraded" if res is None else \
                "ineligible_no_dense_layout"
        if off_mesh is not None:
            _metrics.MESH_ROUTE.labels("single_chip", off_mesh).inc()
        self._bump("copr_device_exec")
        return self._execute_device(dag, tbl, arrays, valid, n, handles,
                                    ectx)

    def _bump(self, name):
        """Routing metrics (reference pkg/util/execdetails): which copr
        backend actually ran — the observable the golden routing tests
        pin so a silent device->host regression fails CI."""
        self.last_backend = {"copr_device_exec": "device",
                             "copr_mpp_exec": "device-mpp",
                             "copr_host_exec": "host"}.get(name, "")
        dom = getattr(self, "domain", None)
        if dom is not None:
            dom.inc_metric(name)
            # the copr span covers this (sub)dag's scan+kernel stage:
            # tag it with the backend that actually served it
            dom.tracer.tag(backend=self.last_backend)

    def _apply_overlay(self, dag, tbl, arrays, valid, n, overlay):
        valid = valid.copy()
        for h in overlay:
            pos = tbl.handle_pos.get(h)
            if pos is not None:
                valid[pos] = False
        put_rows = [(h, row) for h, row in overlay.items() if row is not None]
        if not put_rows:
            return arrays, valid, n
        m = len(put_rows)
        cols_info = tbl.table_info.columns
        off_by_id = {ci.id: i for i, ci in enumerate(cols_info)}
        new_arrays = {}
        new_handles = np.array([h for h, _ in put_rows], dtype=np.int64)
        for cid, (data, nulls, sdict) in arrays.items():
            off = off_by_id.get(cid)
            add = np.zeros(m, dtype=data.dtype)
            add_nulls = np.zeros(m, dtype=bool)
            for i, (_, row) in enumerate(put_rows):
                d = row[off] if off is not None and off < len(row) else None
                if d is None or d.is_null:
                    add_nulls[i] = True
                elif sdict is not None:
                    v = d.val
                    add[i] = sdict.encode_one(
                        v if isinstance(v, str) else str(v))
                elif data.dtype == np.float64:
                    add[i] = float(d.val)
                else:
                    add[i] = int(d.val)
            nd = np.concatenate([data, add])
            nn = None
            if nulls is not None or add_nulls.any():
                base_n = nulls if nulls is not None else \
                    np.zeros(len(data), dtype=bool)
                nn = np.concatenate([base_n, add_nulls])
            new_arrays[cid] = (nd, nn, sdict)
        valid = np.concatenate([valid, np.ones(m, dtype=bool)])
        self._overlay_handles = new_handles  # used by _bind_cols for _tidb_rowid
        return new_arrays, valid, n + m

    def _materialize_virtual(self, table_info):
        """INFORMATION_SCHEMA virtual table -> transient columnar table
        (reference pkg/executor/infoschema_reader.go memtable reads)."""
        from ..infoschema.virtual import virtual_rows
        from ..storage.columnar import ColumnarTable
        from ..chunk.column import py_to_datum_fast
        domain = getattr(self, "domain", None)
        tbl = ColumnarTable(table_info)
        if domain is None:
            return tbl
        rows = virtual_rows(domain, table_info)
        fts = [c.ft for c in table_info.columns]
        for h, row in enumerate(rows, start=1):
            datums = [None if v is None else py_to_datum_fast(v, ft)
                      for v, ft in zip(row, fts)]
            tbl.put_row(h, datums)
        return tbl

    def _cid(self, dag, sc):
        """Map a plan SchemaCol to the storage column id by name."""
        ci = dag.table_info.find_column(sc.name)
        if ci is None:
            # hidden handle column
            return -1
        return ci.id

    # ---- shared prep --------------------------------------------------
    def _bind_cols(self, dag, tbl, arrays, part_slice, handles,
                   cacheable=False, valid=None):
        """-> cols mapping plan-col-idx -> (np data, np nulls, dict).
        When cacheable, also records device-cache keys per column in
        self._bind_keys (cache valid only for pristine table arrays),
        and under _MASK the block's of `valid` where that is the
        table version's kept visibility mask (version_mask: not a mask
        made for one snapshot, nor one a statement laid its rows
        over)."""
        cols = {}
        self._bind_keys = {}
        if cacheable and valid is not None:
            mver = tbl.version_mask(valid)
            if mver is not None:
                self._bind_keys[_MASK] = (tbl.uid, tbl.gc_epoch,
                                          part_slice.start, mver)
        for sc in dag.cols:
            cid = self._cid(dag, sc)
            if cid == -1:
                cols[sc.col.idx] = (handles[part_slice], None, None)
                continue
            data, nulls, sdict = arrays[cid]
            cols[sc.col.idx] = (data[part_slice],
                                None if nulls is None else nulls[part_slice],
                                sdict)
            if cacheable:
                # append-seam bind record (consumed by _pad_upload):
                # version/gc_epoch ride OUT of the cache key so a
                # pure-append commit tail-patches the resident buffer
                # instead of re-uploading it (copr/delta.py)
                self._bind_keys[sc.col.idx] = (
                    tbl.uid, cid, tbl.gc_epoch, part_slice.start,
                    part_slice.stop, tbl.version)
        return cols

    # ---- host (numpy) fallback ---------------------------------------
    def _execute_host(self, dag, tbl, arrays, valid, n, handles):
        t0 = time.perf_counter()
        try:
            return self._execute_host_inner(dag, tbl, arrays, valid, n,
                                            handles)
        finally:
            phase.add("host_exec_s", time.perf_counter() - t0)
            phase.inc("host_execs")

    def _execute_host_inner(self, dag, tbl, arrays, valid, n, handles):
        out = []
        step = self.device_rows
        produced = 0
        shared_dicts = {}
        for start in range(0, n, step):
            sl = slice(start, min(start + step, n))
            cols = self._bind_cols(dag, tbl, arrays, sl, handles)
            v = valid[sl].copy()
            m = v.shape[0]
            ctx = EvalCtx(np, m, cols, host=True)
            for f in dag.filters + dag.host_filters:
                v &= np.asarray(eval_bool_mask(ctx, f))
            if dag.aggs or dag.group_items:
                out.append(host_partial_agg(ctx, dag, v,
                                            shared_dicts=shared_dicts))
                continue
            idx = np.nonzero(v)[0]
            if dag.limit >= 0:
                remain = dag.limit - produced
                if remain <= 0:
                    break
                idx = idx[:remain]
            produced += len(idx)
            chunk_cols = []
            for sc in dag.cols:
                data, nulls, sdict = cols[sc.col.idx]
                chunk_cols.append(Column(
                    sc.col.ft, data[idx],
                    None if nulls is None else nulls[idx], sdict))
            out.append(Chunk(chunk_cols))
            if 0 <= dag.limit <= produced:
                break
        return out

    # ---- device path --------------------------------------------------
    def _execute_device(self, dag, tbl, arrays, valid, n, handles,
                        ectx=None):
        """Supervised device execution: each partition kernel dispatch
        runs under device_guard (classified retry/backoff, watchdog).
        An exhausted dispatch degrades the whole (sub)dag to the host
        twin mid-query — correctness over placement (the TQP CPU-twin
        rationale)."""
        try:
            return self._execute_device_inner(dag, tbl, arrays, valid,
                                              n, handles, ectx)
        except device_guard.DeviceDegradedError:
            self._bump("copr_host_exec")
            return self._execute_host(dag, tbl, arrays, valid, n,
                                      handles)

    def _execute_device_inner(self, dag, tbl, arrays, valid, n, handles,
                              ectx=None):
        out = []
        step = self.device_rows
        produced = 0
        dom = getattr(self, "domain", None)
        parts = -(-n // step)
        for start in range(0, n, step):
            sl = slice(start, min(start + step, n))
            m = sl.stop - sl.start
            cap = shape_bucket(m)
            part = start // step
            with phase.bind_span():
                cols = self._bind_cols(dag, tbl, arrays, sl, handles,
                                       cacheable=(n == tbl.n), valid=valid)
            v = valid[sl]
            if dag.aggs or dag.group_items:
                res = device_guard.guarded_dispatch(
                    lambda: self._run_agg_partition(
                        dag, tbl, cols, v, m, cap, part=(part, parts)),
                    site="copr/agg", ectx=ectx, domain=dom)
                out.append(res)
                continue
            if dag.topn is not None:
                idx = device_guard.guarded_dispatch(
                    lambda: self._run_topn_partition(
                        dag, tbl, cols, v, m, cap, part=(part, parts)),
                    site="copr/topn", ectx=ectx, domain=dom,
                    host_fallback=lambda: self._topn_host(dag, cols, v,
                                                          m))
                with _tracing.span("consume", part=part, parts=parts):
                    out.append(self._gather_chunk(dag, cols, idx))
                continue
            mask = device_guard.guarded_dispatch(
                lambda: self._run_filter_partition(
                    dag, tbl, cols, v, m, cap, part=(part, parts)),
                site="copr/filter", ectx=ectx, domain=dom)
            with _tracing.span("consume", part=part, parts=parts):
                idx = np.nonzero(np.asarray(mask)[:m])[0]
                if dag.limit >= 0:
                    remain = dag.limit - produced
                    if remain <= 0:
                        break
                    idx = idx[:remain]
                produced += len(idx)
                out.append(self._gather_chunk(dag, cols, idx))
            if 0 <= dag.limit <= produced:
                break
        return out

    @staticmethod
    def _gather_chunk(dag, cols, idx):
        """The host rows `idx` of a partition's bound columns."""
        return Chunk([Column(sc.col.ft, data[idx],
                             None if nulls is None else nulls[idx], sdict)
                      for sc in dag.cols
                      for data, nulls, sdict in (cols[sc.col.idx],)])

    def _pad_upload(self, cols, v, m, cap, bind_keys=None):
        jcols = {}
        if bind_keys is None:
            # instance state is only valid for the MOST RECENT
            # _bind_cols call: pipelined/retried partitions must pass
            # their own captured keys or wrong cached buffers bind
            bind_keys = getattr(self, "_bind_keys", {})
        from .delta import append_key
        for k, (data, nulls, sdict) in cols.items():
            ck = bind_keys.get(k)
            if ck is not None:
                # _bind_cols record: (uid, cid, epoch, start, stop,
                # version). Keys are version-free ("tcol" layout): the
                # entry's rows/version advance in place under appends
                uid, cid, epoch, start, stop, ver = ck
                want = stop - start
                jd = self._dev_put_append(
                    append_key(uid, "frag", cid, "d", epoch, (start,),
                               cap),
                    data, want, cap, uid, ver, epoch, start,
                    self.device_rows)
                jn = None
                if nulls is not None:
                    jn = self._dev_put_append(
                        append_key(uid, "frag", cid, "n", epoch,
                                   (start,), cap),
                        nulls, want, cap, uid, ver, epoch, start,
                        self.device_rows, pad_fill=True)
            else:
                d = data
                if len(d) != cap:
                    d = np.concatenate([d, np.zeros(cap - m, dtype=d.dtype)])
                jd = jnp.asarray(d)
                phase.add("upload_bytes", d.nbytes)
                jn = None
                if nulls is not None:
                    nl = np.concatenate(
                        [nulls, np.ones(cap - m, dtype=bool)]) \
                        if len(nulls) != cap else nulls
                    jn = jnp.asarray(nl)
                    phase.add("upload_bytes", nl.nbytes)
            jcols[k] = (jd, jn, sdict)
        mk = bind_keys.get(_MASK)
        if mk is not None:
            return jcols, self._mask_operand(mk, v, cap)
        # the statement's own mask (a snapshot older than the table's
        # newest timestamp, a transaction's overlay, its delta
        # partition): scratch of this dispatch, and bytes the
        # statement uploads
        vv = np.concatenate([v, np.zeros(cap - m, dtype=bool)]) \
            if len(v) != cap else v
        phase.add("upload_bytes", vv.nbytes)
        return jcols, jnp.asarray(vv)

    def _mask_operand(self, mk, v, cap):
        """A row block's slice `v` of the table version's visibility
        mask as a program's operand: resident with the block's columns
        under the block's own key (uid, epoch, block start, version,
        cap; dropped by invalidate(uid, version) like every versioned
        entry), so a statement over an unchanged version pads and
        uploads nothing. The one-chip programs donate their mask
        operand (per-dispatch scratch they may overwrite), so where
        donation is on they get a copy made on the device, never the
        store's buffer."""
        uid, epoch, start, ver = mk
        dev = self._dev_put((uid, "fragv", epoch, start, ver, cap), v,
                            pad_fill=False, uid=uid, version=ver)
        if not jaxcfg.donation_enabled():
            return dev
        # tpulint: disable=unguarded-dispatch — inside the partition's
        # supervised dispatch (_run_*_partition under guarded_dispatch;
        # fused_partials under executors.FusedPipeline's)
        return tidb_mask_copy(dev)

    @staticmethod
    def _whole_mask_key(tbl, valid, read_ts):
        """(version, read_ts) to key a resident copy of the whole
        visibility mask `valid` by: the kept mask of a table version
        is every snapshot's at or past its newest timestamp (one entry
        a version, under the version it was stamped with); any other
        is its own snapshot's."""
        mver = tbl.version_mask(valid)
        return (tbl.version, read_ts) if mver is None else (mver, None)

    def _get_mesh(self):
        import jax
        if getattr(self, "_mesh", None) is None:
            from ..parallel import make_mesh
            if len(jax.devices()) < 2:
                self._mesh = False
            else:
                self._mesh = make_mesh()
        return self._mesh or None

    def _dev_put_sharded(self, key, arr_np, mesh, cap, pad_fill=0,
                         uid=None, version=None):
        """Mesh-sharded upload: the padded array partitions over the
        row axis (parallel.row_sharding) and STAYS partitioned across
        statements — each device holds 1/ndev, so the store charges
        the aggregate (per-shard x ndev), never x ndev."""
        hit = self._dev_store.get(key)
        if hit is not None:
            phase.inc("upload_hits")
            _metrics.DEV_BUFFER_POOL.labels("hit").inc()
            return hit
        _metrics.DEV_BUFFER_POOL.labels("miss").inc()
        dev, ndev = self._upload_padded(arr_np, cap, pad_fill=pad_fill,
                                        mesh=mesh, spec="sharded")
        self._dev_store.put(key, dev, dev.size * dev.dtype.itemsize,
                            uid=key[0] if uid is None else uid,
                            version=version, spec="sharded", ndev=ndev)
        return dev

    def _dev_put_replicated(self, key, arr_np, mesh, cap, pad_fill=0,
                            uid=None, version=None):
        """Broadcast-exchange upload: the array replicates to every
        mesh device (parallel.replicated_sharding); the store charges
        size * ndev (evictions refund what was charged). Counted as a
        Broadcast exchange on the actual upload, not on pool hits."""
        hit = self._dev_store.get(key)
        if hit is not None:
            phase.inc("upload_hits")
            _metrics.DEV_BUFFER_POOL.labels("hit").inc()
            return hit
        _metrics.DEV_BUFFER_POOL.labels("miss").inc()
        dev, ndev = self._upload_padded(arr_np, cap, pad_fill=pad_fill,
                                        mesh=mesh, spec="replicated")
        self._dev_store.put(key, dev, dev.size * dev.dtype.itemsize,
                            uid=key[0] if uid is None else uid,
                            version=version, spec="replicated",
                            ndev=ndev)
        return dev

    def _dev_put_append(self, key, arr_np, want, cap, uid, version,
                        epoch, start, span, pad_fill=0, mesh=None,
                        spec="local"):
        """Append-aware resident upload of an append-only table-column
        slice (docs/PERFORMANCE.md "Incremental HTAP"). ``arr_np``
        holds rows [start, start+want) of the column; the buffer pads
        to ``cap``. A live entry with enough rows is a pure hit; one
        that fell behind is TAIL-PATCHED on device (O(delta) upload)
        and advances its version in place; only a missing entry (or a
        failed/oversized patch) pays the full upload. ``spec``/mesh
        choose placement exactly like _dev_put/_dev_put_sharded/
        _dev_put_replicated."""
        store = self._dev_store
        ent = store.get_appendable(key)
        if ent is not None:
            dev, rows, ver = ent
            if rows >= want:
                phase.inc("upload_hits")
                _metrics.DEV_BUFFER_POOL.labels("hit").inc()
                if ver != version:
                    # delete/update-only version bump: data unchanged
                    store.advance_version(key, version)
                return dev
            patched = self.delta.patch_entry(
                key, dev, rows, want, cap, spec, arr_np[rows:want],
                pad_fill, version)
            if patched is not None:
                phase.inc("upload_hits")
                _metrics.DEV_BUFFER_POOL.labels("hit").inc()
                return patched
            store.drop(key, "delta_overflow")
            _metrics.DELTA_APPLY.labels("fell_back_full_upload").inc()
        _metrics.DEV_BUFFER_POOL.labels("miss").inc()
        if mesh is None:
            spec = "local"
        dev, ndev = self._upload_padded(arr_np, cap, pad_fill=pad_fill,
                                        mesh=mesh, spec=spec)
        store.put_appendable(key, dev, dev.size * dev.dtype.itemsize,
                             uid, version, rows=want, start=start,
                             span=span, cap=cap, spec=spec, ndev=ndev,
                             epoch=epoch)
        return dev

    def _try_execute_mpp(self, dag, tbl, arrays, valid, n, handles,
                         read_ts=None):
        """MPP fragment path: shard rows across the mesh, run the dense
        partial-agg kernel per shard inside shard_map, merge with psum
        (the hash exchange collapsed into an allreduce over the dense key
        domain — tidb_tpu/mpp design). Returns _NO_DENSE_LAYOUT when
        the aggregation has no dense layout to allreduce.

        Every input — column data AND the MVCC validity mask — rides the
        sharded residency store, so a repeated statement over an
        unchanged table uploads zero bytes to the mesh."""
        mesh = self._get_mesh()
        cols_full = self._bind_cols(dag, tbl, arrays, slice(0, n), handles)
        kd, sd = capture_agg_dicts(dag, cols_full)
        strides = dense_strides(dag, kd, cols_full, n)
        if strides is None or not _al.dense_fits(strides):
            # no dense layout, or none the policy lowers at this size:
            # the caller falls through to the single-chip path
            return _NO_DENSE_LAYOUT
        ndev = int(mesh.devices.size)
        padded, local = shard_lanes(n, ndev)
        cols = cols_full
        names = sorted(cols.keys())
        # cache by STORAGE column id, never plan column idx: idxs are
        # per-plan and collide across statements (a scalar subquery
        # priming the cache poisoned the outer query's columns)
        cid_of_idx = {sc.col.idx: self._cid(dag, sc) for sc in dag.cols}
        from .delta import append_key
        with phase.bind_span():
            args = []
            has_nulls = {}
            epoch = tbl.gc_epoch
            for k in names:
                data, nulls, sdict = cols[k]
                cid = cid_of_idx.get(k, -1)
                kind = "h" if cid == -1 else "d"
                args.append(self._dev_put_append(
                    append_key(tbl.uid, "mppcol", cid, kind, epoch,
                               (ndev,), padded),
                    data, n, padded, tbl.uid, tbl.version, epoch, 0, None,
                    mesh=mesh, spec="sharded"))
                has_nulls[k] = nulls is not None
                if nulls is not None:
                    args.append(self._dev_put_append(
                        append_key(tbl.uid, "mppcol", cid, "n", epoch,
                                   (ndev,), padded),
                        nulls, n, padded, tbl.uid, tbl.version, epoch, 0,
                        None, pad_fill=True, mesh=mesh, spec="sharded"))
            # the MVCC validity mask is version+snapshot-keyed (same policy
            # as _upload_dim's ts_keyed entries): within one (version,
            # read_ts) it is immutable, so it stays resident too — the old
            # raw device_put here was an uncounted warm re-upload per
            # statement
            mver, mts = self._whole_mask_key(tbl, valid, read_ts)
            args.append(self._dev_put_sharded(
                (tbl.uid, "mppvalid", mver, mts, ndev, padded),
                valid[:n], mesh, padded, pad_fill=False, uid=tbl.uid,
                version=mver))
        key = self._cache_key(dag, tbl, "mpp", padded,
                              (tuple(strides), ndev,
                               tuple(sorted(has_nulls.items()))))
        kern = self._kernel_cache.get(key)
        if kern is None:
            kern = _build_dense_agg_kernel_mpp(
                dag, cols, local, strides, mesh, names, has_nulls)
            kern = self._kernel_cache.put(key, kern)
        res = kern(*args)
        from ..mpp.exec import exchange_observed, tree_nbytes
        exchange_observed("passthrough", tree_nbytes(res))
        with _tracing.span("consume"):
            return [compact_dense(dag, res, strides, kd, sd)]

    def _cache_key(self, dag, tbl, kind, cap, extra=()):
        dict_vers = tuple(sorted(
            (cid, len(d.values)) for cid, d in tbl.dicts.items()))
        fps = tuple(f.fingerprint() for f in dag.filters)
        gfps = tuple(g.fingerprint() for g in dag.group_items)
        afps = tuple(a.fingerprint() for a in dag.aggs)
        colsig = tuple(sorted((sc.col.idx, sc.name) for sc in dag.cols))
        return (kind, tbl.uid, cap, fps, gfps, afps, dict_vers, colsig,
                _al.policy(), extra)

    @_as_row_block
    def _run_filter_partition(self, dag, tbl, cols, v, m, cap):
        key = self._cache_key(dag, tbl, "filter", cap)
        kern = self._kernel_cache.get(key)
        sdicts = {k: c[2] for k, c in cols.items()}
        filters = list(dag.filters)
        if kern is None:
            def tidb_filter(jc, vv):
                full = {k: (d, nl, sdicts[k]) for k, (d, nl) in jc.items()}
                ctx = EvalCtx(jnp, cap, full, host=False)
                mask = vv
                for f in filters:
                    mask = mask & eval_bool_mask(ctx, f)
                return mask
            # the validity mask is per-dispatch scratch (rebuilt by
            # _pad_upload every call, never pooled): donate its HBM
            dn = jaxcfg.donation_argnums(1)
            kern = jaxcfg.guard_donation(
                jax.jit(tidb_filter, donate_argnums=dn), dn)
            kern = self._kernel_cache.put(key, kern)
        with phase.bind_span():
            jcols, vv = self._pad_upload(cols, v, m, cap)
        jc = {k: (d, nl) for k, (d, nl, _) in jcols.items()}
        res = prefetch(kern(jc, vv))
        with _tracing.span("consume", **phase.part_attrs()):
            mask = host_array(res)
            # host-only filters applied on host afterwards
            if dag.host_filters:
                ctx = EvalCtx(np, m, cols, host=True)
                hm = mask[:m].copy()
                for f in dag.host_filters:
                    hm &= np.asarray(eval_bool_mask(ctx, f))
                return hm
            return mask

    @_as_row_block
    def _run_topn_partition(self, dag, tbl, cols, v, m, cap):
        """Fused filter + device top-k over the single sort key; returns
        host indices of the top rows (<= k) in key order."""
        (expr, desc), k = dag.topn
        if jax.default_backend() == "cpu":
            # lax.top_k lowers poorly on CPU; numpy argpartition instead
            return self._topn_host(dag, cols, v, m)
        key = self._cache_key(dag, tbl, "topn", cap,
                              (expr.fingerprint(), desc, k))
        kern = self._kernel_cache.get(key)
        sdicts = {kk: c[2] for kk, c in cols.items()}
        if kern is None:
            filters = list(dag.filters)

            def tidb_topn(jc, vv):
                full = {kk: (d, nl, sdicts[kk]) for kk, (d, nl) in jc.items()}
                ctx = EvalCtx(jnp, cap, full, host=False)
                mask = vv
                for f in filters:
                    mask = mask & eval_bool_mask(ctx, f)
                d, nl, sd = eval_expr(ctx, expr)
                if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
                    d = jnp.full(cap, d)
                nm = materialize_nulls(ctx, nl)
                if sd is not None:
                    ranks = jnp.asarray(sd.ranks())
                    d = ranks[d]
                if d.dtype.kind == "f":
                    kv = d if desc else -d
                    nullv = jnp.asarray(-np.inf if desc else np.inf)
                    minus_inf = jnp.asarray(-np.inf)
                else:
                    kv = d.astype(jnp.int64)
                    kv = kv if desc else -kv
                    nullv = jnp.asarray(-_I64_MAX if desc else _I64_MAX)
                    minus_inf = jnp.asarray(-_I64_MAX - 1)
                kv = jnp.where(nm, nullv, kv)
                kv = jnp.where(mask, kv, minus_inf)
                _, top_idx = jax.lax.top_k(kv, min(k, cap))
                cnt = jnp.minimum(jnp.sum(mask.astype(jnp.int64)), k)
                return top_idx, cnt
            dn = jaxcfg.donation_argnums(1)
            kern = jaxcfg.guard_donation(
                jax.jit(tidb_topn, donate_argnums=dn), dn)
            kern = self._kernel_cache.put(key, kern)
        with phase.bind_span():
            jcols, vv = self._pad_upload(cols, v, m, cap)
            jc = {kk: (d, nl) for kk, (d, nl, _) in jcols.items()}
            vv = self._and_host_filters(dag, cols, vv, m, cap)
        top_idx, cnt = prefetch(kern(jc, vv))
        with _tracing.span("consume", **phase.part_attrs()):
            return host_array(top_idx)[:host_int(cnt)]

    @staticmethod
    def _and_host_filters(dag, cols, vv, m, cap):
        """The uploaded validity mask AND the host-only filters."""
        if not dag.host_filters:
            return vv
        ctx = EvalCtx(np, m, cols, host=True)
        hm = np.ones(m, dtype=bool)
        for f in dag.host_filters:
            hm &= np.asarray(eval_bool_mask(ctx, f))
        hmp = np.concatenate([hm, np.zeros(cap - m, dtype=bool)]) \
            if m != cap else hm
        return vv & jnp.asarray(hmp)

    def _topn_host(self, dag, cols, v, m):
        (expr, desc), k = dag.topn
        ctx = EvalCtx(np, m, cols, host=True)
        mask = v[:m].copy()
        for f in dag.filters + dag.host_filters:
            mask &= np.asarray(eval_bool_mask(ctx, f))
        d, nl, sd = eval_expr(ctx, expr)
        if np.isscalar(d):
            d = np.full(m, d)
        d = np.asarray(d)
        nm = np.asarray(materialize_nulls(ctx, nl))
        if sd is not None:
            d = sd.ranks()[d]
        if d.dtype.kind == "f":
            kv = d if desc else -d
            nullv = -np.inf if desc else np.inf
            sentinel = -np.inf
        else:
            kv = d.astype(np.int64)
            kv = kv if desc else -kv
            # NULLs: last on desc (near-min), first on asc (max);
            # filtered rows: strictly below every real key. Values chosen
            # so that negation in argpartition(-kv) cannot overflow.
            nullv = (-_I64_MAX + 1) if desc else _I64_MAX
            sentinel = -_I64_MAX
        kv = np.where(nm, nullv, kv)
        kv = np.where(mask, kv, sentinel)
        cnt = min(int(mask.sum()), k)
        if cnt == 0:
            return np.empty(0, dtype=np.int64)
        if k < m:
            part = np.argpartition(-kv, k)[:k]
        else:
            part = np.arange(m)
        order = part[np.argsort(-kv[part], kind="stable")]
        return order[:cnt]

    @_as_row_block
    def _run_agg_partition(self, dag, tbl, cols, v, m, cap):
        """Device partial aggregation; returns PartialAggResult."""
        kd, sd = capture_agg_dicts(dag, cols)
        # dense fast path: group keys span a small combined domain
        # (dict codes, or int keys after a runtime min/max pass) ->
        # direct scatter-add, no sort (Q1 / year()-grouping shapes)
        low = _al.Lowering(
            _al.ShapeState(self, tbl, dag.group_items, dag.aggs),
            sizes=dense_strides(dag, kd, cols, m), site="dag",
            unclustered=lambda: _al.host_unclustered(dag, cols, m))
        retries = 0     # re-dispatches the learned lowering forced
        while True:
            kind, param, _ecap = low.choose(cap)
            if kind == "dense":
                key = self._cache_key(dag, tbl, "dagg", cap, param)
                kern = self._kernel_cache.get(key)
                if kern is None:
                    kern = _build_dense_agg_kernel(dag, cols, cap,
                                                   low.sizes)
                    kern = self._kernel_cache.put(key, kern)
            else:
                key = self._cache_key(dag, tbl, "agg", cap, param[:2])
                kern = self._kernel_cache.get(key)
                if kern is None:
                    kern = _build_agg_kernel(dag, cols, cap, *param[:2])
                    kern = self._kernel_cache.put(key, kern)
            with phase.bind_span():
                jcols, vv = self._pad_upload(cols, v, m, cap)
                jc = {k: (d, nl) for k, (d, nl, _) in jcols.items()}
                vv = self._and_host_filters(dag, cols, vv, m, cap)
            res = prefetch(kern(jc, vv))
            with _tracing.span("consume", retries=retries,
                               **phase.part_attrs()):
                ngroups = None if kind == "dense" else \
                    host_int(res["ngroups"])
                if low.observe(kind, param, None, cap, m,
                               ngroups=ngroups) == "retry":
                    retries += 1
                    continue
                if kind == "dense":
                    return compact_dense(dag, res, low.sizes, kd, sd)
                return PartialAggResult(
                    ngroups=ngroups,
                    keys=[host_array(k)[:ngroups] for k in res["keys"]],
                    key_nulls=[host_array(kn)[:ngroups]
                               for kn in res["key_nulls"]],
                    states=[[host_array(s)[:ngroups] for s in st]
                            for st in res["states"]],
                    key_dicts=kd, state_dicts=sd,
                )


def _dag_device_ready(dag) -> bool:
    from ..expression.vec import is_device_safe
    for sc in dag.cols:
        if not is_device_safe(sc.col):
            return False           # e.g. big-decimal object columns
    for f in dag.filters:
        if not is_device_safe(f):
            return False
    for g in dag.group_items:
        if not is_device_safe(g):
            return False
    for a in dag.aggs:
        if not all(is_device_safe(arg) for arg in a.args):
            return False
    return True


def _build_dense_agg_kernel(dag, sample_cols, cap, sizes):
    """Partial agg via direct scatter-add into the dense key-product table."""
    sdicts = {k: c[2] for k, c in sample_cols.items()}
    group_items = list(dag.group_items)
    aggs = list(dag.aggs)

    def tidb_agg_dense(jc, vv):
        full = {k: (d, nl, sdicts[k]) for k, (d, nl) in jc.items()}
        ctx = EvalCtx(jnp, cap, full, host=False)
        mask = vv
        for f in dag.filters:
            mask = mask & eval_bool_mask(ctx, f)
        return dense_agg_body(ctx, mask, group_items, aggs, sizes, cap)
    dn = jaxcfg.donation_argnums(1)
    return jaxcfg.guard_donation(
        jax.jit(tidb_agg_dense, donate_argnums=dn), dn)


def _build_dense_agg_kernel_mpp(dag, sample_cols, local_cap, sizes, mesh,
                                names, has_nulls):
    """The dense partial-agg kernel wrapped in shard_map: each device
    aggregates its row shard into the dense table; one psum merges —
    the MPP hash exchange as an allreduce (tidb_tpu/mpp/exec.py design)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    sdicts = {k: c[2] for k, c in sample_cols.items()}
    group_items = list(dag.group_items)
    aggs = list(dag.aggs)

    def tidb_mpp_agg_dense(*flat):
        cols = {}
        i = 0
        for k in names:
            d = flat[i]
            i += 1
            nl = None
            if has_nulls[k]:
                nl = flat[i]
                i += 1
            cols[k] = (d, nl, sdicts[k])
        vv = flat[-1]
        cap = vv.shape[0]
        ctx = EvalCtx(jnp, cap, cols, host=False)
        mask = vv
        for f in dag.filters:
            mask = mask & eval_bool_mask(ctx, f)
        local = dense_agg_body(ctx, mask, group_items, aggs, sizes, cap)
        return psum_dense_result(local, aggs, "dp")

    nargs = sum(1 + (1 if has_nulls[k] else 0) for k in names) + 1
    fn = shard_map(tidb_mpp_agg_dense, mesh=mesh,
                   in_specs=tuple(P("dp") for _ in range(nargs)),
                   out_specs={"present": P(),
                              "states": [[P() for _ in range(
                                  2 if a.name != "count" else 1)]
                                  for a in aggs]},
                   check_vma=False)
    return jax.jit(fn)


def _build_agg_kernel(dag, sample_cols, cap, group_bucket, impl=None):
    """Compile the partial-agg kernel for this dag/bucket."""
    sdicts = {k: c[2] for k, c in sample_cols.items()}
    group_items = list(dag.group_items)
    aggs = list(dag.aggs)

    def tidb_agg_sort(jc, vv):
        full = {k: (d, nl, sdicts[k]) for k, (d, nl) in jc.items()}
        ctx = EvalCtx(jnp, cap, full, host=False)
        mask = vv
        for f in dag.filters:
            mask = mask & eval_bool_mask(ctx, f)
        return sort_agg_body(ctx, mask, group_items, aggs, cap,
                             group_bucket, impl=impl)
    dn = jaxcfg.donation_argnums(1)
    return jaxcfg.guard_donation(
        jax.jit(tidb_agg_sort, donate_argnums=dn), dn)
