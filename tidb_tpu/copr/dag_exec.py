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
from ..utils import phase
from ..utils import device_guard
from ..utils import metrics as _metrics
from ..utils import tracing as _tracing
from ..errors import TiDBError
from ..chunk.device import shape_bucket
from ..chunk.column import Column
from ..chunk.chunk import Chunk

_I64_MAX = np.iinfo(np.int64).max


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
            # partitions win until HBM pressure. 4M rows has not been
            # measured on this chip (ROADMAP D2, S4)
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
        if use_mpp and (dag.aggs or dag.group_items) and not overlay \
                and not dag.host_filters \
                and n >= mpp_min_rows:
            try:
                # supervised mesh dispatch: retryable classes retry with
                # backoff, anything else degrades to None so the
                # single-chip path (which always works) takes over
                with _tracing.span("mpp_dispatch",
                                   table=dag.table_info.name, rows=n):
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
            if res is not None:
                self._bump("copr_mpp_exec")
                return res
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
                   cacheable=False):
        """-> cols mapping plan-col-idx -> (np data, np nulls, dict).
        When cacheable, also records device-cache keys per column in
        self._bind_keys (cache valid only for pristine table arrays)."""
        cols = {}
        self._bind_keys = {}
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
                out.append(_host_partial_agg(ctx, dag, v,
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
                                       cacheable=(n == tbl.n))
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
                jn = None
                if nulls is not None:
                    nl = np.concatenate(
                        [nulls, np.ones(cap - m, dtype=bool)]) \
                        if len(nulls) != cap else nulls
                    jn = jnp.asarray(nl)
            jcols[k] = (jd, jn, sdict)
        vv = np.concatenate([v, np.zeros(cap - m, dtype=bool)]) \
            if len(v) != cap else v
        return jcols, jnp.asarray(vv)

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
        domain — tidb_tpu/mpp design). Returns None when ineligible.

        Every input — column data AND the MVCC validity mask — rides the
        sharded residency store, so a repeated statement over an
        unchanged table uploads zero bytes to the mesh."""
        mesh = self._get_mesh()
        if mesh is None:
            return None
        cols_full = self._bind_cols(dag, tbl, arrays, slice(0, n), handles)
        kd, sd = capture_agg_dicts(dag, cols_full)
        strides = _dense_strides(dag, kd, cols_full, n)
        if strides is None:
            return None
        if _segment_impl() == "runs" and \
                _dense_nslots(strides) > _BCR_MAX:
            # no scatter-free dense lowering at this size: let the
            # caller fall through to the single-chip runs path rather
            # than hit the argsort fallback inside dense_agg_states
            return None
        ndev = int(mesh.devices.size)
        lane = 128 * ndev
        # BUCKETED lane-multiple padding (was an exact lane multiple):
        # residency + delta maintenance need the padded capacity — and
        # with it the compiled kernel shape and the buffer keys — to
        # survive appends within a bucket, so a steady write stream
        # tail-patches the sharded buffers instead of re-keying them
        # every `lane` rows
        padded = ((shape_bucket(n) + lane - 1) // lane) * lane
        local = padded // ndev
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
            args.append(self._dev_put_sharded(
                (tbl.uid, "mppvalid", tbl.version, read_ts, ndev, padded),
                valid[:n], mesh, padded, pad_fill=False, uid=tbl.uid,
                version=tbl.version))
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
            return [_compact_dense(dag, res, strides, kd, sd)]

    def _cache_key(self, dag, tbl, kind, cap, extra=()):
        dict_vers = tuple(sorted(
            (cid, len(d.values)) for cid, d in tbl.dicts.items()))
        fps = tuple(f.fingerprint() for f in dag.filters)
        gfps = tuple(g.fingerprint() for g in dag.group_items)
        afps = tuple(a.fingerprint() for a in dag.aggs)
        colsig = tuple(sorted((sc.col.idx, sc.name) for sc in dag.cols))
        return (kind, tbl.uid, cap, fps, gfps, afps, dict_vers, colsig,
                _segment_impl(), extra)

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
    def _run_agg_partition(self, dag, tbl, cols, v, m, cap,
                           group_bucket=1024):
        """Device partial aggregation; returns PartialAggResult."""
        gbkey = ("gb", tbl.uid,
                 tuple(g.fingerprint() for g in dag.group_items),
                 tuple(a.fingerprint() for a in dag.aggs))
        group_bucket = max(group_bucket, self._host_cache.get(gbkey, 0))
        impl_key = ("aggimpl",) + gbkey
        retries = 0     # re-dispatches the learned lowering forced
        while True:
            impl = self._host_cache.get(impl_key) or _segment_impl()
            kd, sd = capture_agg_dicts(dag, cols)
            # dense fast path: group keys span a small combined domain
            # (dict codes, or int keys after a runtime min/max pass) ->
            # direct scatter-add, no sort (Q1 / year()-grouping shapes)
            strides = _dense_strides(dag, kd, cols, m)
            if strides is not None and impl == "runs" and \
                    _dense_nslots(strides) > _BCR_MAX:
                # dense-but-big domains have no scatter-free dense
                # lowering on TPU: take the general path, which runs
                # runs_agg_body (contiguous-run partials)
                strides = None
            if strides is not None:
                key = self._cache_key(dag, tbl, "dagg", cap, tuple(strides))
                kern = self._kernel_cache.get(key)
                if kern is None:
                    kern = _build_dense_agg_kernel(dag, cols, cap, strides)
                    kern = self._kernel_cache.put(key, kern)
            else:
                key = self._cache_key(dag, tbl, "agg", cap,
                                      (group_bucket, impl))
                kern = self._kernel_cache.get(key)
                if kern is None:
                    kern = _build_agg_kernel(dag, cols, cap, group_bucket,
                                             impl)
                    kern = self._kernel_cache.put(key, kern)
            with phase.bind_span():
                jcols, vv = self._pad_upload(cols, v, m, cap)
                jc = {k: (d, nl) for k, (d, nl, _) in jcols.items()}
                vv = self._and_host_filters(dag, cols, vv, m, cap)
            res = prefetch(kern(jc, vv))
            with _tracing.span("consume", retries=retries,
                               **phase.part_attrs()):
                if strides is not None:
                    return _compact_dense(dag, res, strides, kd, sd)
                ngroups = host_int(res["ngroups"])
                if impl == "runs" and _runs_degraded(ngroups, m):
                    # keys uncorrelated with storage order: runs
                    # exploded into ~per-row partials. Pin this (table,
                    # group, agg) shape to the sorted lowering (one
                    # partial per group) before the regrow loop learns
                    # the inflated bucket.
                    self._host_cache[impl_key] = "sorted"
                    retries += 1
                    continue
                if ngroups > group_bucket:
                    group_bucket = shape_bucket(ngroups)
                    self._host_cache[gbkey] = group_bucket
                    retries += 1
                    continue
                return PartialAggResult(
                    ngroups=ngroups,
                    keys=[host_array(k)[:ngroups] for k in res["keys"]],
                    key_nulls=[host_array(kn)[:ngroups]
                               for kn in res["key_nulls"]],
                    states=[[host_array(s)[:ngroups] for s in st]
                            for st in res["states"]],
                    key_dicts=kd, state_dicts=sd,
                )


class PartialAggResult:
    """Per-partition aggregation partials: group keys (encoded: dict codes /
    int64) + per-agg state arrays (sum/count/min/max). key_dicts/state_dicts
    carry StringDicts for string-typed keys/args (codes are comparable
    across partitions because dict transforms are deterministic over the
    shared table dictionary)."""

    __slots__ = ("ngroups", "keys", "key_nulls", "states", "key_dicts",
                 "state_dicts")

    def __init__(self, ngroups, keys, key_nulls, states, key_dicts=None,
                 state_dicts=None):
        self.ngroups = ngroups
        self.keys = keys
        self.key_nulls = key_nulls
        self.states = states
        self.key_dicts = key_dicts or [None] * len(keys)
        self.state_dicts = state_dicts or [None] * len(states)


def capture_agg_dicts(dag, cols):
    """Evaluate group items / agg args over a 1-row host ctx to learn which
    produce dict-coded outputs (and with which dictionary)."""
    one = {}
    for k, (data, nulls, sdict) in cols.items():
        d1 = data[:1] if len(data) else np.zeros(1, dtype=data.dtype)
        n1 = None if nulls is None else nulls[:1]
        one[k] = (d1, n1, sdict)
    ctx = EvalCtx(np, 1, one, host=True)
    key_dicts = []
    for g in dag.group_items:
        try:
            _, _, sd = eval_expr(ctx, g)
        except Exception:
            sd = None
        key_dicts.append(sd)
    state_dicts = []
    for a in dag.aggs:
        sd = None
        if a.args:
            try:
                _, _, sd = eval_expr(ctx, a.args[0])
            except Exception:
                sd = None
        state_dicts.append(sd)
    return key_dicts, state_dicts


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


_DENSE_MAX = 1 << 18


def _dense_strides(dag, key_dicts, cols=None, n=0):
    """-> per-key (size, offset) when the combined group domain is small:
    dictionary codes (offset 0, size = |dict|+1) or integer keys whose
    runtime min/max span fits (offset = min). slot 0 per key = NULL. A
    global aggregation is the degenerate dense case (empty layout)."""
    if not dag.group_items:
        return []
    if len(key_dicts) != len(dag.group_items):
        return None
    layout = []
    total = 1
    pending = []            # indexes needing a min/max host pass
    for i, d in enumerate(key_dicts):
        if d is None:
            pending.append(i)
            layout.append(None)
            continue
        size = len(d.values) + 1
        layout.append((size, 0))
        total *= size
        if total > _DENSE_MAX:
            return None
    if pending:
        if cols is None or n == 0:
            return None
        ctx = EvalCtx(np, n, cols, host=True)
        for i in pending:
            g = dag.group_items[i]
            try:
                data, nulls, sd = eval_expr(ctx, g)
            except Exception:
                return None
            if sd is not None or np.isscalar(data):
                return None
            data = np.asarray(data)
            if data.dtype.kind not in "iu" or len(data) == 0:
                return None
            nm = np.asarray(materialize_nulls(ctx, nulls))
            live = data[~nm] if nm.any() else data
            if len(live) == 0:
                lo, hi = 0, 0
            else:
                lo, hi = int(live.min()), int(live.max())
            size = hi - lo + 2
            if size <= 0:
                return None
            layout[i] = (size, lo)
            total *= size
            if total > _DENSE_MAX:
                return None
    return layout


def dense_agg_body(ctx, mask, group_items, aggs, sizes, cap):
    """Dense scatter-add partial agg over an eval ctx + row mask: direct
    segment ops into the dense key-product table. Shared by the copr
    reader kernel and the fused scan-join-agg pipeline kernel."""
    nslots = 1
    for s, _off in sizes:
        nslots *= s
    slot = jnp.zeros(cap, dtype=jnp.int64)
    for g, (size, off) in zip(group_items, sizes):
        d, nl, _ = eval_expr(ctx, g)
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        nm = materialize_nulls(ctx, nl)
        code = jnp.clip(jnp.where(nm, 0, d.astype(jnp.int64) - off + 1),
                        0, size - 1)
        slot = slot * size + code
    slot = jnp.where(mask, slot, nslots)      # invalid rows -> spill slot
    return dense_agg_states(ctx, mask, aggs, slot, nslots, cap)


def dense_agg_states(ctx, mask, aggs, slot, nslots, cap):
    """Partial-agg states into a precomputed dense slot table (slot ==
    nslots means masked-out). Used with key-product slots and with
    join-POSITION slots (group-by-FK in the fused pipeline).

    Lowerings:
    - scatter (segment ops): good on CPU, but on TPU the int64 values
      emulate as u32 pairs and the variadic scatter-add serializes
      (~16KB of vreg traffic PER ROW measured: a 655k-row Q6 kernel
      read 10.8GB and ran 145ms).
    - sorted: ONE shared argsort of the slot array + segmented scans;
      no scatter, but argsort itself is ~855ms/1M on the v5e.
    - reduce/bcr (via the "runs" policy): plain masked reductions for
      the global case, [nslots, cap] broadcast-compare reductions for
      tiny domains — no sort AND no scatter; larger domains are routed
      to runs_agg_body by the callers before reaching here."""
    impl = _segment_impl()
    if nslots == 1:
        # global aggregation: a scatter into one slot is never better
        # than a plain masked reduce, on ANY backend (on the CPU proxy
        # segment_sum lowers to a serial scatter — q6 lost 40% to it)
        return _dense_agg_states_reduce(ctx, mask, aggs, cap)
    if impl == "runs":
        if nslots <= _BCR_MAX:
            return _dense_agg_states_bcr(ctx, mask, aggs, slot, nslots,
                                         cap)
        impl = "sorted"      # callers route big domains to runs_agg_body
    if impl == "sorted":
        return _dense_agg_states_sorted(ctx, mask, aggs, slot, nslots, cap)
    states = []
    for a in aggs:
        if a.args:
            d, nl, _ = eval_expr(ctx, a.args[0])
            if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
                d = jnp.full(cap, d)
            nm = materialize_nulls(ctx, nl)
            row_ok = mask & ~nm
        else:
            d = jnp.ones(cap, dtype=jnp.int64)
            row_ok = mask
        cnt = jax.ops.segment_sum(row_ok.astype(jnp.int64), slot,
                                  num_segments=nslots + 1)[:nslots]
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            s = jax.ops.segment_sum(jnp.where(row_ok, d, 0), slot,
                                    num_segments=nslots + 1)[:nslots]
            states.append([s, cnt])
        elif a.name == "min":
            big = (jnp.asarray(np.inf) if d.dtype.kind == "f"
                   else jnp.asarray(_I64_MAX)).astype(d.dtype)
            s = jax.ops.segment_min(jnp.where(row_ok, d, big), slot,
                                    num_segments=nslots + 1)[:nslots]
            states.append([s, cnt])
        elif a.name == "max":
            small = (jnp.asarray(-np.inf) if d.dtype.kind == "f"
                     else jnp.asarray(-_I64_MAX)).astype(d.dtype)
            s = jax.ops.segment_max(jnp.where(row_ok, d, small), slot,
                                    num_segments=nslots + 1)[:nslots]
            states.append([s, cnt])
        elif a.name == "first_row":
            fi = jax.ops.segment_min(
                jnp.where(row_ok, jnp.arange(cap), cap - 1), slot,
                num_segments=nslots + 1)[:nslots]
            states.append([d[jnp.minimum(fi, cap - 1)], cnt])
        else:
            raise NotImplementedError(a.name)
    present = jax.ops.segment_sum(mask.astype(jnp.int64), slot,
                                  num_segments=nslots + 1)[:nslots]
    return {"present": present, "states": states}


_FORCE_SEGMENT_IMPL = None  # tests: "scatter"|"sorted"|"runs"|None (auto)

# broadcast-compare-reduce ceiling: a [nslots, cap] fused compare+reduce
# reads each value column nslots times, so it only wins for tiny group
# domains (Q1's flag x status = 12, Q5's 25 nations)
_BCR_MAX = int(os.environ.get("TIDB_TPU_BCR_MAX", "64"))

# if the runs lowering yields more partials than this (and more than
# half the partition's rows), the group key is uncorrelated with
# storage order — pin the query shape to the sorted lowering instead
_RUNS_DEGRADE_MIN = int(os.environ.get("TIDB_TPU_RUNS_DEGRADE", "65536"))


def _runs_degraded(ngroups, m) -> bool:
    """Did the runs lowering explode into ~per-row partials over `m`
    rows? Keys uncorrelated with storage order give about one run a row
    (m(1 - 1/D) runs for D distinct values); a key the storage clusters
    gives m / L for runs of L rows, which the sorted lowering could not
    shrink either. The line is at runs of two and not higher up: at
    four it is TPC-H's mean lines an order (4.0008), and lineitem GROUP
    BY l_orderkey (q18's subquery: 1,048,366 +- 500 runs a
    4,194,304-row block) falls on either side of it block by block —
    where the wrong side is a sort program that costs the TPU compiler
    29 GB of host memory and 390 s at that width (PERF.md, PR 27)."""
    return ngroups > max(_RUNS_DEGRADE_MIN, m // 2)


def _segment_impl():
    """How segment aggregations lower: "scatter" | "sorted" | "runs".

    The relative costs have not been measured on this chip
    (benchmarks/microbench_tpu.py is the instrument; ROADMAP D2, D5):
    - scatter (jax.ops.segment_*): XLA variadic scatter serializes row
      by row on TPU and is slow to compile there — never use it in a
      TPU kernel.
    - sorted (argsort + segmented scans): pays a 64-bit device argsort
      per call and a sort compile per shape.
    - runs (cumsum + boundary gathers): no sort, no
      scatter; contiguous equal-key runs become partial groups that the
      existing partial-agg merge combines, which is exact for any input
      and compact whenever the data is clustered by the group key
      (TPC-H lineitem by l_orderkey, dict codes from sorted loads, ...).
    CPU keeps scatter: it is fast there and serves as the oracle the
    device lowerings are tested against."""
    impl = _FORCE_SEGMENT_IMPL or \
        os.environ.get("TIDB_TPU_SEGMENT_IMPL")
    if impl and impl != "auto":
        if impl not in ("scatter", "sorted", "runs"):
            raise ValueError(
                f"TIDB_TPU_SEGMENT_IMPL={impl!r}: expected one of "
                "scatter|sorted|runs|auto")
        return impl
    return "runs" if jax.default_backend() != "cpu" else "scatter"


def _dense_nslots(sizes):
    n = 1
    for s, _off in sizes:
        n *= s
    return n


def _minmax_sentinel(name, dtype):
    """-> (sentinel, combine) for a min/max agg over arrays of dtype:
    the identity the masked-out rows take and the elementwise combiner.
    Shared by every lowering so they cannot diverge from the oracle."""
    is_f = dtype.kind == "f"
    if name == "min":
        return (jnp.asarray(np.inf if is_f else _I64_MAX).astype(dtype),
                jnp.minimum)
    return (jnp.asarray(-np.inf if is_f else -_I64_MAX).astype(dtype),
            jnp.maximum)


def _agg_eval_rows(ctx, a, mask, cap):
    """-> (d, row_ok) for one agg over the eval ctx (count(*) -> ones)."""
    if a.args:
        d, nl, _ = eval_expr(ctx, a.args[0])
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        nm = materialize_nulls(ctx, nl)
        return d, mask & ~nm
    return jnp.ones(cap, dtype=jnp.int64), mask


# one-hot MXU segment aggregation (small learned group domains): the
# slot table must fit this many groups, and per-limb int32 accumulation
# stays exact while cap * 127 < 2^31 (cap <= 2^23 guard at dispatch).
# MXU cost is cap*scap*limbs int8 MACs — ~3.4 T-MAC at 4M x 32k x 13,
# ~10ms on a v5e; the block size shrinks with scap to bound the
# materialized one-hot tile at 32MB
_ONEHOT_MAX = int(os.environ.get("TIDB_TPU_ONEHOT_MAX", "32768"))
_ONEHOT_LIMBS = 10        # 9 x 7-bit limbs (bits 0..62) + the sign bit


def onehot_agg_limb_layout(aggs):
    """-> (col_specs, L): per-agg limb-column layout of the one-hot
    matmul accumulator. col_specs: list of (agg_index, state_index,
    nlimbs) in accumulator column order; a trailing 1-limb row-count
    column (spec (-1, -1, 1)) drives the zero-slot drop. Only
    count/sum/avg lay out — eligibility is checked at pin time."""
    specs = []
    for ai, a in enumerate(aggs):
        if a.name == "count":
            specs.append((ai, 0, 1))
        elif a.name in ("sum", "avg"):
            specs.append((ai, 0, _ONEHOT_LIMBS))
            specs.append((ai, 1, 1))
        else:
            raise NotImplementedError(
                f"onehot lowering over {a.name}")
    specs.append((-1, -1, 1))
    return specs, sum(n for _, _, n in specs)


def onehot_agg_body(ctx, mask, group_items, aggs, cap, scap, sargs):
    """Segment aggregation as ONE one-hot int8 matmul chain on the MXU
    instead of a device argsort (the sorted lowering's 64-bit sort is
    the cost it avoids; neither has been measured on this chip).

    sargs (host-learned slot table, uploaded by the caller):
      skeys (scap,) i64  sorted packed keys, padded with _I64_MAX
      los   (K,)   i64   per-key-column pack offset
      spans (K,)   i64   per-key-column pack span (null code 0 included)
      nslots (1,)  i64   live slot count
    Exactness: values decompose into 9x7-bit limbs + the sign bit,
    each limb column accumulates in int32 (cap*127 < 2^31), and the
    host recombines with arbitrary-precision ints mod 2^64 — bitwise
    identical to an int64 sum for any input whose true sum fits int64.
    Any probe key missing from the table (new/changed data, span
    drift) is counted in res["miss"]; the caller falls back to the
    sorted lowering and relearns, so staleness can never corrupt a
    result. Keys/states for empty slots are dropped by the caller via
    the trailing row-count column."""
    packed = jnp.zeros(cap, dtype=jnp.int64)
    okr = jnp.ones(cap, dtype=bool)
    for i, g in enumerate(group_items):
        d, nl, _ = eval_expr(ctx, g)
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        d = d.astype(jnp.int64)
        nm = materialize_nulls(ctx, nl)
        lo = sargs["los"][i]
        span = sargs["spans"][i]
        code = jnp.where(nm, 0, d - lo + 1)
        # out-of-range codes would alias other packed tuples: they must
        # register as misses, never as hits
        okr = okr & (code >= 0) & (code < span)
        packed = packed * span + jnp.clip(code, 0, span - 1)
    sk = sargs["skeys"]
    nslots = sargs["nslots"][0]
    loc = jnp.searchsorted(sk, packed)
    locc = jnp.minimum(loc, scap - 1)
    hit = (sk[locc] == packed) & okr & (locc < nslots)
    miss = jnp.sum((mask & ~hit).astype(jnp.int64))
    live = mask & hit
    slot = jnp.where(live, locc, 0)     # dead rows masked out of the
    #                                     one-hot below, slot value moot
    specs, L = onehot_agg_limb_layout(aggs)
    vecs = []                           # (int64 vector, nlimbs)
    for ai, sj, n in specs:
        if ai < 0:
            vecs.append((live.astype(jnp.int64), 1))
            continue
        a = aggs[ai]
        if a.name == "count" or sj == 1:
            d, ok = _agg_eval_rows(ctx, a, mask, cap)
            vecs.append(((ok & live).astype(jnp.int64), 1))
        else:
            d, ok = _agg_eval_rows(ctx, a, mask, cap)
            dv = jnp.where(ok & live, d.astype(jnp.int64),
                           jnp.zeros((), jnp.int64))
            vecs.append((dv, _ONEHOT_LIMBS))

    blk = max(512, min(8192, (1 << 25) // max(scap, 1)))
    while cap % blk:
        blk >>= 1           # caps/blk are powers of two; blk <= cap
    blk = max(blk, 1)
    nblk = cap // blk
    sl_ids = jnp.arange(scap, dtype=jnp.int64)

    def block(b, acc):
        s = b * blk
        sl_b = jax.lax.dynamic_slice(slot, (s,), (blk,))
        lv_b = jax.lax.dynamic_slice(live, (s,), (blk,))
        oh = ((sl_b[:, None] == sl_ids[None, :]) &
              lv_b[:, None]).astype(jnp.int8)
        cols8 = []
        for vec, n in vecs:
            vb = jax.lax.dynamic_slice(vec, (s,), (blk,))
            if n == 1:
                cols8.append((vb & 1).astype(jnp.int8)[:, None])
            else:
                limbs = [((vb >> (7 * i)) & 0x7F).astype(jnp.int8)
                         for i in range(9)]
                limbs.append(((vb >> 63) & 1).astype(jnp.int8))
                cols8.append(jnp.stack(limbs, axis=1))
        lm = jnp.concatenate(cols8, axis=1)          # (blk, L)
        p = jax.lax.dot_general(oh, lm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return acc + p

    acc = jax.lax.fori_loop(
        0, nblk, block, jnp.zeros((scap, L), dtype=jnp.int32))
    return {"oh_acc": acc, "miss": miss, "ngroups": nslots}


def onehot_decode_states(acc, aggs, nslots):
    """Host side: recombine the int32 limb accumulator into exact int64
    state arrays -> (states, rowcnt). Mirrors _segscan_states' layout
    (count -> [cnt]; sum/avg -> [s, cnt])."""
    specs, _l = onehot_agg_limb_layout(aggs)
    states = [[None] * (2 if a.name in ("sum", "avg") else 1)
              for a in aggs]
    rowcnt = None
    off = 0
    for ai, sj, n in specs:
        cols = acc[:nslots, off:off + n]
        off += n
        if n == 1:
            out = cols[:, 0].astype(np.int64)
        else:
            # int64 wraparound IS the mod-2^64 recombination: the true
            # sum fits int64 by SQL semantics, so the wrapped total is
            # bit-exact (vectorized; no per-slot python loop)
            with np.errstate(over="ignore"):
                tot = np.zeros(nslots, dtype=np.int64)
                for i in range(9):
                    tot = tot + np.left_shift(
                        cols[:, i].astype(np.int64), 7 * i)
                tot = tot + np.left_shift(
                    cols[:, 9].astype(np.int64), 63)
            out = tot
        if ai < 0:
            rowcnt = out
        else:
            states[ai][sj] = out
    return states, rowcnt


def _dense_agg_states_reduce(ctx, mask, aggs, cap):
    """Global aggregation (nslots == 1) as plain masked reductions —
    no segment ops of any kind."""
    states = []
    for a in aggs:
        d, ok = _agg_eval_rows(ctx, a, mask, cap)
        cnt = jnp.sum(ok.astype(jnp.int64))[None]
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            z = jnp.zeros((), d.dtype)
            states.append([jnp.sum(jnp.where(ok, d, z))[None], cnt])
        elif a.name in ("min", "max"):
            sent, _ = _minmax_sentinel(a.name, d.dtype)
            red = jnp.min if a.name == "min" else jnp.max
            states.append([red(jnp.where(ok, d, sent))[None], cnt])
        elif a.name == "first_row":
            fpos = jnp.argmax(ok)       # first True; 0 when none (cnt=0)
            states.append([d[fpos][None], cnt])
        else:
            raise NotImplementedError(a.name)
    return {"present": jnp.sum(mask.astype(jnp.int64))[None],
            "states": states}


def _dense_agg_states_bcr(ctx, mask, aggs, slot, nslots, cap):
    """Tiny dense domains: one [nslots, cap] broadcast compare fused by
    XLA into per-slot reductions. Exact for every dtype and agg kind;
    reads each column nslots times, so gated by _BCR_MAX."""
    eq = slot[None, :] == jnp.arange(nslots)[:, None]     # [nslots, cap]
    iota = jnp.arange(cap)
    states = []
    for a in aggs:
        d, ok = _agg_eval_rows(ctx, a, mask, cap)
        sel = eq & ok[None, :]
        cnt = jnp.sum(sel.astype(jnp.int64), axis=1)
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            z = jnp.zeros((), d.dtype)
            states.append([jnp.sum(jnp.where(sel, d[None, :], z), axis=1),
                           cnt])
        elif a.name in ("min", "max"):
            sent, _ = _minmax_sentinel(a.name, d.dtype)
            red = jnp.min if a.name == "min" else jnp.max
            states.append([red(jnp.where(sel, d[None, :], sent), axis=1),
                           cnt])
        elif a.name == "first_row":
            fi = jnp.min(jnp.where(sel, iota[None, :], cap - 1), axis=1)
            states.append([d[fi], cnt])
        else:
            raise NotImplementedError(a.name)
    return {"present": jnp.sum(eq.astype(jnp.int64), axis=1),
            "states": states}


def _runs_agg_core(keys, key_nulls, mask, ctx, aggs, cap, bucket):
    """Contiguous-run partial aggregation: every maximal run of equal
    group keys becomes one partial group, extracted with cumulative
    sums + monotone searchsorted gathers — no sort, no scatter.

    Exactness: int sums/counts via prefix-sum differences (exact);
    float sums and min/max via a segmented associative scan that resets
    at run starts (no cross-group cancellation). Runs wholly masked out
    are dropped on device, so the returned ngroups counts only groups
    with visible rows. Unclustered inputs stay CORRECT (duplicate keys
    appear as multiple partials; the partial-agg merge combines them)
    but degrade to ~one run per row — callers should prefer this
    lowering when storage order clusters the key, which TPC-H fact
    tables and join positions do.

    key_nulls=None: the keys cannot be NULL (join positions, the fused
    pipeline's "posruns" kind) — no null masks are compared or
    returned."""
    idx = jnp.arange(cap)
    if keys:
        neq = jnp.zeros(cap - 1, dtype=bool)
        for i, k in enumerate(keys):
            neq = neq | (k[1:] != k[:-1])
            if key_nulls is not None:
                kn = key_nulls[i]
                neq = neq | (kn[1:] != kn[:-1])
        change = jnp.concatenate([jnp.ones(1, dtype=bool), neq])
    else:
        change = jnp.concatenate([jnp.ones(1, dtype=bool),
                                  jnp.zeros(cap - 1, dtype=bool)])
    cs_change = jnp.cumsum(change.astype(jnp.int64))      # run ordinal
    run_start = jax.lax.cummax(jnp.where(change, idx, -1))
    mi = mask.astype(jnp.int64)
    mask_cs = jnp.cumsum(mi)
    mask_before_run = (mask_cs - mi)[run_start]
    vstart = mask & (mask_cs == mask_before_run + 1)      # first valid row
    vcs = jnp.cumsum(vstart.astype(jnp.int64))
    ngroups = vcs[cap - 1]
    pos = jnp.searchsorted(vcs, jnp.arange(1, bucket + 1))
    posc = jnp.minimum(pos, cap - 1)
    rs = run_start[posc]                                  # run start
    rid = cs_change[posc]
    re = jnp.minimum(jnp.searchsorted(cs_change, rid + 1), cap) - 1

    out_keys = [k[posc] for k in keys]
    out_key_nulls = [kn[posc] for kn in key_nulls or ()]

    def seg_at_end(vals, combine):
        return _seg_scan(change, vals, combine)[re]

    states = []
    for a in aggs:
        d, ok = _agg_eval_rows(ctx, a, mask, cap)
        is_f = d.dtype.kind == "f"
        oki = ok.astype(jnp.int64)
        ok_cs = jnp.cumsum(oki)
        cnt = ok_cs[re] - (ok_cs - oki)[rs]
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            z = jnp.zeros((), d.dtype)
            v0 = jnp.where(ok, d, z)
            if is_f:
                s = seg_at_end(v0, jnp.add)
                s = jnp.where(cnt > 0, s, z)
            else:
                scs = jnp.cumsum(v0)
                s = scs[re] - (scs - v0)[rs]
            states.append([s, cnt])
        elif a.name in ("min", "max"):
            sent, comb = _minmax_sentinel(a.name, d.dtype)
            s = seg_at_end(jnp.where(ok, d, sent), comb)
            s = jnp.where(cnt > 0, s, sent)
            states.append([s, cnt])
        elif a.name == "first_row":
            ford = (ok_cs - oki)[rs] + 1
            fpos = jnp.minimum(jnp.searchsorted(ok_cs, ford), cap - 1)
            states.append([d[fpos], cnt])
        else:
            raise NotImplementedError(a.name)
    return {"ngroups": ngroups, "keys": out_keys,
            "key_nulls": out_key_nulls, "states": states}


def runs_agg_body(ctx, mask, group_items, aggs, cap, group_bucket):
    """sort_agg_body's TPU lowering without the sort: group keys are
    evaluated, contiguous equal-key runs become partial groups
    (_runs_agg_core). Same output contract as sort_agg_body, except
    groups appear in first-occurrence order (downstream merge is
    order-insensitive) and unclustered duplicate keys yield multiple
    partials for the merge to combine."""
    if not group_items:
        r = _dense_agg_states_reduce(ctx, mask, aggs, cap)
        return {"ngroups": jnp.asarray(1, dtype=jnp.int64), "keys": [],
                "key_nulls": [], "states": r["states"]}
    keys, key_nulls = [], []
    for g in group_items:
        d, nl, _sd = eval_expr(ctx, g)
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        d = d.astype(jnp.int64) if d.dtype != jnp.int64 else d
        nm = materialize_nulls(ctx, nl)
        keys.append(jnp.where(nm, 0, d))
        key_nulls.append(nm)
    return _runs_agg_core(keys, key_nulls, mask, ctx, aggs, cap,
                          group_bucket)


def _seg_scan(flags, vals, combine):
    """Segmented inclusive scan along the last axis: `combine`
    accumulates within a segment and resets where flags is True
    (segment starts). flags: [cap] bool; vals: [..., cap]."""
    def op(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, combine(va, vb))
    f = jnp.broadcast_to(flags, vals.shape[:-1] + flags.shape)
    _, acc = jax.lax.associative_scan(op, (f, vals), axis=-1)
    return acc


def _segscan_states(aggs, make_row, fi_vals, seg_start, last, cap,
                    present=None):
    """Per-agg state arrays via segmented scans over sorted rows.

    make_row(a) -> (gather_base, d_sorted, ok_sorted): the agg arg in
    sorted segment order plus the array first_row gathers from (indexed
    by fi_vals). fi_vals: per sorted row, the index first_row should
    remember (original row for the dense path, sorted position for the
    sort path). present: per-slot live count, or None when every
    surviving slot is known non-empty. All additive states batch into
    one stacked scan per dtype."""
    def seg_reduce(vals, combine, identity):
        out = _seg_scan(seg_start, vals, combine)[..., last]
        if present is not None:
            out = jnp.where(present > 0, out, identity)
        return out

    states = []
    sum_rows, sum_slots = [], []
    for a in aggs:
        base, d_s, ok_s = make_row(a)
        cnt_row = ok_s.astype(jnp.int64)
        if a.name == "count":
            sum_slots.append((len(states), 0))
            sum_rows.append(cnt_row)
            states.append([None])
        elif a.name in ("sum", "avg"):
            sum_slots.append((len(states), 0))
            sum_rows.append(jnp.where(ok_s, d_s, jnp.zeros((), d_s.dtype)))
            sum_slots.append((len(states), 1))
            sum_rows.append(cnt_row)
            states.append([None, None])
        elif a.name in ("min", "max"):
            sent, comb = _minmax_sentinel(a.name, d_s.dtype)
            s = seg_reduce(jnp.where(ok_s, d_s, sent), comb, sent)
            sum_slots.append((len(states), 1))
            sum_rows.append(cnt_row)
            states.append([s, None])
        elif a.name == "first_row":
            fi = seg_reduce(jnp.where(ok_s, fi_vals, cap - 1),
                            jnp.minimum, cap - 1)
            sum_slots.append((len(states), 1))
            sum_rows.append(cnt_row)
            states.append([base[jnp.minimum(fi, cap - 1)], None])
        else:
            raise NotImplementedError(a.name)
    by_dtype = {}
    for row, (si, sj) in zip(sum_rows, sum_slots):
        by_dtype.setdefault(row.dtype, []).append((row, si, sj))
    for dt, items in by_dtype.items():
        stack = jnp.stack([r for r, _, _ in items])
        outs = _seg_scan(seg_start, stack, jnp.add)[..., last]
        if present is not None:
            outs = jnp.where(present > 0, outs, jnp.zeros((), dt))
        for i, (_, si, sj) in enumerate(items):
            states[si][sj] = outs[i]
    return states


def _dense_agg_states_sorted(ctx, mask, aggs, slot, nslots, cap):
    order = jnp.argsort(slot)
    ss = slot[order]
    seg_start = jnp.concatenate(
        [jnp.ones(1, dtype=bool), ss[1:] != ss[:-1]])
    sl_ids = jnp.arange(nslots)
    ends = jnp.searchsorted(ss, sl_ids, side="right")     # [nslots]
    last = jnp.maximum(ends - 1, 0)
    present = ends - jnp.searchsorted(ss, sl_ids, side="left")

    def make_row(a):
        d, row_ok = _agg_eval_rows(ctx, a, mask, cap)
        return d, d[order], row_ok[order]

    states = _segscan_states(aggs, make_row, order, seg_start, last,
                             cap, present=present)
    return {"present": present, "states": states}


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


def _psum_first(lv, lc, axis):
    """Exact cross-shard first_row merge: take the value from the FIRST
    shard (by axis index) that has any rows per slot. (The previous
    pmax-with-sentinel trick was wrong for values equal to the
    sentinel.)"""
    my = jax.lax.axis_index(axis)
    first = jax.lax.pmin(jnp.where(lc > 0, my, 1 << 30), axis)
    return jax.lax.psum(
        jnp.where(my == first, lv, jnp.zeros((), lv.dtype)), axis)


def _gather_minmax(name, st, axis):
    """Cross-shard min/max of a dense state table. XLA:TPU lowers
    64-bit all-reduces for SUM only (`pmax` over s64 fails to compile:
    "Supported lowering only of Sum all reduce", seen on four v5e
    chips) and every state here is int64/float64, so the per-shard
    tables are gathered and reduced locally — data movement plus an
    elementwise reduce, on every backend."""
    g = jax.lax.all_gather(st, axis)
    return jnp.min(g, axis=0) if name == "min" else jnp.max(g, axis=0)


def psum_dense_result(res, aggs, axis):
    """Merge per-shard dense_agg_states outputs with one allreduce per
    state array (the MPP hash exchange collapsed into psum)."""
    out = []
    for a, st in zip(aggs, res["states"]):
        if a.name == "count":
            out.append([jax.lax.psum(st[0], axis)])
        elif a.name in ("sum", "avg"):
            out.append([jax.lax.psum(st[0], axis),
                        jax.lax.psum(st[1], axis)])
        elif a.name in ("min", "max"):
            out.append([_gather_minmax(a.name, st[0], axis),
                        jax.lax.psum(st[1], axis)])
        elif a.name == "first_row":
            out.append([_psum_first(st[0], st[1], axis),
                        jax.lax.psum(st[1], axis)])
        else:
            raise NotImplementedError(a.name)
    return {"present": jax.lax.psum(res["present"], axis), "states": out}


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
    nslots = 1
    for s, _off in sizes:
        nslots *= s

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
        slot = jnp.zeros(cap, dtype=jnp.int64)
        for g, (size, off) in zip(group_items, sizes):
            d, nl, _ = eval_expr(ctx, g)
            if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
                d = jnp.full(cap, d)
            nm = materialize_nulls(ctx, nl)
            code = jnp.clip(jnp.where(nm, 0, d.astype(jnp.int64) - off + 1),
                            0, size - 1)
            slot = slot * size + code
        slot = jnp.where(mask, slot, nslots)
        local = dense_agg_states(ctx, mask, aggs, slot, nslots, cap)
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


def _compact_dense(dag, res, sizes, key_dicts, state_dicts):
    """Compact the dense slot table (host side; <= _DENSE_MAX slots)."""
    prefetch(res)
    present = host_array(res["present"])
    slots = np.nonzero(present > 0)[0]
    ngroups = len(slots)
    keys = []
    key_nulls = []
    rem = slots.copy()
    for size, off in reversed(sizes):
        code = rem % size
        rem = rem // size
        keys.append(np.where(code == 0, 0, code - 1 + off).astype(np.int64))
        key_nulls.append(code == 0)
    keys.reverse()
    key_nulls.reverse()
    states = [[host_array(s)[slots] for s in st] for st in res["states"]]
    return PartialAggResult(ngroups=ngroups, keys=keys, key_nulls=key_nulls,
                            states=states, key_dicts=key_dicts,
                            state_dicts=state_dicts)


def _agg_identity(name):
    if name in ("sum", "count", "avg"):
        return 0
    if name == "min":
        return _I64_MAX
    if name == "max":
        return -_I64_MAX
    return 0


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


def sort_agg_body(ctx, mask, group_items, aggs, cap, group_bucket,
                  impl=None):
    """Sort-based partial agg over an eval ctx + row mask (general group
    domains). Shared by the copr reader kernel and the fused pipeline.

    Fast path: all group keys packed into ONE int64 sort key using
    runtime min/max spans (values are data-dependent — fine for XLA;
    only SHAPES must be static), so grouping costs a single argsort.
    A compiled lax.cond falls back to stable lexicographic multi-sort
    when the combined span overflows 62 bits.

    Under the "runs" policy (TPU default) the sort is skipped entirely:
    contiguous equal-key runs become partial groups (runs_agg_body).
    `impl` overrides the policy (the runs-degradation guard pins
    unclustered query shapes to "sorted")."""
    impl = impl or _segment_impl()
    if impl == "runs":
        return runs_agg_body(ctx, mask, group_items, aggs, cap,
                             group_bucket)
    # ---- group keys ----
    keys = []
    key_nulls = []
    for g in group_items:
        d, nl, sd = eval_expr(ctx, g)
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        d = d.astype(jnp.int64) if d.dtype != jnp.int64 else d
        nm = materialize_nulls(ctx, nl)
        keys.append(jnp.where(nm, 0, d))
        key_nulls.append(nm)

    if not keys:
        # global aggregation: one group
        seg = jnp.zeros(cap, dtype=jnp.int64)
        ngroups = jnp.asarray(1, dtype=jnp.int64)
        order = jnp.arange(cap)
        sorted_mask = mask
        first_idx = jnp.zeros(group_bucket, dtype=jnp.int64)
        change = jnp.zeros(cap, dtype=bool).at[0].set(True)
    else:
        # per-key codes: NULL -> 0, value -> (v - min + 1); span per key
        codes, spans = [], []
        fits = jnp.asarray(True)
        for k, kn in zip(keys, key_nulls):
            live = jnp.where(mask & ~kn, k, _I64_MAX)
            lo = jnp.min(live)
            lo = jnp.where(lo == _I64_MAX, 0, lo)       # no live rows
            hi = jnp.max(jnp.where(mask & ~kn, k, -_I64_MAX))
            hi = jnp.where(hi == -_I64_MAX, 0, hi)
            raw = hi - lo + 2
            # int64 wraparound (keys near +-2^62) -> raw <= 0: packing
            # would corrupt codes, force the multisort branch
            fits = fits & (raw > 0)
            codes.append(jnp.where(kn, 0, k - lo + 1))
            spans.append(jnp.maximum(raw, 1))
        total_bits = jnp.zeros((), dtype=jnp.float64)
        for s in spans:
            total_bits = total_bits + jnp.log2(s.astype(jnp.float64))
        fits = fits & (total_bits < 61.0)

        def packed_order(_):
            packed = jnp.zeros(cap, dtype=jnp.int64)
            for c, s in zip(codes, spans):
                packed = packed * s + c
            packed = jnp.where(mask, packed, _I64_MAX)
            order = jnp.argsort(packed, stable=True)
            sp = packed[order]
            change = (sp != jnp.roll(sp, 1)).at[0].set(True)
            return order, change

        def multisort_order(_):
            def sort_by(order, arr):
                vals = arr[order]
                idx = jnp.argsort(vals, stable=True)
                return order[idx]
            order = jnp.arange(cap)
            # sort so invalid rows go last: key = (~mask, keys..., )
            for k, kn in zip(reversed(keys), reversed(key_nulls)):
                order = sort_by(order, jnp.where(mask, k, _I64_MAX))
                order = sort_by(order,
                                jnp.where(mask, kn.astype(jnp.int64), 2))
            order = sort_by(order, (~mask).astype(jnp.int64))
            change = jnp.zeros(cap, dtype=bool)
            for k, kn in zip(keys, key_nulls):
                sk = jnp.where(mask, k, _I64_MAX)[order]
                skn = jnp.where(mask, kn.astype(jnp.int64), 2)[order]
                change = change | (sk != jnp.roll(sk, 1)) | \
                    (skn != jnp.roll(skn, 1))
            change = change.at[0].set(True)
            return order, change

        order, change = jax.lax.cond(fits, packed_order, multisort_order,
                                     operand=None)
        sorted_mask = mask[order]
        change = change & sorted_mask
        seg = jnp.cumsum(change.astype(jnp.int64)) - 1
        seg = jnp.where(sorted_mask, seg, group_bucket)  # overflow slot
        ngroups = jnp.max(jnp.where(sorted_mask, seg, -1)) + 1
        seg = jnp.minimum(seg, group_bucket)   # clamp; detect on host
        first_idx = jax.ops.segment_min(
            jnp.arange(cap), seg, num_segments=group_bucket + 1,
            indices_are_sorted=True)[:group_bucket]
        first_idx = jnp.minimum(first_idx, cap - 1)

    out_keys = []
    out_key_nulls = []
    if keys:
        for k, kn in zip(keys, key_nulls):
            out_keys.append(k[order][first_idx])
            out_key_nulls.append(kn[order][first_idx])

    # ---- agg states ----
    if impl == "sorted":
        # seg is sorted by construction: segmented scans, no scatter
        # (the TPU variadic-scatter serialization — see
        # dense_agg_states)
        sl_ids = jnp.arange(group_bucket)
        last = jnp.maximum(jnp.searchsorted(seg, sl_ids,
                                            side="right") - 1, 0)

        def make_row(a):
            d, row_ok = _agg_eval_rows(ctx, a, mask, cap)
            dv = d[order] if keys else d
            ok = row_ok[order] if keys else row_ok
            return dv, dv, ok

        states = _segscan_states(aggs, make_row, jnp.arange(cap),
                                 change, last, cap)
        return {"ngroups": ngroups, "keys": out_keys,
                "key_nulls": out_key_nulls, "states": states}
    states = []
    for a in aggs:
        if a.args:
            d, nl, sd = eval_expr(ctx, a.args[0])
            if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
                d = jnp.full(cap, d)
            nm = materialize_nulls(ctx, nl)
            dv = d[order] if keys else d
            nv = nm[order] if keys else nm
            row_ok = sorted_mask & ~nv
        else:   # count(*)
            dv = jnp.ones(cap, dtype=jnp.int64)
            row_ok = sorted_mask
        segN = group_bucket + 1
        if a.name == "count":
            st = [jax.ops.segment_sum(row_ok.astype(jnp.int64), seg,
                                      num_segments=segN,
                                      indices_are_sorted=True)[:group_bucket]]
        elif a.name in ("sum", "avg", "first_row"):
            zero = jnp.zeros((), dtype=dv.dtype)
            vals = jnp.where(row_ok, dv, zero)
            s = jax.ops.segment_sum(vals, seg, num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            c = jax.ops.segment_sum(row_ok.astype(jnp.int64), seg,
                                    num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            if a.name == "first_row":
                fi = jax.ops.segment_min(
                    jnp.where(row_ok, jnp.arange(cap), cap - 1), seg,
                    num_segments=segN,
                    indices_are_sorted=True)[:group_bucket]
                st = [dv[jnp.minimum(fi, cap - 1)], c]
            else:
                st = [s, c]
        elif a.name == "min":
            big = (jnp.asarray(np.float64(np.inf))
                   if dv.dtype.kind == "f" else jnp.asarray(_I64_MAX))
            vals = jnp.where(row_ok, dv, big.astype(dv.dtype))
            s = jax.ops.segment_min(vals, seg, num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            c = jax.ops.segment_sum(row_ok.astype(jnp.int64), seg,
                                    num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            st = [s, c]
        elif a.name == "max":
            small = (jnp.asarray(np.float64(-np.inf))
                     if dv.dtype.kind == "f" else jnp.asarray(-_I64_MAX))
            vals = jnp.where(row_ok, dv, small.astype(dv.dtype))
            s = jax.ops.segment_max(vals, seg, num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            c = jax.ops.segment_sum(row_ok.astype(jnp.int64), seg,
                                    num_segments=segN,
                                    indices_are_sorted=True)[:group_bucket]
            st = [s, c]
        else:
            raise NotImplementedError(a.name)
        states.append(st)
    return {"ngroups": ngroups, "keys": out_keys,
            "key_nulls": out_key_nulls, "states": states}



def sorted_run_starts(kvecs, min_rows=1024):
    """Pre-sorted single-key fast path shared by the host partial agg
    and the partial MERGE (executors.HashAggExec): when the one key
    vector is already non-decreasing, group boundaries are run
    boundaries — no argsort / np.unique. -> (starts, change) or
    (None, None). Callers pick their own null sentinel BEFORE calling
    (the two sites differ) and derive inverse/firsts as needed."""
    if len(kvecs) != 1 or len(kvecs[0]) <= min_rows or \
            not bool(np.all(kvecs[0][:-1] <= kvecs[0][1:])):
        return None, None
    kv = kvecs[0]
    change = np.empty(len(kv), dtype=bool)
    change[0] = True
    np.not_equal(kv[1:], kv[:-1], out=change[1:])
    return np.nonzero(change)[0], change

def _host_partial_agg(ctx, dag, valid, shared_dicts=None):
    """numpy fallback with identical output layout.

    shared_dicts: when the caller aggregates chunk-by-chunk, pass ONE
    dict ({group_idx: StringDict}) for the whole loop — raw-string keys
    must encode through a dict shared across chunks or the int64 codes
    are not comparable when the partials merge."""
    mask = valid
    xp = np
    keys = []
    key_nulls = []
    key_dict_override = {}
    for gi, g in enumerate(dag.group_items):
        d, nl, sd = eval_expr(ctx, g)
        if np.isscalar(d):
            d = np.full(ctx.n, d)
        d = np.asarray(d)
        nm = np.asarray(materialize_nulls(ctx, nl))
        if d.dtype == object and sd is None:
            # raw strings (e.g. null-padded columns from a left join
            # fallback): encode into a dict so keys stay int64
            from ..chunk.device import StringDict
            if shared_dicts is not None:
                sd2 = shared_dicts.setdefault(gi, StringDict())
            else:
                sd2 = StringDict()
            d = np.array([0 if m else sd2.encode_one(str(v))
                          for v, m in zip(d, nm)], dtype=np.int64)
            key_dict_override[gi] = sd2
        d = d.astype(np.int64)
        keys.append(np.where(nm, 0, d))
        key_nulls.append(nm)
    idx = np.nonzero(mask)[0]
    starts = None       # run starts when keys arrive pre-sorted
    if keys:
        kvecs = [np.where(kn, -1, k)[idx] for k, kn in zip(keys, key_nulls)]
        starts, _change = sorted_run_starts(kvecs)
        if starts is not None:
            # pre-sorted single key (clustered-PK order, e.g. GROUP BY
            # l_orderkey over lineitem): group boundaries are run
            # boundaries — no argsort, and the agg loop below uses
            # exact dtype-preserving ufunc.reduceat instead of the
            # unbuffered (slow) ufunc.at scatters
            ngroups = len(starts)
            firsts = idx[starts]
        else:
            kmat = np.stack(kvecs, axis=1)
            uniq, inverse = np.unique(kmat, axis=0, return_inverse=True)
            ngroups = len(uniq)
            firsts = np.full(ngroups, np.iinfo(np.int64).max,
                             dtype=np.int64)
            np.minimum.at(firsts, inverse, idx)
        out_keys = [k[firsts] for k in keys]
        out_key_nulls = [kn[firsts] for kn in key_nulls]
    else:
        ngroups = 1
        inverse = np.zeros(len(idx), dtype=np.int64)
        out_keys = []
        out_key_nulls = []
    states = []
    for a in dag.aggs:
        if a.args:
            d, nl, _ = eval_expr(ctx, a.args[0])
            if np.isscalar(d):
                d = np.full(ctx.n, d)
            nm = np.asarray(materialize_nulls(ctx, nl))
            dv = np.asarray(d)[idx]
            ok = ~nm[idx]
        else:
            dv = np.ones(len(idx), dtype=np.int64)
            ok = np.ones(len(idx), dtype=bool)
        if starts is not None:
            cnt = np.add.reduceat(ok.astype(np.int64), starts)
        else:
            cnt = np.zeros(ngroups, dtype=np.int64)
            np.add.at(cnt, inverse, ok.astype(np.int64))
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            if starts is not None:
                s = np.add.reduceat(np.where(ok, dv, 0), starts)
            else:
                s = np.zeros(ngroups, dtype=dv.dtype)
                np.add.at(s, inverse, np.where(ok, dv, 0))
            states.append([s, cnt])
        elif a.name == "first_row":
            if starts is not None:
                pos = np.where(ok, np.arange(len(idx)),
                               np.iinfo(np.int64).max)
                fp = np.minimum.reduceat(pos, starts)
                fi = idx[np.minimum(fp, max(len(idx) - 1, 0))]
                fi = np.where(fp == np.iinfo(np.int64).max,
                              max(ctx.n - 1, 0), fi)
            else:
                fi = np.full(ngroups, np.iinfo(np.int64).max,
                             dtype=np.int64)
                np.minimum.at(fi, inverse[ok], idx[ok])
                fi = np.minimum(fi, max(ctx.n - 1, 0))
            states.append([np.asarray(d)[fi], cnt])
        elif a.name == "min":
            big = np.inf if dv.dtype.kind == "f" else _I64_MAX
            if starts is not None:
                s = np.minimum.reduceat(
                    np.where(ok, dv, np.asarray(big, dtype=dv.dtype)),
                    starts)
            else:
                s = np.full(ngroups, big, dtype=dv.dtype)
                np.minimum.at(s, inverse, np.where(ok, dv, big))
            states.append([s, cnt])
        elif a.name == "max":
            small = -np.inf if dv.dtype.kind == "f" else -_I64_MAX
            if starts is not None:
                s = np.maximum.reduceat(
                    np.where(ok, dv, np.asarray(small, dtype=dv.dtype)),
                    starts)
            else:
                s = np.full(ngroups, small, dtype=dv.dtype)
                np.maximum.at(s, inverse, np.where(ok, dv, small))
            states.append([s, cnt])
        else:
            raise NotImplementedError(a.name)
    kd, sd = capture_agg_dicts(dag, ctx.cols)
    for gi, sd2 in key_dict_override.items():
        kd[gi] = sd2
    return PartialAggResult(ngroups=ngroups, keys=out_keys,
                            key_nulls=out_key_nulls, states=states,
                            key_dicts=kd, state_dicts=sd)
