"""Which aggregation lowering a shape takes, and the lowerings.

Both coprocessor engines (dag_exec.py per DAG, pipeline.py fused) end in
a partial aggregation. Here, and nowhere else: which kind of kernel a
shape gets (`Lowering.choose`; docs/PERFORMANCE.md "Fragment compiler"),
the thresholds between the kinds, what earlier runs taught about a shape
(`ShapeState`, in `copr._host_cache`), when a run must be repeated
(`Lowering.observe`), the bodies both engines trace, their host twin
and `PartialAggResult`. The engines keep how they dispatch, how a result
becomes partials, and their spans.
"""
from __future__ import annotations

import math

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax
import jax.numpy as jnp

from ..chunk.device import shape_bucket
from ..expression import EvalCtx, eval_expr
from ..expression.vec import materialize_nulls
from ..utils import metrics as _metrics, tracing as _tracing
from ..utils.fetch import prefetch, host_array, host_int

_I64_MAX = np.iinfo(np.int64).max

# ---- the thresholds ---------------------------------------------------
# Constants: none is read from the environment. Each says what the chip
# has shown of it (PERF.md) and what it has not.

# Most slots of a dense table reduced by broadcast-compare (the runs
# policy's only dense form): a [nslots, cap] compare+reduce reads each
# value column nslots times (q1's 12 slots, q5's 25). The step shows
# as 0.97 s against 1.41 s in PERF.md finding 4. The form is linear in
# the slots, 0.03 ms a slot a 4,194,304-lane block for one int64 sum
# and two counts (PR 43's step 0, PERF.md section 7: 8.1 ms at 256
# slots, 57 at 2,048, 889 at 32,768), so 64 slots are about 2 ms; what
# the scatter past it costs at 65 slots: not measured.
BCR_MAX = 64
# The runs lowering calls itself degraded above this many partials AND
# half the partition's rows (`runs_degraded`). The half was measured
# (PR 27: at a quarter, q18's subquery sat on the line); this floor: not
# measured.
RUNS_DEGRADE_MIN = 65536
# Most groups of a learned slot table. At this many the matmul form
# reads 169.5 ms a 4,194,304-lane block for one sum (PR 43's step 0),
# five gathers' worth; no cell learns a table of more than 256.
ONEHOT_MAX = 32768
# Most slots of a learned table reduced by a compare at the slot and an
# int64 select-and-sum (`onehot_form`); past it, the int8 matmul. PR
# 43's step 0, a 4,194,304-lane block, ONE sum and two counts, ms:
# compare 8.1 / 57.0 / 889.5 at 256 / 2,048 / 32,768 slots (linear: 0.03
# a slot), the matmul 13.8 / 18.2 / 169.5; they cross near 512 slots,
# where neither was timed, so the line is at the largest size the compare
# was seen to win. The compare reads its [scap, cap] tile once a state
# and the matmul takes more states in the MXU's idle columns: with more
# sums the crossing is lower, by how much: not measured.
ONEHOT_CMP_MAX = 256
# A one-hot limb column accumulates in int32: exact while
# cap * 127 < 2^31.
ONEHOT_CAP_MAX = 1 << 23
# Most slots of a position-grouped / of a dense table off the runs
# policy (the CPU's scatter). Not measured on the chip: it never takes
# them.
POS_DENSE_MAX = 1 << 22
DENSE_MAX = 1 << 18
# The smallest group bucket a sort/runs kernel is built with. Not
# measured.
GROUP_BUCKET_MIN = 1024

# The two seams tests hold (module globals, monkeypatched; never read
# from the environment): the policy forced on any backend, and the
# one-hot kind let onto the CPU backend. The second does not follow the
# first: tests that force "runs" on the CPU must not start learning
# one-hot tables whose matmul a host core runs in seconds.
_FORCE_SEGMENT_IMPL = None      # "scatter" | "sorted" | "runs" | None
_FORCE_ONEHOT = False


def policy():
    """How segment aggregations lower on this backend; to callers an
    opaque token (the kernel cache keys carry it). "runs" off the CPU:
    contiguous equal-key runs become partials the merge combines — no
    sort, no scatter, compact when storage order clusters the key.
    "scatter" (jax.ops.segment_*) on the CPU: fast there and the oracle
    of the tests; XLA:TPU serializes it row by row. "sorted" (argsort +
    segmented scans) is the pin of a shape whose keys do not cluster:
    393 s and 28.9 GB to compile at 4M lanes (PERF.md, PR 27). The rest
    of their costs: not measured on this chip."""
    impl = _FORCE_SEGMENT_IMPL
    if impl:
        if impl not in ("scatter", "sorted", "runs"):
            raise ValueError(f"_FORCE_SEGMENT_IMPL={impl!r}: expected "
                             "one of scatter|sorted|runs")
        return impl
    return "runs" if jax.default_backend() != "cpu" else "scatter"


def dense_nslots(sizes):
    n = 1
    for s, _off in sizes:
        n *= s
    return n


def dense_form(nslots):
    """How a dense table of `nslots` slots is reduced: "reduce" | "bcr"
    | "sorted" | "scatter"; None: no dense form at this size. Under the
    runs policy a table of more than BCR_MAX slots is a scatter
    (`jax.ops.segment_*`, which XLA:TPU serializes: seconds over a 6M-row
    table, and two seconds to compile where the argsort program takes
    minutes and 29 GB) up to DENSE_MAX slots, and no shape's first
    choice (`dense_fits`): only a shape pinned to it takes it. The one
    place that rule lives: `Lowering` and `dense_agg_states` ask here."""
    if nslots == 1:
        # global aggregation: a scatter into one slot is never better
        # than a plain masked reduce, on ANY backend (on the CPU proxy
        # segment_sum lowers to a serial scatter — q6 lost 40% to it)
        return "reduce"
    impl = policy()
    if impl != "runs":
        return impl
    if nslots <= BCR_MAX:
        return "bcr"
    return "scatter" if nslots <= DENSE_MAX else None


def dense_first(nslots) -> bool:
    """Is a dense table of `nslots` slots a shape's first choice under
    the policy? Under "runs" only the scatter-free forms are."""
    form = dense_form(nslots)
    return form is not None and not (form == "scatter" and
                                     policy() == "runs")


def dense_fits(sizes) -> bool:
    """Is the dense layout `sizes` a shape's first choice?"""
    return dense_first(dense_nslots(sizes))


def onehot_fits(nslots) -> bool:
    """May a one-hot slot table hold `nslots` groups?"""
    return 0 < nslots <= ONEHOT_MAX


def runs_degraded(ngroups, m) -> bool:
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
    return ngroups > max(RUNS_DEGRADE_MIN, m // 2)


def host_unclustered(dag, cols, n) -> bool:
    """Would the runs lowering explode over these `n` rows? One host
    pass over the group items (plain integer items alone: anything else
    answers False and the device run decides) counting the rows whose
    key differs from the row before: the least partials the runs
    lowering can return, since a filter only splits runs further.
    Asked once a shape, while nothing is pinned (`Lowering`)."""
    if not dag.group_items or n < 2:
        return False
    ctx = EvalCtx(np, n, cols, host=True)
    change = np.zeros(n - 1, dtype=bool)
    for g in dag.group_items:
        try:
            data, nulls, sd = eval_expr(ctx, g)
        except Exception:               # noqa: BLE001
            return False
        if sd is not None or np.isscalar(data) or nulls is not None and \
                not np.isscalar(nulls) and np.asarray(nulls).any():
            return False
        data = np.asarray(data)
        if data.dtype.kind not in "iu" or len(data) != n:
            return False
        change |= data[1:] != data[:-1]
    return runs_degraded(int(np.count_nonzero(change)) + 1, n)


# ---- what a shape has taught ------------------------------------------

def _slot(prefix):
    """One learned fact of a shape, per gc epoch: a compaction that
    restores clustering lets the shape try again what it had pinned
    off."""
    def key(self):
        return (prefix, self._epoch) + self._gb
    return property(lambda self: self._c.get(key(self)),
                    lambda self, v: self._c.__setitem__(key(self), v),
                    lambda self: self._c.pop(key(self), None))


class ShapeState:
    """What earlier runs taught about one (table, gc epoch, group items,
    aggregates) shape, for both engines: a pin says the table's rows do
    not cluster by these keys. Kept in `copr._host_cache` under keys
    only this class builds."""

    __slots__ = ("_c", "_gb", "_epoch")

    def __init__(self, copr, tbl, group_items, aggs):
        self._c = copr._host_cache
        self._epoch = tbl.gc_epoch
        self._gb = ("gb", tbl.uid,
                    tuple(g.fingerprint() for g in group_items),
                    tuple(a.fingerprint() for a in aggs))

    pin = _slot("aggimpl")              # "sorted" | "dense" | None
    compact = _slot("fcompact")         # late buffer: int | "off" | None
    early_compact = _slot("fecompact")  # early buffer, likewise
    topn_off = _slot("ftopn_off")       # True | None
    onehot = _slot("onehot")            # slot table | False (never) | None

    @property
    def bucket(self):
        """The group bucket a sort/runs kernel is built with."""
        return max(GROUP_BUCKET_MIN, self._c.get(self._gb, 0))

    def grow_bucket(self, ngroups):
        self._c[self._gb] = max(self.bucket, shape_bucket(ngroups))


def _compact_verdict(state, which, ccap, nvalid, denom):
    """Learn/regrow policy of a compact-then-aggregate buffer (`which`:
    "compact" | "early_compact") -> "retry" when the kernel must
    rebuild with a larger buffer; None otherwise (first sight of a
    shape learns the bucket when survivors are <= 1/8 of the partition,
    else pins compaction off)."""
    if ccap is not None and nvalid > ccap:
        if nvalid > denom // 4:
            # selectivity drifted: survivors are no longer a small
            # fraction — compaction would gather ~the whole partition
            # just to sort the same size again. Pin it off instead of
            # regrowing toward cap forever.
            setattr(state, which, "off")
        else:
            setattr(state, which, shape_bucket(nvalid))
        return "retry"
    if ccap is None and getattr(state, which) != "off":
        setattr(state, which, shape_bucket(max(nvalid, 1))
                if nvalid <= denom // 8 else "off")
    return None


def judged(site, kind, param, verdict):
    """One device run of an aggregation program, judged: a count in
    `tidb_tpu_agg_lowering_total{site, kind, verdict}` and the same on
    the open `consume` span. site: the `Lowering`'s; kind: the lowering
    that ran, the sort kind with its segment impl ("sort_runs",
    "sort_sorted"; the CPU's "sort_scatter") and a slot table reduced
    by compare at its code as "onehot_cmp" ("onehot": the matmul, which
    as a rule has searched for its slot); verdict: "stands"
    or why the run is thrown away and run again ("retry_<reason>")."""
    if kind == "sort":
        kind = "sort_" + param[1]
    elif kind == "onehot" and param[2] == "cmp":
        kind = "onehot_cmp"
    _metrics.AGG_LOWERING.labels(site, kind, verdict).inc()
    _tracing.tag(lowering=kind, verdict=verdict)


# ---- the decision -----------------------------------------------------

class Lowering:
    """One statement's aggregation: what its group domain admits
    (.pos, .posruns, .sizes: at most one stands), resolved once, then
    `choose` a dispatch and `observe` a run.

    pos_spec: the fused pipeline's (group_map, pos_dims, nslots) or
    None. sizes: the dense layout, None, or a callable asked only when
    no position domain stands (it may cost a host pass). site: whose
    kernels, and the `site` of the runs `judged` — "fused" (one chip:
    every kind), "fused_mpp" (the mesh: no posruns, one-hot, top-n,
    early compaction), "dag" (per-DAG: dense or sort, no compaction).
    topn: the validated (kind, index, desc, k)."""

    __slots__ = ("state", "pos", "posruns", "sizes", "dense_alt", "site",
                 "dims", "topn")

    def __init__(self, state, pos_spec=None, sizes=None, *, site="fused",
                 dims=False, topn=None, unclustered=None):
        self.state, self.site, self.dims = state, site, dims
        self.topn = topn if site == "fused" else None
        # a position domain too large for the packed-slot lowering: on
        # one chip under the runs policy the positions stay the group
        # keys, as separate run keys ("posruns"); elsewhere the group
        # items are evaluated at fact width and sorted
        self.posruns = None
        if pos_spec is not None and (pos_spec[2] > POS_DENSE_MAX or
                                     not dense_first(pos_spec[2])):
            if site == "fused" and policy() == "runs":
                self.posruns = pos_spec
            pos_spec = None
        self.pos = pos_spec
        if pos_spec is not None:
            sizes = None
        elif callable(sizes):
            sizes = sizes()
        self.dense_alt = None
        if sizes is not None and not dense_fits(sizes):
            # big dense domains have no scatter-free dense lowering:
            # they fall to the contiguous-run partials, and keep the
            # layout for the day the runs degrade (pin "dense": the
            # scatter, where the other pin is the argsort program)
            self.dense_alt, sizes = sizes, None
            if state.pin is None and unclustered is not None and \
                    unclustered():
                # the host has counted the key changes: no runs program
                # is built to be thrown away
                state.pin = "dense"
        if sizes is not None:
            # a few dict codes (c_mktsegment over 150k customers): the
            # dense kind's compare-reduce beats runs over scattered
            # positions
            self.posruns = None
        self.sizes = sizes

    def _posruns_on(self):
        """Group on the join positions? Read a dispatch: a degraded
        partition pins the shape to "sorted" mid-statement. A learned
        one-hot table (it can only date from a pinned spell) keeps the
        one-hot kind."""
        return self.posruns is not None and \
            self.state.pin != "sorted" and \
            not isinstance(self.state.onehot, dict)

    def choose(self, cap):
        """-> (agg_kind, agg_param, ecap) for a partition of `cap` lanes
        as learned so far. agg_param: "posdense" (pos_dims, nslots);
        "dense" sizes; "onehot" (scap, slot_by, reducer); "sort" /
        "posruns" (bucket, segment impl / position dims, top-n
        candidates, late compact capacity). ecap: early compact capacity
        or None."""
        st, fused = self.state, self.site == "fused"
        if self.pos is not None:
            kind, param = "posdense", (tuple(self.pos[1]), self.pos[2])
        elif self.sizes is not None:
            kind, param = "dense", tuple(self.sizes)
        elif st.pin == "dense" and self.dense_alt is not None:
            self.sizes = self.dense_alt     # what the consumers decode by
            kind, param = "dense", tuple(self.sizes)
        elif fused and isinstance(st.onehot, dict) and \
                cap <= ONEHOT_CAP_MAX:
            kind, param = "onehot", (st.onehot["scap"],
                                     *onehot_form(st.onehot))
        else:
            posruns = self._posruns_on()
            # (a "dense" pin whose layout this statement lacks: the
            # policy's; a degraded run then pins "sorted")
            impl = "sorted" if st.pin == "sorted" else policy()
            bucket = st.bucket
            topn = None
            # candidate pruning is sound ONLY under the runs lowering:
            # its run order is storage order, so the partition-edge
            # (possibly split) groups are exactly runs 0 and ngroups-1,
            # which the kernel forces into the candidate set;
            # sorted/scatter order groups by key rank, where the edge
            # groups can sit anywhere. The coverage proof needs >= k
            # complete groups strictly above the candidate min: with
            # bucket < k+2 it can never pass, so don't burn a kernel
            # compile + permanent off-pin on a shape that cannot verify
            ts = self.topn
            if ts is not None and impl == "runs" and \
                    bucket >= ts[3] + 2 and not st.topn_off:
                topn = (ts[0], ts[1], ts[2], min(ts[3] + 66, bucket))
            ccap = st.compact if self.site != "dag" else None
            # both kinds share the bucket-growth retry, the compaction
            # policy and the top-n proof, so their agg_param differs in
            # one slot: the segment impl or the position dims
            kind = "posruns" if posruns else "sort"
            param = (bucket,
                     tuple(self.posruns[1]) if posruns else impl, topn,
                     ccap if isinstance(ccap, int) else None)
        ec = st.early_compact if fused else None
        ecap = ec if isinstance(ec, int) and ec < cap else None
        if ecap is not None and not self.dims:
            # zero-dim pipeline: downstream of the fact filter is ONE
            # aggregation pass — gather-compaction (cumsum + per-column
            # gathers) costs more than it saves. Compaction pays when
            # dim probes and multi-pass agg lowerings run at survivor
            # scale. (Neither side measured on this chip.)
            ecap = None
        if ecap is not None and kind in ("sort", "posruns"):
            # survivors are already compacted: the late (post-join)
            # compact stage would re-gather the same buffer
            param = param[:3] + (None,)
        return kind, param, ecap

    def observe(self, kind, param, ecap, cap, rows, ngroups=None,
                nvalid=None, fnvalid=None, hold=False):
        """Does a run stand? -> "retry": run the partition again with
        what this run taught (already in the state); None: consume it.
        rows / cap: the partition's; fnvalid: the fact filter's
        survivors BEFORE any compaction loss; nvalid: survivors at the
        aggregation; ngroups: partials — None where not reported.
        Every device run of an aggregation program comes here once and
        is counted once (`judged`) under its verdict. hold: a run that
        stands by its sizes is not counted yet, because its consumer
        can still throw it away: the consumer owes one `settle`."""
        why = self._run_again(kind, param, ecap, cap, rows, ngroups,
                              nvalid, fnvalid)
        if why or not hold:
            judged(self.site, kind, param, why or "stands")
        return "retry" if why else None

    def settle(self, kind, param, why=None):
        """The count of a run `observe` held: it stands, or is thrown
        away after all by what only its consumer sees (`why`:
        "onehot_miss", the learned slot table lacks a key;
        "topn_unproven", the candidates cannot be shown to cover the
        top k)."""
        judged(self.site, kind, param, "retry_" + why if why else "stands")

    def _run_again(self, kind, param, ecap, cap, rows, ngroups, nvalid,
                   fnvalid):
        """-> why the run must be repeated (the verdict's name), with
        the lesson already in the state; None: it stands."""
        st = self.state
        if fnvalid is not None and _compact_verdict(
                st, "early_compact", ecap, fnvalid, cap):
            return "retry_early_compact"
        if kind not in ("sort", "posruns"):
            return None
        if nvalid is not None and _compact_verdict(
                st, "compact", param[3], nvalid, cap):
            return "retry_compact"
        if (kind == "posruns" or param[1] == "runs") and \
                runs_degraded(ngroups, rows):
            # unclustered group keys: pin this shape to the dense
            # scatter where it has a dense layout, else to the sorted
            # lowering (one partial per group), before the bucket
            # learns the inflated count
            st.pin = "dense" if self.dense_alt is not None else "sorted"
            return "retry_pin_" + st.pin
        if ngroups > param[0]:
            # against the bucket THIS kernel was built with, not one
            # grown since by another partition: an overflowed run
            # truncated its key/state buffers
            st.grow_bucket(ngroups)
            return "retry_grow_bucket"
        return None

    def onehot_learnable(self, group_items, aggs, one, delta) -> bool:
        """May this statement learn a one-hot table from its partials?
        `one`: a 1-row host ctx's columns. Position-grouped shapes
        learn none: that kind evaluates every group item at fact width,
        the gathers the positions save."""
        if not group_items or self.pos is not None or \
                self._posruns_on() or self.sizes is not None or delta \
                or self.site != "fused" or self.state.onehot is False:
            return False
        if jax.default_backend() == "cpu" and not _FORCE_ONEHOT:
            # the one-hot matmul is O(cap*scap*limbs): for the MXU,
            # SECONDS on a host core — accelerators only
            return False
        ctx1 = EvalCtx(np, 1, one, host=True)
        for a in aggs:
            if a.name == "count":
                continue
            if a.name not in ("sum", "avg"):
                return False
            try:
                d1, _nl1, _sd1 = eval_expr(ctx1, a.args[0])
                dt = getattr(d1, "dtype", None)
                if dt is None or dt.kind != "i":
                    return False    # exact limb sums are int64-only
            except Exception:       # noqa: BLE001
                return False
        return True


# ---- the lowerings both engines trace ---------------------------------

class PartialAggResult:
    """Per-partition aggregation partials: group keys (encoded: dict codes /
    int64) + per-agg state arrays (sum/count/min/max). key_dicts/state_dicts
    carry StringDicts for string-typed keys/args (codes are comparable
    across partitions because dict transforms are deterministic over the
    shared table dictionary).

    ident: the indices into `keys` of the group items that identify the
    group, the others being functions of them (q10's six customer and
    nation columns beside `c_custkey`), or None: all of them. Set only
    by a producer that has verified what makes it true; the final merge
    (executors.HashAggExec) then groups on those items alone."""

    __slots__ = ("ngroups", "keys", "key_nulls", "states", "key_dicts",
                 "state_dicts", "ident")

    def __init__(self, ngroups, keys, key_nulls, states, key_dicts=None,
                 state_dicts=None, ident=None):
        self.ngroups = ngroups
        self.keys = keys
        self.key_nulls = key_nulls
        self.states = states
        self.key_dicts = key_dicts or [None] * len(keys)
        self.state_dicts = state_dicts or [None] * len(states)
        self.ident = ident


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


def dense_strides(dag, key_dicts, cols=None, n=0):
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
        if total > DENSE_MAX:
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
            if total > DENSE_MAX:
                return None
    return layout


def dense_agg_body(ctx, mask, group_items, aggs, sizes, cap):
    """Dense scatter-add partial agg over an eval ctx + row mask: direct
    segment ops into the dense key-product table. Shared by the copr
    reader kernel and the fused scan-join-agg pipeline kernel."""
    nslots = dense_nslots(sizes)
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

    Which form — a plain masked reduction, a [nslots, cap]
    broadcast-compare, one shared argsort of the slots + segmented
    scans, or segment-op scatters — is `dense_form`'s to say; the
    caller (`Lowering`) has already kept a domain with no dense form
    away. Costs on this chip: not measured."""
    form = dense_form(nslots) or "sorted"   # None: a caller that never asked
    if form == "reduce":
        return _dense_agg_states_reduce(ctx, mask, aggs, cap)
    if form == "bcr":
        return _dense_agg_states_bcr(ctx, mask, aggs, slot, nslots, cap)
    if form == "sorted":
        return _dense_agg_states_sorted(ctx, mask, aggs, slot, nslots, cap)
    states = []
    for a in aggs:
        d, row_ok = _agg_eval_rows(ctx, a, mask, cap)
        cnt = jax.ops.segment_sum(row_ok.astype(jnp.int64), slot,
                                  num_segments=nslots + 1)[:nslots]
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            s = jax.ops.segment_sum(jnp.where(row_ok, d, 0), slot,
                                    num_segments=nslots + 1)[:nslots]
            states.append([s, cnt])
        elif a.name in ("min", "max"):
            sent, _comb = _minmax_sentinel(a.name, d.dtype)
            seg_op = jax.ops.segment_min if a.name == "min" \
                else jax.ops.segment_max
            s = seg_op(jnp.where(row_ok, d, sent), slot,
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


# a learned slot table (small learned group domains): at most
# ONEHOT_MAX groups, at most ONEHOT_CAP_MAX lanes. Where the slot comes
# from and how a block is reduced are two decisions (`onehot_form`).
# What the chip read of them (PR 43's step 0, PERF.md section 7; a
# 4,194,304-lane block, 256 slots): the search for the slot is what the
# kind cost, not its reduction. `searchsorted` of the packed codes in
# 256 sorted s64 keys reads 678.5 ms, the packed code taken as the slot
# 1; the blocked one-hot int8 matmul (cap*scap*L int8 MACs, its block
# shrinking with scap to bound the materialized one-hot tile at 32MB)
# reads 13.8 ms, its one-hot build 9.8, its limbs 2.1, its dot 3.3; a
# compare of the 32-bit slot id against the slot axis and an exact int64
# select-and-sum, the dense kind's form, 8.1. Ten limbs against five
# and a sign is 3.0 against 2.6 ms in a matmul whose operands keep the
# lanes minor: the limb count is not where the time is.
_ONEHOT_LIMBS = 10        # 9 x 7-bit limbs (bits 0..62) + the sign bit


def onehot_form(table):
    """-> (slot_by, reducer) of a run over the learned slot `table`, each
    from what the table holds. slot_by: "code", the packed code itself,
    when every code of the learned spans is under `scap` and there is
    nothing to search (q9: 26 x 8 = 208 codes, 175 of them learned,
    under 256), else "search" in the sorted keys. reducer: "cmp", the
    compare at a coded slot of at most ONEHOT_CMP_MAX, else "mxu", the
    int8 matmul."""
    scap = table["scap"]
    if math.prod(int(x) for x in table["spans"]) > scap:
        return "search", "mxu"
    return "code", "cmp" if scap <= ONEHOT_CMP_MAX else "mxu"


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


def _onehot_cmp_states(ctx, live, aggs, slot, scap, cap):
    """The compare form's reduction, `_dense_agg_states_bcr`'s for the
    aggregates a slot table takes: one [scap, cap] compare of int32 slot
    ids (a dead lane's is -1) that XLA fuses into a reduction a slot,
    every sum in int64 whatever the column's width -> (states, rowcnt),
    int64 arrays of `scap` in `_segscan_states`' layout."""
    eq = slot[None, :] == jnp.arange(scap, dtype=jnp.int32)[:, None]
    z = jnp.zeros((), jnp.int64)
    states = []
    for a in aggs:
        d, ok = _agg_eval_rows(ctx, a, live, cap)
        sel = eq & ok[None, :]
        cnt = jnp.sum(sel.astype(jnp.int64), axis=1)
        if a.name == "count":
            states.append([cnt])
        elif a.name in ("sum", "avg"):
            states.append([jnp.sum(jnp.where(
                sel, d.astype(jnp.int64)[None, :], z), axis=1), cnt])
        else:
            raise NotImplementedError(f"onehot lowering over {a.name}")
    return states, jnp.sum(eq.astype(jnp.int64), axis=1)


def onehot_agg_body(ctx, mask, group_items, aggs, cap, scap, slot_by,
                    reducer, sargs):
    """Segment aggregation into a host-learned slot table instead of a
    device argsort (the sorted lowering's 64-bit sort is the cost it
    avoids: 393 s to compile at 4M lanes, PERF.md PR 27; the forms'
    own prices on the chip are in the header above).

    sargs (host-learned slot table, uploaded by the caller):
      skeys (scap,) i64  sorted packed keys, padded with _I64_MAX
      los   (K,)   i64   per-key-column pack offset
      spans (K,)   i64   per-key-column pack span (null code 0 included)
      nslots (1,)  i64   live slot count
    slot_by, reducer (`onehot_form`): a "code" slot indexes the result by
    packed code, not by learned slot, and searches nothing: a row on a
    code the table lacks is the consumer's to find in the row counts
    (`onehot_states`). "cmp" returns res["states"], int64 arrays of
    `scap` a state, and res["rowcnt"]; exact as the dense kind's int64
    sums are. "mxu" returns res["oh_acc"]: values decompose into
    9x7-bit limbs + the sign bit, each limb column accumulates in int32
    (cap*127 < 2^31), and the host recombines mod 2^64
    (`onehot_decode_states`) — bitwise identical to an int64 sum.
    Any probe key missing from the table (new/changed data, span
    drift) is counted in res["miss"]; the caller falls back to the
    sorted lowering and relearns, so staleness can never corrupt a
    result. Keys/states for empty slots are dropped by the caller via
    the row count a slot."""
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
    nslots = sargs["nslots"][0]
    if slot_by == "code":
        # every code of the spans is under scap: the code is the slot
        at, hit = packed, okr
    else:
        sk = sargs["skeys"]
        loc = jnp.searchsorted(sk, packed)
        at = jnp.minimum(loc, scap - 1)
        hit = (sk[at] == packed) & okr & (at < nslots)
    miss = jnp.sum((mask & ~hit).astype(jnp.int64))
    live = mask & hit
    if reducer == "cmp":
        states, rowcnt = _onehot_cmp_states(
            ctx, live, aggs, jnp.where(live, at, -1).astype(jnp.int32),
            scap, cap)
        return {"states": states, "rowcnt": rowcnt, "miss": miss,
                "ngroups": nslots}
    slot = jnp.where(live, at, 0)       # dead rows masked out of the
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


def onehot_states(res, aggs, table, slot_by):
    """Host side -> (states, rowcnt, miss) over the table's learned
    slots, from either reducer's result. Under a "code" slot the device
    indexed by packed code: the slots are read at the table's codes,
    and the rows that fell on any other code are misses the device
    could not count."""
    if "oh_acc" in res:
        states, every = onehot_decode_states(
            host_array(res["oh_acc"]), aggs, table["scap"])
    else:
        states = [[host_array(s) for s in st] for st in res["states"]]
        every = host_array(res["rowcnt"])
    nslots = table["nslots"]
    at = table["skeys"][:nslots] if slot_by == "code" else slice(nslots)
    rowcnt = every[at]
    miss = host_int(res["miss"]) + int(every.sum() - rowcnt.sum())
    return [[s[at] for s in st] for st in states], rowcnt, miss


def onehot_decode_states(acc, aggs, nslots):
    """Host side of the matmul form: recombine the int32 limb
    accumulator into exact int64 state arrays -> (states, rowcnt).
    Mirrors _segscan_states' layout (count -> [cnt]; sum/avg -> [s,
    cnt])."""
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
    reads each column nslots times, so gated by BCR_MAX."""
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


# ---- inverting a prefix count ------------------------------------------
# Where the k-th set lane of a mask is: what a compaction gathers
# through and where the runs lowering finds a run's first valid row. A
# look-up is paid per index (7.4 ns a lane on a v5e, PERF.md section 7)
# and a row at one index costs little more than a scalar, so the count
# is searched k-ary by rows of block ends, not binary by scalars.
# Lanes a row of block ends holds, and the most ends the top level
# compares whole against every probe. Step 0 of PR 44 read them
# (benchmarks/microbench_tpu.py select).
SELECT_ROW = 128
SELECT_TOP = 512
_I32_MAX = np.iinfo(np.int32).max


def _noted_select(site, form):
    """One count a traced program a call site in
    `tidb_tpu_prefix_select_total{site, form}`, and `select_<site>` on
    the span that is open while the program is traced."""
    if site is None:
        return
    _metrics.PREFIX_SELECT.labels(site, form).inc()
    _tracing.tag(**{"select_" + site: form})


def prefix_count(flags):
    """-> the inclusive count of set lanes, int32: exact under 2^31
    lanes, which every caller's `cap` is (ONEHOT_CAP_MAX, device_rows,
    a mesh shard), and one 32-bit gather a look-up where an int64 count
    is two."""
    return jnp.cumsum(flags.astype(jnp.int32))


def prefix_search(cs, probes, site=None, row=SELECT_ROW, top=SELECT_TOP):
    """Lanes of the non-decreasing int32 count `cs` that read under each
    probe: position for position `jnp.searchsorted(cs, probes)`, so with
    `cs` a mask's `prefix_count` and probe k the lane of its k-th set
    bit, and `cap` for a probe past the count (callers clamp).

    k-ary: the ends of blocks of `row` lanes, and of blocks of those,
    until at most `top` are left (a count of at most `top` lanes is its
    own top level). The top level is a broadcast compare and a count;
    each level under it one gather of a row [K, row] and a
    compare-count along it: no loop on any backend. A level `row` does
    not divide is padded with the type's maximum, which no probe reads
    under."""
    probes = probes.astype(jnp.int32)
    _noted_select(site, "rows")
    levels, lens = [cs], [cs.shape[0]]
    while lens[-1] > top:
        a, n = levels[-1], lens[-1]
        nb = -(-n // row)
        if nb * row != n:
            a = jnp.concatenate(
                [a, jnp.full(nb * row - n, _I32_MAX, dtype=jnp.int32)])
        levels[-1] = a.reshape(nb, row)
        levels.append(levels[-1][:, row - 1])
        lens.append(nb)
    p = probes[:, None]
    c = jnp.sum(levels[-1][None, :] < p, axis=1, dtype=jnp.int32)
    for rows, n in zip(levels[-2::-1], lens[:0:-1]):
        # c blocks end under the probe: the next holds it, or the
        # last when all do (its padding counts for nothing)
        j = jnp.minimum(c, n - 1)
        r = rows.at[j].get(mode="promise_in_bounds")
        c = j * row + jnp.sum(r < p, axis=1, dtype=jnp.int32)
    return c


def prefix_select(flags, probes, site=None):
    """-> (positions, count): `prefix_search` over the flags' own
    `prefix_count`, and how many are set (int64, as the results carry
    it)."""
    cs = prefix_count(flags)
    return (prefix_search(cs, probes, site),
            cs[flags.shape[0] - 1].astype(jnp.int64))


def next_flag(flags, at, cap, site=None):
    """The first set lane after lane `at[k]`, `cap` where none is: a
    reverse running minimum over the lanes' own indexes and one gather,
    where the count of `flags` was searched for its next step."""
    _noted_select(site, "scan")
    idx = jnp.arange(cap, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(flags, idx, cap), reverse=True)
    a1 = at + 1
    return jnp.where(a1 < cap, nxt[jnp.minimum(a1, cap - 1)], cap)


def runs_agg_core(keys, key_nulls, mask, ctx, aggs, cap, bucket):
    """Contiguous-run partial aggregation: every maximal run of equal
    group keys becomes one partial group, extracted with cumulative
    sums and gathers at the k-th run's first valid row (`prefix_select`)
    and its end (`next_flag`) — no sort, no scatter, no search loop.

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
    run_start = jax.lax.cummax(jnp.where(change, idx, -1))
    mi = mask.astype(jnp.int64)
    mask_cs = jnp.cumsum(mi)
    mask_before_run = (mask_cs - mi)[run_start]
    vstart = mask & (mask_cs == mask_before_run + 1)      # first valid row
    pos, ngroups = prefix_select(vstart, jnp.arange(1, bucket + 1),
                                 "runs_pos")
    posc = jnp.minimum(pos, cap - 1)
    rs = run_start[posc]                                  # run start
    # the run's end is where the next one starts: read off a scan,
    # nothing searched
    re = next_flag(change, posc, cap, "runs_end") - 1

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
            fpos = jnp.minimum(prefix_select(ok, ford, "first_row")[0],
                               cap - 1)
            states.append([d[fpos], cnt])
        else:
            raise NotImplementedError(a.name)
    return {"ngroups": ngroups, "keys": out_keys,
            "key_nulls": out_key_nulls, "states": states}


def _group_keys(ctx, group_items, cap):
    """-> (keys, key_nulls): the group items as int64 vectors (NULL ->
    0) and their null masks, for the runs and sort lowerings."""
    keys, key_nulls = [], []
    for g in group_items:
        d, nl, _sd = eval_expr(ctx, g)
        if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
            d = jnp.full(cap, d)
        d = d.astype(jnp.int64) if d.dtype != jnp.int64 else d
        nm = materialize_nulls(ctx, nl)
        keys.append(jnp.where(nm, 0, d))
        key_nulls.append(nm)
    return keys, key_nulls


def runs_agg_body(ctx, mask, group_items, aggs, cap, group_bucket):
    """sort_agg_body's TPU lowering without the sort: group keys are
    evaluated, contiguous equal-key runs become partial groups
    (runs_agg_core). Same output contract as sort_agg_body, except
    groups appear in first-occurrence order (downstream merge is
    order-insensitive) and unclustered duplicate keys yield multiple
    partials for the merge to combine."""
    if not group_items:
        r = _dense_agg_states_reduce(ctx, mask, aggs, cap)
        return {"ngroups": jnp.asarray(1, dtype=jnp.int64), "keys": [],
                "key_nulls": [], "states": r["states"]}
    keys, key_nulls = _group_keys(ctx, group_items, cap)
    return runs_agg_core(keys, key_nulls, mask, ctx, aggs, cap,
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


def compact_dense(dag, res, sizes, key_dicts, state_dicts):
    """Compact the dense slot table (host side; <= DENSE_MAX slots)."""
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
    impl = impl or policy()
    if impl == "runs":
        return runs_agg_body(ctx, mask, group_items, aggs, cap,
                             group_bucket)
    keys, key_nulls = _group_keys(ctx, group_items, cap)

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
        elif a.name in ("min", "max"):
            sent, _comb = _minmax_sentinel(a.name, dv.dtype)
            seg_op = jax.ops.segment_min if a.name == "min" \
                else jax.ops.segment_max
            s = seg_op(jnp.where(row_ok, dv, sent), seg, num_segments=segN,
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

def host_partial_agg(ctx, dag, valid, shared_dicts=None):
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
        elif a.name in ("min", "max"):
            red, sign = (np.minimum, 1) if a.name == "min" \
                else (np.maximum, -1)
            sent = sign * (np.inf if dv.dtype.kind == "f" else _I64_MAX)
            if starts is not None:
                s = red.reduceat(
                    np.where(ok, dv, np.asarray(sent, dtype=dv.dtype)),
                    starts)
            else:
                s = np.full(ngroups, sent, dtype=dv.dtype)
                red.at(s, inverse, np.where(ok, dv, sent))
            states.append([s, cnt])
        else:
            raise NotImplementedError(a.name)
    kd, sd = capture_agg_dicts(dag, ctx.cols)
    for gi, sd2 in key_dict_override.items():
        kd[gi] = sd2
    return PartialAggResult(ngroups=ngroups, keys=out_keys,
                            key_nulls=out_key_nulls, states=states,
                            key_dicts=kd, state_dicts=sd)
