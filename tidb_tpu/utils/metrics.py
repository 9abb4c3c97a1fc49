"""Unified metrics registry (reference pkg/metrics: the Prometheus
instrument registry TiDB's operability rests on, plus Top SQL's
per-digest resource attribution).

Three typed instruments — Counter, Gauge, Histogram (exponential
buckets) — with label support, lock-cheap recording (one short-held
lock per labeled child; the hot path is a dict hit + one add), and
explicit reset/snapshot so tests never depend on execution order.
`REGISTRY.expose()` renders Prometheus text exposition format 0.0.4
(`# HELP`/`# TYPE`, escaped labels, `_bucket`/`_sum`/`_count`);
`parse_text()` is the strict parser the smoke harness checks that
output with, including the histogram invariants.

The registry is process-global, like the Prometheus default registry:
module-level code (device_guard, copr) records without threading a
handle through every call. Per-store state stays on the Domain — the
legacy `domain.metrics` flat dict (kept as a compat mirror: every
`inc_metric` also bumps an unlabeled compat counter here) and the
`TopSQL` ring that folds each statement's phase snapshot
(utils/phase.py: device/compile/host/fetch time, kernel builds, upload
bytes) into a bounded per-digest aggregate — the table behind
`information_schema.tidb_top_sql`, i.e. the answer to "which statement
digest is burning the TPU".

Test isolation: `reset_all()` (wired as an autouse fixture in
tests/conftest.py) zeroes the registry and every live Domain's metric
dict + Top SQL ring, so assertions on absolute values are never
order-dependent.
"""
from __future__ import annotations

import bisect
import math
import re
import time
import weakref
from . import lockrank


# ---- naming ----------------------------------------------------------

_NAME_OK = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_NAME_BAD_CHARS = re.compile(r"[^a-zA-Z0-9_:]")
_LABEL_OK = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")


def sanitize_name(name: str) -> str:
    """Coerce an arbitrary string into the Prometheus metric-name
    charset `[a-zA-Z_:][a-zA-Z0-9_:]*` (invalid chars -> `_`, leading
    digit prefixed) so raw dict keys can never produce an unscrapable
    page."""
    name = _NAME_BAD_CHARS.sub("_", str(name))
    if not name or not re.match(r"[a-zA-Z_:]", name[0]):
        name = "_" + name
    return name


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label_value(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def format_value(v) -> str:
    """Prometheus sample value: integral floats render as ints (stable
    for exact-count assertions), specials as +Inf/-Inf/NaN."""
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, float):
        if math.isnan(v):
            return "NaN"
        if math.isinf(v):
            return "+Inf" if v > 0 else "-Inf"
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v)
    return str(v)


def format_le(b: float) -> str:
    if math.isinf(b):
        return "+Inf"
    return f"{b:.12g}"


def exponential_buckets(start: float, factor: float, count: int) -> list:
    """`count` upper bounds growing geometrically from `start`
    (reference prometheus.ExponentialBuckets)."""
    if start <= 0 or factor <= 1 or count < 1:
        raise ValueError("exponential_buckets(start>0, factor>1, count>=1)")
    return [start * (factor ** i) for i in range(count)]


# 0.25ms .. ~131s in x2 steps: covers a point-get on CPU through a
# cold (compiling) full-table TPC-H aggregate.
DEFAULT_BUCKETS = exponential_buckets(0.00025, 2.0, 20)


# ---- instruments -----------------------------------------------------

class _Child:
    """One (instrument, labelset) time series. Recording holds the
    child's own lock for one add — scrapes (ThreadingHTTPServer
    thread) and recording sessions never tear each other's state."""

    __slots__ = ("_reg", "_mu")

    def __init__(self, reg):
        self._reg = reg
        self._mu = lockrank.ranked_lock("metrics.child")


class _CounterChild(_Child):
    __slots__ = ("value",)

    def __init__(self, reg):
        super().__init__(reg)
        self.value = 0

    def inc(self, v=1):
        if not self._reg.enabled:
            return
        if v < 0:
            raise ValueError("counters only go up")
        with self._mu:
            self.value += v


class _GaugeChild(_Child):
    __slots__ = ("value",)

    def __init__(self, reg):
        super().__init__(reg)
        self.value = 0

    def set(self, v):
        if self._reg.enabled:
            with self._mu:
                self.value = v

    def inc(self, v=1):
        if self._reg.enabled:
            with self._mu:
                self.value += v

    def dec(self, v=1):
        if self._reg.enabled:
            with self._mu:
                self.value -= v


class _HistogramChild(_Child):
    __slots__ = ("buckets", "counts", "sum", "count")

    def __init__(self, reg, buckets):
        super().__init__(reg)
        self.buckets = buckets            # ascending upper bounds, no +Inf
        self.counts = [0] * (len(buckets) + 1)   # last slot = +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, v):
        if not self._reg.enabled:
            return
        v = float(v)
        i = bisect.bisect_left(self.buckets, v)
        with self._mu:
            self.counts[i] += 1
            self.sum += v
            self.count += 1

    def read(self):
        """Consistent (counts, sum, count) triple: a scrape racing
        observe() must never render _count != +Inf bucket — the strict
        parser treats that as a format violation."""
        with self._mu:
            return list(self.counts), self.sum, self.count


class Instrument:
    kind = "untyped"

    def __init__(self, registry, name, help_text, labelnames=()):
        if not _NAME_OK.match(name):
            raise ValueError(f"bad metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_OK.match(ln) or ln.startswith("__"):
                raise ValueError(f"bad label name {ln!r}")
        self.registry = registry
        self.name = name
        self.help = help_text
        self.labelnames = tuple(labelnames)
        self._children: dict = {}
        self._mu = lockrank.ranked_lock("metrics.instrument")
        self._compat = False      # compat mirrors hide from metrics_summary

    def _new_child(self):
        raise NotImplementedError

    def labels(self, *values):
        """The child time series for one labelset; created on first
        use. Hot path after creation is a plain dict hit."""
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: got {len(values)} label values, "
                f"want {len(self.labelnames)}")
        key = tuple(str(v) for v in values)
        child = self._children.get(key)
        if child is None:
            with self._mu:
                child = self._children.get(key)
                if child is None:
                    child = self._new_child()
                    self._children[key] = child
        return child

    def _default(self):
        return self.labels()

    def reset(self):
        with self._mu:
            self._children.clear()

    def child_items(self):
        with self._mu:                   # snapshot: labels() may insert
            items = list(self._children.items())
        return sorted(items)

    def sample_rows(self):
        """-> (sample_name, labels_dict, value) rows for every child —
        the single rendering of this instrument's series; histograms
        expand to cumulative _bucket/_sum/_count. Both expose() and
        the SQL surface (metrics_summary) consume this."""
        for key, child in self.child_items():
            labels = dict(zip(self.labelnames, key))
            if self.kind == "histogram":
                counts, total, count = child.read()
                acc = 0
                for ub, c in zip(self.buckets + [math.inf], counts):
                    acc += c
                    bl = dict(labels)
                    bl["le"] = format_le(ub)
                    yield (self.name + "_bucket", bl, acc)
                yield (self.name + "_sum", labels, total)
                yield (self.name + "_count", labels, count)
            else:
                yield (self.name, labels, child.value)

    # unlabeled conveniences --------------------------------------------
    def inc(self, v=1):
        self._default().inc(v)

    def observe(self, v):
        self._default().observe(v)

    def set(self, v):
        self._default().set(v)

    def dec(self, v=1):
        self._default().dec(v)


class Counter(Instrument):
    kind = "counter"

    def _new_child(self):
        return _CounterChild(self.registry)


class Gauge(Instrument):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild(self.registry)


class Histogram(Instrument):
    kind = "histogram"

    def __init__(self, registry, name, help_text, labelnames=(),
                 buckets=None):
        super().__init__(registry, name, help_text, labelnames)
        b = sorted(float(x) for x in (buckets or DEFAULT_BUCKETS))
        if b and math.isinf(b[-1]):
            b = b[:-1]                    # +Inf slot is implicit
        self.buckets = b

    def _new_child(self):
        return _HistogramChild(self.registry, self.buckets)


class Registry:
    """Instrument registry. get-or-create semantics: re-declaring the
    same (name, kind) returns the existing instrument, a kind clash
    raises — one name, one type, like Prometheus."""

    def __init__(self):
        self._instruments: dict = {}
        self._mu = lockrank.ranked_lock("metrics.registry")
        self.enabled = True

    def _get_or_create(self, cls, name, help_text, labelnames, **kw):
        with self._mu:
            inst = self._instruments.get(name)
            if inst is not None:
                if not isinstance(inst, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{inst.kind}, not {cls.kind}")
                return inst
            inst = cls(self, name, help_text, labelnames, **kw)
            self._instruments[name] = inst
            return inst

    def counter(self, name, help_text="", labelnames=()) -> Counter:
        return self._get_or_create(Counter, name, help_text, labelnames)

    def gauge(self, name, help_text="", labelnames=()) -> Gauge:
        return self._get_or_create(Gauge, name, help_text, labelnames)

    def histogram(self, name, help_text="", labelnames=(),
                  buckets=None) -> Histogram:
        return self._get_or_create(Histogram, name, help_text, labelnames,
                                   buckets=buckets)

    def instruments(self) -> list:
        with self._mu:
            return sorted(self._instruments.values(),
                          key=lambda i: i.name)

    def reset(self):
        """Zero every time series (instruments stay registered)."""
        for inst in self.instruments():
            inst.reset()

    # ---- read side ----------------------------------------------------
    def samples(self, include_compat=True):
        """-> iterator of (name, labels_dict, value) over scalar samples;
        histograms yield _bucket/_sum/_count rows (le included)."""
        for inst in self.instruments():
            if inst._compat and not include_compat:
                continue
            yield from inst.sample_rows()

    def snapshot(self) -> dict:
        """{rendered sample name: value} — the test-friendly view."""
        out = {}
        for name, labels, value in self.samples():
            out[_render_sample_name(name, labels)] = value
        return out

    def expose(self) -> str:
        """Prometheus text exposition format 0.0.4: sample_rows()
        grouped under # HELP/# TYPE headers."""
        lines = []
        for inst in self.instruments():
            rows = list(inst.sample_rows())
            if not rows:
                continue
            lines.append(f"# HELP {inst.name} "
                         f"{_escape_help(inst.help or inst.name)}")
            lines.append(f"# TYPE {inst.name} {inst.kind}")
            for name, labels, value in rows:
                lines.append(f"{_render_sample_name(name, labels)}"
                             f" {format_value(value)}")
        return "\n".join(lines) + "\n"


def _render_sample_name(name, labels) -> str:
    if not labels:
        return name
    pairs = ",".join(f'{k}="{_escape_label_value(str(v))}"'
                     for k, v in labels.items())
    return f"{name}{{{pairs}}}"


def render_labels(labels: dict) -> str:
    """`{k="v",...}` body without braces, for SQL surfacing."""
    return ",".join(f'{k}="{_escape_label_value(str(v))}"'
                    for k, v in sorted(labels.items()))


# ---- strict exposition parser (smoke harness) ------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(.*)\})?"
    r"\s+(-?(?:[0-9]*\.?[0-9]+(?:[eE][+-]?[0-9]+)?)|[+-]?Inf|NaN)"
    r"(?:\s+(-?[0-9]+))?$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_labelset(body: str, errors, lineno):
    """Parse `k="v",k2="v2"` strictly: every byte must be consumed by
    label pairs + separators."""
    labels = {}
    pos = 0
    body = body.strip()
    if not body:
        return labels
    while pos < len(body):
        m = _LABEL_RE.match(body, pos)
        if m is None:
            errors.append(f"line {lineno}: malformed label at {body[pos:]!r}")
            return labels
        k = m.group(1)
        if k in labels:
            errors.append(f"line {lineno}: duplicate label {k!r}")
        v = m.group(2)
        v = v.replace("\\\\", "\x00").replace('\\"', '"') \
            .replace("\\n", "\n").replace("\x00", "\\")
        labels[k] = v
        pos = m.end()
        if pos < len(body):
            if body[pos] != ",":
                errors.append(f"line {lineno}: expected ',' at "
                              f"{body[pos:]!r}")
                return labels
            pos += 1
    return labels


def parse_text(text: str):
    """Strict Prometheus text-format parser.

    -> (families, errors). families: base name -> {"type", "help",
    "samples": [(sample_name, labels, value)]}. errors is a list of
    human-readable violations: malformed lines, samples without a
    preceding # TYPE, duplicate series, bad names, and the histogram
    invariants (bucket monotonicity, `_count` == +Inf bucket,
    `_sum` >= 0)."""
    families: dict = {}
    errors: list = []
    seen_series = set()
    for lineno, raw in enumerate(text.split("\n"), start=1):
        line = raw.rstrip("\r")
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] == "TYPE":
                name, mtype = parts[2], (parts[3] if len(parts) > 3 else "")
                if not _NAME_OK.match(name):
                    errors.append(f"line {lineno}: bad TYPE name {name!r}")
                if mtype not in ("counter", "gauge", "histogram",
                                 "summary", "untyped"):
                    errors.append(f"line {lineno}: bad TYPE {mtype!r}")
                fam = families.setdefault(
                    name, {"type": None, "help": None, "samples": []})
                if fam["type"] is not None:
                    errors.append(f"line {lineno}: duplicate TYPE for "
                                  f"{name}")
                fam["type"] = mtype
            elif len(parts) >= 3 and parts[1] == "HELP":
                fam = families.setdefault(
                    parts[2], {"type": None, "help": None, "samples": []})
                fam["help"] = parts[3] if len(parts) > 3 else ""
            # other comments are legal and ignored
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {lineno}: malformed sample {line!r}")
            continue
        name, labelbody, valstr = m.group(1), m.group(2), m.group(3)
        labels = _parse_labelset(labelbody or "", errors, lineno)
        try:
            value = float(valstr.replace("Inf", "inf"))
        except ValueError:
            errors.append(f"line {lineno}: bad value {valstr!r}")
            continue
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            b = name[:-len(suffix)] if name.endswith(suffix) else None
            if b and families.get(b, {}).get("type") == "histogram":
                base = b
                break
        fam = families.get(base)
        if fam is None or fam["type"] is None:
            errors.append(f"line {lineno}: sample {name} has no "
                          "preceding # TYPE")
            fam = families.setdefault(
                base, {"type": None, "help": None, "samples": []})
        series_key = (name, tuple(sorted(labels.items())))
        if series_key in seen_series:
            errors.append(f"line {lineno}: duplicate series {name}"
                          f"{sorted(labels.items())}")
        seen_series.add(series_key)
        fam["samples"].append((name, labels, value))
    _check_histograms(families, errors)
    return families, errors


def _check_histograms(families, errors):
    for base, fam in families.items():
        if fam["type"] != "histogram":
            continue
        series: dict = {}
        for name, labels, value in fam["samples"]:
            lk = tuple(sorted((k, v) for k, v in labels.items()
                              if k != "le"))
            s = series.setdefault(lk, {"buckets": [], "sum": None,
                                       "count": None})
            if name == base + "_bucket":
                if "le" not in labels:
                    errors.append(f"{base}: bucket sample missing le")
                    continue
                le = labels["le"]
                s["buckets"].append(
                    (math.inf if le == "+Inf" else float(le), value))
            elif name == base + "_sum":
                s["sum"] = value
            elif name == base + "_count":
                s["count"] = value
        for lk, s in series.items():
            bks = sorted(s["buckets"])
            if not bks or not math.isinf(bks[-1][0]):
                errors.append(f"{base}{dict(lk)}: no +Inf bucket")
                continue
            last = -1.0
            for ub, c in bks:
                if c < last:
                    errors.append(f"{base}{dict(lk)}: bucket counts "
                                  f"decrease at le={format_le(ub)}")
                last = c
            if s["count"] is None or s["count"] != bks[-1][1]:
                errors.append(f"{base}{dict(lk)}: _count "
                              f"{s['count']} != +Inf bucket {bks[-1][1]}")
            if s["sum"] is None or s["sum"] < 0:
                errors.append(f"{base}{dict(lk)}: _sum missing or < 0")


# ---- Top SQL ---------------------------------------------------------

def phase_device_ms(ph: dict) -> float:
    """Enqueue + compile time of a phase snapshot in ms (snap() already
    converts `*_s` keys to ms), NOT device time: the host's time inside
    kernel calls, which return before the device finishes. Device time
    is the profiler trace's. statements_summary and Top SQL's
    `sum_device_ms` both mean this number."""
    ph = ph or {}
    return ph.get("dispatch_s", 0.0) + ph.get("compile_s", 0.0)

class TopSQL:
    """Bounded per-digest resource aggregation (reference TopSQL's
    per-digest CPU attribution, at the TPU-engine altitude). Each
    finished statement folds its utils/phase snapshot — device dispatch
    ms, XLA compile ms, host-path ms, fetch/sync ms, kernel builds,
    upload/fetch bytes, device fallbacks — into the ring; at capacity
    the digest with the least attributed time is evicted, so the heavy
    hitters the table exists to expose always survive."""

    __test__ = False

    def __init__(self, capacity: int = 200):
        self.capacity = capacity
        self._by_digest: dict = {}
        self._mu = lockrank.ranked_lock("metrics.stmts")

    def record(self, digest, normalized, dur_ms, phases, ok=True,
               drift=None, route=None):
        """drift: optional (max_drift, mean_drift) q-error pair from the
        statement's plan-feedback fold — running max / running mean kept
        per digest so a planner regression is visible next to the time
        it cost. route: the replica-routing outcome ("replica-<rid>",
        "leader_fallback", "degraded_midstmt", ""/None = leader-only)
        folded per digest, so a digest's fallback rate sits next to its
        cost."""
        ph = phases or {}
        device_ms = phase_device_ms(ph)
        with self._mu:
            e = self._by_digest.get(digest)
            if e is None:
                if len(self._by_digest) >= self.capacity:
                    self._evict_locked()
                e = self._by_digest[digest] = {
                    "digest": digest, "normalized": normalized,
                    "exec_count": 0, "sum_ms": 0.0, "sum_device_ms": 0.0,
                    "sum_compile_ms": 0.0, "sum_host_ms": 0.0,
                    "sum_fetch_ms": 0.0, "sum_upload_ms": 0.0,
                    "kernel_builds": 0, "dispatches": 0,
                    "upload_bytes": 0, "fetch_bytes": 0,
                    "fallback_count": 0, "sum_errors": 0,
                    "delta_applies": 0, "delta_bytes": 0,
                    "ml_predicts": 0, "ml_rows": 0,
                    "max_drift": 0.0, "sum_drift": 0.0, "drift_execs": 0,
                    "replica_reads": 0, "leader_fallbacks": 0,
                    "degraded_midstmt": 0}
            e["exec_count"] += 1
            e["sum_ms"] += dur_ms
            e["sum_device_ms"] += device_ms
            e["sum_compile_ms"] += ph.get("compile_s", 0.0)
            e["sum_host_ms"] += ph.get("host_exec_s", 0.0)
            e["sum_fetch_ms"] += ph.get("fetch_s", 0.0) + \
                ph.get("sync_s", 0.0)
            e["sum_upload_ms"] += ph.get("upload_s", 0.0)
            e["kernel_builds"] += ph.get("kernel_builds", 0)
            e["dispatches"] += ph.get("dispatches", 0)
            e["upload_bytes"] += ph.get("upload_bytes", 0)
            e["fetch_bytes"] += ph.get("fetch_bytes", 0)
            e["fallback_count"] += ph.get("device_fallbacks", 0)
            # freshness cost attribution (incremental HTAP): which
            # digest's binds paid for delta folds, and how many bytes
            e["delta_applies"] += ph.get("delta_applies", 0)
            e["delta_bytes"] += ph.get("delta_bytes", 0)
            # in-SQL inference attribution: which digest's statements
            # ran model forwards, and over how many rows
            e["ml_predicts"] = e.get("ml_predicts", 0) + \
                ph.get("ml_predicts", 0)
            e["ml_rows"] = e.get("ml_rows", 0) + ph.get("ml_rows", 0)
            if drift is not None:
                mx, mean = drift
                if mx > e["max_drift"]:
                    e["max_drift"] = mx
                e["sum_drift"] += mean
                e["drift_execs"] += 1
            if route:
                if route.startswith("replica"):
                    e["replica_reads"] = e.get("replica_reads", 0) + 1
                elif route == "leader_fallback":
                    e["leader_fallbacks"] = \
                        e.get("leader_fallbacks", 0) + 1
                elif route == "degraded_midstmt":
                    e["degraded_midstmt"] = \
                        e.get("degraded_midstmt", 0) + 1
            if not ok:
                e["sum_errors"] += 1

    def _evict_locked(self):
        victim = min(self._by_digest.values(),
                     key=lambda e: (e["sum_device_ms"] + e["sum_host_ms"],
                                    e["sum_ms"]))
        del self._by_digest[victim["digest"]]

    def rows(self, limit: int = 100) -> list:
        with self._mu:
            entries = [dict(e) for e in self._by_digest.values()]
        entries.sort(key=lambda e: (-e["sum_device_ms"], -e["sum_ms"]))
        return entries[:limit]

    def clear(self):
        with self._mu:
            self._by_digest.clear()


# ---- domain integration ----------------------------------------------

_TRACKED_DOMAINS = weakref.WeakSet()
_COMPAT_COUNTERS: dict = {}
# WeakSet/compat-map mutation lock: domains register from whatever
# thread constructs them, compat counters materialize lazily on the
# first inc_metric of a name — both race with a concurrent scrape
_DOMAINS_MU = lockrank.ranked_lock("metrics.domains")


def track_domain(domain):
    with _DOMAINS_MU:
        _TRACKED_DOMAINS.add(domain)


def compat_counter(name: str):
    """Unlabeled mirror counter for legacy `domain.inc_metric` names —
    the shim that puts every pre-registry call site on the /metrics
    page (sanitized) without touching its flat-dict readers."""
    child = _COMPAT_COUNTERS.get(name)   # lockless fast path
    if child is None:
        with _DOMAINS_MU:
            child = _COMPAT_COUNTERS.get(name)
            if child is not None:
                return child
            base = "tidb_tpu_" + sanitize_name(name)
            with REGISTRY._mu:
                taken = base in REGISTRY._instruments
            if taken:
                # a typed instrument owns this name (e.g. a flat
                # 'connections' vs the connections Gauge): a kind/label
                # clash must park the legacy series, never crash the bump
                base += "_legacy"
            # tpulint: disable=metrics-hygiene — the compat shim's name
            # and HELP are dynamic BY DESIGN: it mirrors the bounded set
            # of legacy domain.inc_metric slugs (code constants, never
            # user data) onto the exposition page
            inst = REGISTRY.counter(
                base, f"legacy flat counter {name!r} (domain.inc_metric)")
            inst._compat = True
            child = _COMPAT_COUNTERS[name] = inst.labels()
    return child


def update_runtime_gauges(domain):
    """Point-in-time gauges sampled at collect time (scrape or SQL
    read), the pull-model analog of a collector callback."""
    live = 0
    in_txn = 0
    for ref in list(getattr(domain, "sessions", {}).values()):
        s = ref()
        if s is None:
            continue
        live += 1
        t = getattr(s, "_txn", None)
        if t is not None and not t.committed and not t.aborted:
            in_txn += 1
    CONNECTIONS.set(live)
    ACTIVE_TXNS.set(in_txn)
    start = getattr(domain, "_start_time", None)
    if start is not None:
        UPTIME.set(time.time() - start)
    root = getattr(domain, "mem_root", None)
    if root is not None:
        MEM_TRACKER_BYTES.labels("consumed").set(root.consumed)
        MEM_TRACKER_BYTES.labels("max_consumed").set(root.max_consumed)
    store = getattr(getattr(domain, "copr", None), "_dev_store", None)
    if store is not None:
        DEV_RESIDENT_BUDGET.set(store.budget)
    # the stage catalogue of programs that ran under a profiler session
    # is filled here, off every statement's path
    from . import kernel_stages
    kernel_stages.materialise()


def reset_all():
    """Test hook: zero the registry and every live Domain's flat metric
    dict + Top SQL ring (fixture in tests/conftest.py)."""
    REGISTRY.reset()
    with _DOMAINS_MU:
        _COMPAT_COUNTERS.clear()
        domains = list(_TRACKED_DOMAINS)
    for d in domains:
        try:
            d.metrics.clear()
            d.top_sql.clear()
            d.plan_feedback.clear()
        except Exception:               # noqa: BLE001
            pass


# ---- fused-decline reason slugs --------------------------------------

_DIM_PREFIX = re.compile(r"^dim [^:]*: ")
_PAREN = re.compile(r"\([^)]*\)")


def reason_code(msg: str) -> str:
    """Fold a free-text decline reason into a bounded label value:
    table names and parentheticals are template parameters, not
    cardinality."""
    s = _DIM_PREFIX.sub("", str(msg))
    s = _PAREN.sub("", s)
    s = re.sub(r"[0-9]+", "", s)
    s = re.sub(r"[^a-zA-Z]+", "_", s.lower()).strip("_")
    return s[:60] or "unknown"


# ---- the default registry and shared instruments ---------------------

REGISTRY = Registry()

QUERY_DURATION = REGISTRY.histogram(
    "tidb_tpu_query_duration_seconds",
    "Statement wall time by statement type (internal=1: system "
    "sessions — TTL, sysvar persistence; nested internal SQL is not "
    "observed at all)", ("stmt_type", "internal"))
QUERY_ERRORS = REGISTRY.counter(
    "tidb_tpu_query_error_total",
    "Failed statements by statement type", ("stmt_type", "internal"))
PLAN_CACHE = REGISTRY.counter(
    "tidb_tpu_plan_cache_total",
    "Plan-cache lookups by outcome (point fast-path templates + the "
    "instance plan cache): hit=planner skipped, miss=planned then "
    "cached, uncacheable=planned, not cacheable (plan-time data "
    "dependence or unsupported fast-path shape)", ("outcome",))
AST_CACHE = REGISTRY.counter(
    "tidb_tpu_ast_cache_total",
    "AST-cache lookups of statement texts by outcome (hit=parser "
    "skipped, miss=parsed then cached)", ("outcome",))
WAL_GROUP_COMMIT_SIZE = REGISTRY.histogram(
    "tidb_tpu_wal_group_commit_size",
    "Commit frames made durable per WAL group-commit sync (leader "
    "batch size; 1 = no concurrent committer joined the group)",
    buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512])
CARDINALITY_DRIFT = REGISTRY.histogram(
    "tidb_tpu_cardinality_drift",
    "Per-operator estimate-vs-actual q-error max(est/act, act/est) "
    "folded at statement end by plan operator class (always >= 1; "
    "1 = perfect estimate)", ("op",),
    buckets=[1, 1.5, 2, 4, 8, 16, 64, 256, 1024, 4096])
ADMISSION_WAIT_SECONDS = REGISTRY.histogram(
    "tidb_tpu_admission_wait_seconds",
    "Statement admission wait by resource group and workload class "
    "(olap=slot queue, ru=token-bucket throttle)",
    ("rgroup", "klass"))
CONNECTIONS = REGISTRY.gauge(
    "tidb_tpu_connections", "Live sessions (weakref-reachable)")
ACTIVE_TXNS = REGISTRY.gauge(
    "tidb_tpu_active_txns", "Sessions holding an open transaction")
UPTIME = REGISTRY.gauge(
    "tidb_tpu_uptime_seconds", "Seconds since the domain opened")

COPR_DISPATCH_SECONDS = REGISTRY.histogram(
    "tidb_tpu_copr_dispatch_seconds",
    "Coprocessor (sub)DAG execution latency by serving backend",
    ("backend",))
SPAN_SECONDS = REGISTRY.histogram(
    "tidb_tpu_span_seconds",
    "Inclusive duration of every utils/tracing span by span name "
    "(parse / plan / execute / dispatch / fetch / wire_write ...), "
    "sampled or not",
    ("span",))
MPP_EXCHANGE = REGISTRY.counter(
    "tidb_tpu_mpp_exchange_total",
    "MPP exchanges lowered to on-mesh collectives by exchange type "
    "(passthrough=psum/all_gather partial merge, broadcast=replicated "
    "build side, hash=all_to_all shuffle)", ("type",))
MPP_EXCHANGE_BYTES = REGISTRY.counter(
    "tidb_tpu_mpp_exchange_bytes_total",
    "Bytes moved across the mesh by exchange collectives by exchange "
    "type (aggregate over devices, not per-chip)", ("type",))
MESH_ROUTE = REGISTRY.counter(
    "tidb_tpu_mesh_route_total",
    "Device dispatches of statements that could see a mesh (a process "
    "with two or more devices), by where they ran (route: mesh | "
    "single_chip) and why (reason: ok, mpp_off, min_rows, "
    "delta_overlay, ineligible_<why>, degraded); does not move on one "
    "device", ("route", "reason"))


def mesh_routes() -> dict:
    """-> {(route, reason): dispatches} of tidb_tpu_mesh_route_total,
    the samples that have moved."""
    return {(lb["route"], lb["reason"]): int(v)
            for _name, lb, v in MESH_ROUTE.sample_rows() if v}


KERNEL_CACHE = REGISTRY.counter(
    "tidb_tpu_kernel_cache_total",
    "Compiled-kernel cache lookups by result", ("result",))
DEV_BUFFER_POOL = REGISTRY.counter(
    "tidb_tpu_device_buffer_pool_total",
    "Device buffer-pool (HBM-resident column) lookups by result",
    ("result",))
XLA_CACHE = REGISTRY.counter(
    "tidb_tpu_xla_cache_total",
    "Persistent XLA compilation-cache lookups by result", ("result",))
DEV_BUFFER_EVICTIONS = REGISTRY.counter(
    "tidb_tpu_device_buffer_evict_total",
    "Device-resident buffers dropped by cause", ("cause",))
DELTA_APPLY = REGISTRY.counter(
    "tidb_tpu_delta_apply_total",
    "Incremental delta maintenance of device-resident column buffers "
    "by outcome (applied=tail rows patched on device, advanced="
    "version-only advance for delete/update tombstones, compacted="
    "entry dropped after gc/bucket supersession, "
    "fell_back_full_upload=delta overflow or patch failure — next "
    "bind re-uploads the buffer whole)", ("outcome",))
DELTA_APPLY_BYTES = REGISTRY.counter(
    "tidb_tpu_delta_apply_bytes_total",
    "Real delta bytes folded into device-resident buffers (new tail "
    "rows only, excluding pad)")
DELTA_REUPLOAD_AVOIDED_BYTES = REGISTRY.counter(
    "tidb_tpu_delta_reupload_avoided_bytes_total",
    "Buffer bytes NOT re-uploaded because a delta patch advanced the "
    "entry in place (the O(table) invalidate-and-reupload this "
    "replaces)")
REPLICA_LAG_SECONDS = REGISTRY.gauge(
    "tidb_tpu_replica_freshness_lag_seconds",
    "Age of the analytic replica's resolved-ts read view (wallclock "
    "now minus the allocation time of the resolved floor)")
ANALYTIC_READS = REGISTRY.counter(
    "tidb_tpu_analytic_read_total",
    "Resolved-mode analytic read-view routing decisions (counted "
    "only when tidb_tpu_analytic_read_mode='resolved': resolved="
    "snapshot at the resolved-ts floor, staleness_fallback=floor "
    "older than the staleness bound so the leader path served, "
    "strict=FOR UPDATE kept strict; leader-mode statements and AS OF "
    "statements carry their own read view and are not counted)",
    ("outcome",))
REPLICA_ROUTE = REGISTRY.counter(
    "tidb_tpu_replica_route_total",
    "Read-replica router decisions (replica=served by a replica "
    "domain pinned at its applied watermark, leader_fallback=no "
    "replica within the freshness SLA / DDL barrier / own-write "
    "floor, degraded_midstmt=the chosen replica died mid-statement "
    "and the leader transparently retried)", ("outcome",))
REPLICA_STATE = REGISTRY.gauge(
    "tidb_tpu_replica_state",
    "Replica health state machine (0=provisioning 1=serving "
    "2=lagging 3=down)", ("replica",))
REPLICA_LAG = REGISTRY.gauge(
    "tidb_tpu_replica_lag_seconds",
    "Per-replica applied-watermark staleness (wallclock now minus "
    "the allocation time of the applied resolved-ts)", ("replica",))
DEV_RESIDENT_BYTES = REGISTRY.gauge(
    "tidb_tpu_device_resident_bytes",
    "Charged bytes live in the device-resident store by placement "
    "spec (local=single chip, sharded=1/ndev per device so charged "
    "once, replicated=full copy per device so charged x ndev)",
    ("spec",))
DEV_RESIDENT_BUDGET = REGISTRY.gauge(
    "tidb_tpu_device_resident_budget_bytes",
    "Byte budget of the serving domain's device-resident store: what "
    "resident buffers are charged against and LRU-evicted past; "
    "tidb_tpu_device_resident_bytes over this is how full the pool is")
FRAGMENT_ROUTING = REGISTRY.counter(
    "tidb_tpu_fragment_routing_total",
    "Copr fragment placement decisions by outcome", ("outcome",))
VECTOR_SEARCH = REGISTRY.counter(
    "tidb_tpu_vector_search_total",
    "Vector top-k searches by serving path (exact=single-dispatch "
    "brute-force kernel, ivf=ANN through the IVF index, "
    "host_fallback=degraded to the numpy twin — device failure or a "
    "dirty-transaction overlay)", ("path",))
ML_PREDICT = REGISTRY.counter(
    "tidb_tpu_ml_predict_total",
    "In-SQL model inference calls by outcome (device=standalone "
    "full-table forward kernel, host=numpy twin / host eval, "
    "fused=forward chain traced into a copr fragment program — "
    "counted once per compile, the per-dispatch cost rides the "
    "fragment's phase counters, host_fallback=device path degraded "
    "to the twin mid-statement)", ("outcome",))
ML_ROWS = REGISTRY.counter(
    "tidb_tpu_ml_rows_total",
    "Rows scored/embedded by in-SQL model inference (host-observable "
    "paths; fused in-fragment rows ride the fragment row counters)")
VECTOR_NPROBE_PARTITIONS = REGISTRY.counter(
    "tidb_tpu_vector_nprobe_partitions_total",
    "IVF partitions probed across ANN searches (sum of effective "
    "nprobe; rate / search rate = average probe width)")
VECTOR_INDEX_DELTA = REGISTRY.counter(
    "tidb_tpu_vector_index_delta_total",
    "IVF index maintenance by outcome (applied=appended rows "
    "assigned + folded into posting lists O(delta), advanced="
    "delete/update tombstones — visibility rides the MVCC mask, "
    "nothing to fold, rebuild=gc compaction rewrote row positions "
    "so postings rebuilt from the resident matrix; never fired by a "
    "write)", ("outcome",))
SPILLS = REGISTRY.counter(
    "tidb_tpu_spill_total",
    "Blocking-operator disk spills by operator (sort external sort, "
    "agg distinct grace partitioning, join grace hash partitioning; "
    "fired by the memory.Tracker action chain or the operator's "
    "half-quota threshold — the flat sort_spill_count/agg_spill_count/"
    "join_spill_count inc_metric counters stay as compat mirrors)",
    ("operator",))
DDL_JOBS = REGISTRY.counter(
    "tidb_tpu_ddl_job_total",
    "Durable online-DDL job state transitions by job type and state "
    "entered (queueing/running/cancelling/rollingback/synced/"
    "cancelled; owner/ddl_runner.py — synced and cancelled are the "
    "terminal outcomes, everything else is in-flight)",
    ("type", "state"))
DDL_BACKFILL = REGISTRY.gauge(
    "tidb_tpu_ddl_backfill_rows",
    "Reorg backfill progress of the currently running DDL job by stat "
    "(done=rows whose index entries committed, total=live rows at job "
    "start; done resumes from the durable checkpoint after a restart)",
    ("stat",))
BACKUP_TOTAL = REGISTRY.counter(
    "tidb_tpu_backup_total",
    "Backup/restore unit outcomes by phase (snapshot_table=one table's "
    "chunks + manifest checkpoint committed, snapshot_run=a whole "
    "BACKUP DATABASE statement, restore_table=one table imported and "
    "checkpointed, restore_run=a whole RESTORE job, log_flush=a log-"
    "backup sink resolved-ts flush) and outcome (ok/error/skipped — "
    "skipped = the table was already in the manifest done-list)",
    ("phase", "outcome"))
RESTORE_ROWS = REGISTRY.gauge(
    "tidb_tpu_restore_rows",
    "Progress of the currently running restore job by stat (imported="
    "rows bulk-loaded from snapshot chunks, replayed=rows applied from "
    "the log backup, total=imported+replayed; resumes from the durable "
    "job checkpoint after a restart)",
    ("stat",))
MEM_PRESSURE = REGISTRY.counter(
    "tidb_tpu_mem_pressure_total",
    "Memory-pressure protocol outcomes (evict=resident HBM entries "
    "shed before a resource_exhausted retry, evict_noop=pressure "
    "eviction found an empty pool, retry_ok=dispatch succeeded after "
    "a pressure eviction, degrade=resource_exhausted dispatch "
    "degraded to the host twin, spill_trigger=quota breach armed an "
    "operator spill, oom_log=breach recorded under "
    "tidb_tpu_oom_action=log, oom_cancel=statement cancelled with "
    "ER 8175, server_cancel=global controller cancelled the largest "
    "statement past tidb_tpu_server_memory_limit)", ("action",))
MEM_TRACKER_BYTES = REGISTRY.gauge(
    "tidb_tpu_mem_tracker_bytes",
    "Hierarchical memory-tracker accounting at the global root, "
    "sampled at collect time (consumed=bytes currently tracked, "
    "max_consumed=high-water mark since the domain opened)",
    ("stat",))
FUSED_DECLINE = REGISTRY.counter(
    "tidb_tpu_fused_decline_total",
    "Fused-pipeline declines by reason class", ("reason",))
DIM_FOLD = REGISTRY.counter(
    "tidb_tpu_dim_fold_total",
    "Fused-pipeline dimensions by fold outcome, a statement's bind: "
    "folded (resolved at its parent's width), mask_folded (a root's "
    "mask in its probe table), declined_<reason>; build / cache_hit: "
    "a root's folded tables built or found; packed (a root's payload "
    "composed into the word its key addresses), packed_spill (a second "
    "word): a root, a lowering's upload; word32: a word or a table of "
    "positions of it bound as int32, one 32-bit gather a fact lane",
    ("outcome",))
FUSED_DIM_PROBE = REGISTRY.counter(
    "tidb_tpu_fused_dim_probe_total",
    "Dimensions of fused statements by join type (inner, semi, anti, "
    "left) and by what resolves the dimension at fact width, one count "
    "a dimension a statement, at the bind that uploads the dimensions: "
    "folded (resolved into a parent's table or word: no probe of its "
    "own), search (a binary search over sorted keys, whatever the "
    "dimension), bucket (a key of several columns: the slots of a bucket "
    "on one of them and a compare), exists (a prefiltered semi table, "
    "one gather), matdim (a materialised aggregate dimension, one "
    "gather), direct (its own table of positions or word, one gather)",
    ("join", "mode"))
MATDIM = REGISTRY.counter(
    "tidb_tpu_matdim_total",
    "Materialised aggregate dimensions of fused statements (Q17's "
    "decorrelated avg, Q18's grouped IN-subquery, Q13's counted outer "
    "side) by outcome: hit (answered from the cache keyed on the "
    "subplan's fingerprint and its base tables' versions), build (the "
    "subplan ran)", ("outcome",))
DICT_FILTER = REGISTRY.counter(
    "tidb_tpu_dict_filter_total",
    "Python functions mapped over a string dictionary's values on the "
    "host (expression/vec.py _dict_table: LIKE, REGEXP, a string in "
    "boolean context) by outcome: hit (an unchanged dictionary answered "
    "from the table kept with it), build (values were evaluated: all of "
    "them, or a grown dictionary's new ones)", ("outcome",))
AGG_MERGE = REGISTRY.counter(
    "tidb_tpu_agg_merge_total",
    "Final merges of two or more live aggregation partials by what "
    "they grouped on: ident (the items the partials name as identifying "
    "the group, a proper subset), sorted_runs (one key that arrived in "
    "order: run boundaries, no sort), all_items (every group item)",
    ("path",))


def _moved(counter, label, since=None) -> dict:
    """-> {label value: count} of a counter's samples that have moved,
    since an earlier reading of this when one is given; keyed by a
    tuple of values where `label` is a tuple of labels."""
    since = since or {}
    key = (lambda lb: lb[label]) if isinstance(label, str) else \
        (lambda lb: tuple(lb[x] for x in label))
    return {k: n for k, n in (
        (key(lb), int(v) - since.get(key(lb), 0))
        for _name, lb, v in counter.sample_rows()) if n}


def agg_merges(since=None) -> dict:
    """-> {path: count} of `tidb_tpu_agg_merge_total`'s samples that
    have moved, since an earlier reading of this when one is given."""
    return _moved(AGG_MERGE, "path", since)


AGG_LOWERING = REGISTRY.counter(
    "tidb_tpu_agg_lowering_total",
    "Device runs of an aggregation program judged by the lowering "
    "(copr/agg_lowering.py), by whose kernel ran (site: fused | fused_mpp "
    "| dag), the lowering that ran (kind: dense, posdense, "
    "posruns, sort_<segment impl>, onehot: a learned slot table reduced "
    "by the int8 matmul, onehot_cmp: by a compare at its packed code, "
    "nothing searched) and the "
    "verdict: stands, or "
    "why the run was thrown away and run again (retry_early_compact, "
    "retry_compact, retry_pin_sorted, retry_pin_dense, "
    "retry_grow_bucket, "
    "retry_onehot_miss, retry_topn_unproven)",
    ("site", "kind", "verdict"))


PREFIX_SELECT = REGISTRY.counter(
    "tidb_tpu_prefix_select_total",
    "Inversions of a prefix count traced into a device program "
    "(agg_lowering.prefix_search / next_flag: where the k-th set lane of "
    "a mask is), one count a built program a call site, by site "
    "(late_compact, early_compact: the fused pipeline's compactions; "
    "runs_pos, runs_end, first_row: the runs lowering's first valid row "
    "of a run, the run's end, an aggregate's first row) and form (rows: "
    "k-ary over rows of block ends of an int32 count, a compare at "
    "every level; scan: read off a reverse running minimum, nothing "
    "searched)",
    ("site", "form"))


def prefix_selects(since=None) -> dict:
    """-> {(site, form): count} of `tidb_tpu_prefix_select_total`'s
    samples that have moved, since an earlier reading of this."""
    return _moved(PREFIX_SELECT, ("site", "form"), since)


SNAPSHOT_FACTS = REGISTRY.counter(
    "tidb_tpu_snapshot_facts_total",
    "Snapshots of a columnar table (storage/columnar.py snapshot / "
    "valid_at) by what answered them: hit (the version's kept "
    "visibility mask and null facts), build (the version's first "
    "reader made them), bypass_read_ts (a snapshot older than the "
    "table's newest timestamp: computed for it alone), bypass_overlay "
    "(rows other than the table's own n: computed for it alone)",
    ("outcome",))


def snapshot_facts(since=None) -> dict:
    """-> {outcome: count} of `tidb_tpu_snapshot_facts_total`'s samples
    that have moved, since an earlier reading of this when one is
    given."""
    return _moved(SNAPSHOT_FACTS, "outcome", since)


FUSED_PIPELINE = REGISTRY.counter(
    "tidb_tpu_fused_pipeline_total",
    "Fused-pipeline executions by outcome", ("outcome",))

DEVICE_RETRIES = REGISTRY.counter(
    "tidb_tpu_device_retry_total",
    "Supervised device dispatch retries", ("family", "error_class"))
DEVICE_FALLBACKS = REGISTRY.counter(
    "tidb_tpu_device_fallback_total",
    "Device dispatches degraded to the host twin",
    ("family", "error_class"))
DEVICE_DISPATCH_ERRORS = REGISTRY.counter(
    "tidb_tpu_device_dispatch_error_total",
    "Device dispatch attempt failures", ("family", "error_class"))
BREAKER_OPEN = REGISTRY.counter(
    "tidb_tpu_device_breaker_open_total",
    "Circuit-breaker trips by site family", ("family",))
BREAKER_SHORT_CIRCUIT = REGISTRY.counter(
    "tidb_tpu_device_breaker_short_circuit_total",
    "Dispatches short-circuited to host while a breaker was open",
    ("family",))

RPC_SECONDS = REGISTRY.histogram(
    "tidb_tpu_rpc_seconds",
    "Cluster worker RPC round-trip latency by op", ("op",))
RPC_RETRIES = REGISTRY.counter(
    "tidb_tpu_rpc_retry_total",
    "Cluster RPC transport retries by op", ("op",))
CLUSTER_RPC = REGISTRY.counter(
    "tidb_tpu_cluster_rpc_total",
    "Supervised cluster RPC calls by op and outcome "
    "(ok/transport_error/stale_epoch/app_error/breaker_open)",
    ("op", "outcome"))
CLUSTER_RPC_DEDUP = REGISTRY.counter(
    "tidb_tpu_cluster_rpc_dedup_total",
    "Retried cluster RPCs answered from the worker-side dedup window "
    "instead of re-executing", ("op",))
CLUSTER_HB_LAG = REGISTRY.gauge(
    "tidb_tpu_cluster_heartbeat_lag_seconds",
    "Seconds since the last successful heartbeat per worker slot",
    ("worker",))
CLUSTER_BREAKER_STATE = REGISTRY.gauge(
    "tidb_tpu_cluster_breaker_state",
    "Per-worker RPC circuit breaker state (0 closed, 1 open)",
    ("worker",))
CLUSTER_FAILOVERS = REGISTRY.counter(
    "tidb_tpu_cluster_failover_total",
    "Fenced failovers executed by the cluster supervisor")

LOCK_RESOLUTIONS = REGISTRY.counter(
    "tidb_tpu_lock_resolution_total",
    "Foreign-lock resolutions by the lock resolver, by outcome "
    "(committed/rolled_back/expired/no_lock/stale)", ("outcome",))
LOCK_WAITS = REGISTRY.counter(
    "tidb_tpu_lock_wait_total",
    "Lock-wait queue outcomes (acquired/resolved/timeout/deadlock/"
    "nowait)", ("outcome",))
DEADLOCKS = REGISTRY.counter(
    "tidb_tpu_deadlock_total",
    "Deadlock cycles detected by the wait-for graph")
LOCK_WAIT_SECONDS = REGISTRY.histogram(
    "tidb_tpu_lock_wait_seconds",
    "Time spent blocked on foreign locks before acquire/resolution")

LSM_FLUSH_SECONDS = REGISTRY.histogram(
    "tidb_tpu_lsm_flush_seconds",
    "WAL -> immutable-run flush latency",
    buckets=exponential_buckets(0.001, 2.0, 16))
LSM_COMPACTIONS = REGISTRY.counter(
    "tidb_tpu_lsm_compaction_total", "LSM run compactions")

CDC_RESOLVED_LAG_SECONDS = REGISTRY.histogram(
    "tidb_tpu_cdc_resolved_ts_lag_seconds",
    "Changefeed resolved-ts watermark age (wallclock now minus the "
    "allocation time of the resolved ts), sampled per worker poll",
    ("changefeed",),
    buckets=exponential_buckets(0.001, 2.0, 18))
CDC_SINK_ROWS = REGISTRY.counter(
    "tidb_tpu_cdc_sink_row_total",
    "Row events delivered to a changefeed sink", ("changefeed", "sink"))
CDC_SINK_TXNS = REGISTRY.counter(
    "tidb_tpu_cdc_sink_txn_total",
    "Whole transactions delivered to a changefeed sink",
    ("changefeed", "sink"))
CDC_WORKER_ERRORS = REGISTRY.counter(
    "tidb_tpu_cdc_worker_error_total",
    "Changefeed worker poll failures by error class",
    ("changefeed", "error_class"))
CDC_CHECKPOINT_TS = REGISTRY.gauge(
    "tidb_tpu_cdc_checkpoint_ts",
    "Changefeed checkpoint ts (persisted resume point)",
    ("changefeed",))
CDC_RESOLVED_TS = REGISTRY.gauge(
    "tidb_tpu_cdc_resolved_ts",
    "Changefeed resolved ts (emission watermark)", ("changefeed",))
