"""ANALYZE TABLE: column statistics for the planner (reference
pkg/statistics — histograms, CM-sketch, TopN: row count, NDV, null
count, min/max, equal-depth histogram, exact TopN values, count-min
sketch for the long tail; built vectorized from numpy)."""
from __future__ import annotations

import hashlib

import numpy as np


_TOPN = 20


class CMSketch:
    """Count-min sketch (reference pkg/statistics/cmsketch.go). Built
    from the exact (unique value, count) pairs ANALYZE already computes;
    queried with the min-over-rows estimate for equality selectivity of
    values outside the TopN."""
    DEPTH = 4
    WIDTH = 2048

    def __init__(self):
        self.table = np.zeros((self.DEPTH, self.WIDTH), dtype=np.int64)
        self.total = 0

    @classmethod
    def _rows(cls, key: str):
        d = hashlib.blake2b(key.encode("utf-8", "replace"),
                            digest_size=16).digest()
        h1 = int.from_bytes(d[:8], "little")
        h2 = int.from_bytes(d[8:], "little") | 1
        return [(h1 + i * h2) % cls.WIDTH for i in range(cls.DEPTH)]

    def insert(self, key: str, count: int):
        for i, j in enumerate(self._rows(key)):
            self.table[i, j] += count
        self.total += count

    def query(self, key: str) -> int:
        return int(min(self.table[i, j]
                       for i, j in enumerate(self._rows(key))))


class FMSketch:
    """Flajolet-Martin distinct-count sketch (reference
    pkg/statistics/fmsketch.go): hash each value, keep those whose hash
    is divisible by 2^k for adaptively-growing k; NDV ~= |kept| * 2^k.
    Mergeable across samples/partitions (global partition stats)."""

    MAX_SIZE = 10000

    def __init__(self):
        self.mask = np.uint64(0)
        self.hashset: set = set()

    def insert_hashes(self, hashes: np.ndarray):
        """Add a column's hashes: the mask grows until the distinct
        survivors fit. All of it on arrays: the set is built once, from
        at most MAX_SIZE values. (Built a mask bit at a time from Python
        ints it held the interpreter lock for seconds a column of a
        multi-million-row table, on whichever thread analysed — the
        auto-analyze tick stalled every connection: PERF.md, PR 27.)"""
        h = hashes.astype(np.uint64)
        mask = self.mask
        h = np.unique(h[(h & mask) == 0])
        if self.hashset:
            h = np.union1d(h, np.fromiter(self.hashset, dtype=np.uint64,
                                          count=len(self.hashset)))
        while len(h) > self.MAX_SIZE:
            mask = np.uint64((int(mask) << 1) | 1)
            h = h[(h & mask) == 0]
        self.mask = mask
        self.hashset = set(h.tolist())

    def merge(self, other: "FMSketch"):
        self.mask = max(self.mask, other.mask, key=int)
        self.hashset = {v for v in self.hashset
                        if v & int(self.mask) == 0}
        self.hashset.update(v for v in other.hashset
                            if v & int(self.mask) == 0)
        while len(self.hashset) > self.MAX_SIZE:
            self.mask = np.uint64((int(self.mask) << 1) | 1)
            self.hashset = {v for v in self.hashset
                            if v & int(self.mask) == 0}

    def ndv(self) -> int:
        return len(self.hashset) * (int(self.mask) + 1)


def _hash_values(arr: np.ndarray) -> np.ndarray:
    """Cheap vectorized 64-bit mix for the FM sketch."""
    h = arr.astype(np.uint64, copy=True)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xC4CEB9FE1A85EC53)
    h ^= h >> np.uint64(33)
    return h


# ANALYZE samples above this row count (reference row_sampler.go
# bernoulli sampling; exact statistics below it)
SAMPLE_THRESHOLD = 1 << 20
SAMPLE_ROWS = 1 << 17


class ColumnStats:
    __slots__ = ("ndv", "null_count", "min_val", "max_val", "histogram",
                 "topn", "cmsketch", "fmsketch")

    def __init__(self, ndv=0, null_count=0, min_val=None, max_val=None,
                 histogram=None):
        self.ndv = ndv
        self.null_count = null_count
        self.min_val = min_val
        self.max_val = max_val
        self.histogram = histogram   # (bucket_bounds, counts)
        self.topn = {}               # str(value) -> exact count
        self.cmsketch = None         # CMSketch over non-TopN values
        self.fmsketch = None         # FMSketch for NDV merging

    def eq_count(self, key: str):
        """Estimated row count for `col = value`; None if unknown."""
        cnt = self.topn.get(key)
        if cnt is not None:
            return cnt
        if self.cmsketch is not None:
            return self.cmsketch.query(key)
        return None


class TableStats:
    __slots__ = ("row_count", "columns", "version")

    def __init__(self, row_count=0):
        self.row_count = row_count
        self.columns: dict[str, ColumnStats] = {}
        self.version = 0


def analyze_tables(sess, table_names):
    ischema = sess.domain.infoschema()
    for tn in table_names:
        db = tn.db or sess.vars.current_db
        tbl = ischema.table_by_name(db, tn.name)
        analyze_one(sess.domain, tbl)


def analyze_one(domain, tbl):
    """Build TableStats for one table (partitioned tables analyze each
    partition and MERGE into global stats — reference
    statistics/handle/globalstats)."""
    from ..storage.partition import partition_table_info
    if tbl.partitions:
        parts = []
        for p in tbl.partitions["parts"]:
            pinfo = partition_table_info(tbl, p["pid"])
            ctab = domain.columnar.tables.get(pinfo.id)
            parts.append(_analyze_ctab(pinfo, ctab))
        ts = _merge_table_stats(tbl, parts)
    else:
        ctab = domain.columnar.tables.get(tbl.id)
        ts = _analyze_ctab(tbl, ctab)
    ts.version = domain.storage.current_ts()
    domain.stats[tbl.id] = ts
    return ts


def _analyze_ctab(tbl, ctab):
    rng = np.random.RandomState(0xA11)
    ts = TableStats(row_count=0 if ctab is None else ctab.live_count())
    if ctab is None or not ctab.n:
        return ts
    valid = ctab.valid_at()
    vidx = np.nonzero(valid)[0]
    sampled = len(vidx) > SAMPLE_THRESHOLD
    if sampled:
        # bernoulli row sample (reference row_sampler.go): statistics
        # scale by the inverse sampling rate; NDV comes from an FM
        # sketch over the FULL column (vectorized hash, no sort)
        pick = rng.choice(len(vidx), SAMPLE_ROWS, replace=False)
        sidx = vidx[np.sort(pick)]
        rate = len(vidx) / SAMPLE_ROWS
    else:
        sidx = vidx
        rate = 1.0
    for ci in tbl.public_columns():
        full = ctab.data[ci.id][:ctab.n]
        data = full[sidx]
        nulls = ctab.nulls[ci.id][:ctab.n][sidx]
        nn = data[~nulls]
        cs = ColumnStats(null_count=int(round(nulls.sum() * rate)))
        if len(nn):
            uniq, counts = np.unique(nn, return_counts=True)
            if sampled:
                fm = FMSketch()
                fv = full[vidx]
                fm.insert_hashes(_hash_values(
                    fv.view(np.int64) if fv.dtype.kind == "f" else fv))
                cs.ndv = min(fm.ndv(), ts.row_count)
                cs.fmsketch = fm
                counts = np.round(counts * rate).astype(np.int64)
            else:
                cs.ndv = len(uniq)
                fm = FMSketch()
                fm.insert_hashes(_hash_values(
                    nn.view(np.int64) if nn.dtype.kind == "f" else nn))
                cs.fmsketch = fm
            cs.min_val = uniq[0]
            cs.max_val = uniq[-1]
            # exact TopN + CM-sketch over the remainder; string
            # columns are dict codes here — decode so sketch keys
            # match query-time constants
            if len(uniq) <= 200_000:
                sd = ctab.dicts.get(ci.id)
                keys = sd.decode(uniq.astype(np.int64)) \
                    if sd is not None and uniq.dtype.kind in "iu" \
                    else uniq
                order = np.argsort(counts)[::-1]
                top = order[:_TOPN]
                cs.topn = {str(keys[i]): int(counts[i])
                           for i in top}
                rest = order[_TOPN:]
                if len(rest):
                    sk = CMSketch()
                    for i in rest:
                        sk.insert(str(keys[i]), int(counts[i]))
                    cs.cmsketch = sk
            if nn.dtype.kind in "if" and len(nn) > 1:
                qs = np.linspace(0, 1, min(65, max(len(uniq), 2)))
                bounds = np.quantile(nn, qs)
                counts, _ = np.histogram(nn, bounds)
                cs.histogram = (bounds, counts)
        ts.columns[ci.name] = cs
    return ts


def _merge_table_stats(tbl, parts):
    """Global partition stats: row counts sum; NDV merges through the
    FM sketches; TopN/min/max combine."""
    ts = TableStats(row_count=sum(p.row_count for p in parts))
    for ci in tbl.public_columns():
        cs = ColumnStats()
        fm = FMSketch()
        any_fm = False
        for p in parts:
            pc = p.columns.get(ci.name)
            if pc is None:
                continue
            cs.null_count += pc.null_count
            if getattr(pc, "fmsketch", None) is not None:
                fm.merge(pc.fmsketch)
                any_fm = True
            else:
                cs.ndv += pc.ndv       # no sketch: upper-bound sum
            if pc.min_val is not None and (cs.min_val is None or
                                           pc.min_val < cs.min_val):
                cs.min_val = pc.min_val
            if pc.max_val is not None and (cs.max_val is None or
                                           pc.max_val > cs.max_val):
                cs.max_val = pc.max_val
            for k, v in pc.topn.items():
                cs.topn[k] = cs.topn.get(k, 0) + v
        if any_fm:
            cs.ndv = min(max(fm.ndv(), cs.ndv), max(ts.row_count, 1))
            cs.fmsketch = fm
        if cs.topn:
            cs.topn = dict(sorted(cs.topn.items(),
                                  key=lambda kv: -kv[1])[:_TOPN])
        ts.columns[ci.name] = cs
    return ts
