"""How a fact lane finds its dimension row: the probe table of one
dimension of the fused pipeline (copr/pipeline.py) and everything that
depends on its form (side by side with their costs on the chip:
docs/PERFORMANCE.md, "A probe table's forms"):

- `direct`: `table[key - lo]` is the row's position, `n` the miss;
- `bucket`: a key of several columns, row `k - lo` of ONE of them holds
  its slots' other keys, then their positions (`_bucket_table`);
- `sorted`: `keys` ascending and `table`, the position at each rank.

A folded root (copr/dimfold.py) swaps the positions for its folded ones
(`with_positions`) or uploads its composed words in their place
(`upload(pack=...)`). Which dimension folds under which, and what a
word's fields mean, stay dimfold's.
"""
from __future__ import annotations

import math

import numpy as np

from ..utils import jaxcfg  # noqa: F401
import jax.numpy as jnp

from ..chunk.device import shape_bucket
from . import dimfold


def direct_span(copr, span, nv, slot_bytes=8) -> bool:
    """May a dimension's build keys be probed through a direct table
    rather than a binary search over the sorted keys? Two bounds, both
    from what is observed: the keys are dense enough that the table is
    at most four slots a row, and the table (at most `slot_bytes` a
    slot, resident like the dimension's columns) fits an eighth of the
    resident store's budget: 128 Mi slots at the default 8 GiB, which
    keeps TPC-H's orders direct at scale 3 and 10 (PERF.md, PR 27)."""
    return span <= max(4 * nv, 1 << 12) and \
        span * slot_bytes <= copr._dev_store.budget // 8


def _pack_keys(arrays, key_cids, n, vidx):
    """-> (int64 key per row of `vidx`, pack layout) or (None, None): a
    NULL key, or a combined span past 62 bits. A single key passes
    through (pack None); several pack as sum((k_i - lo_i) * stride_i)
    under the layout (los, spans, strides)."""
    cols = []
    for cid in key_cids:
        kdata, knulls, _ = arrays[cid]
        if knulls is not None and knulls[:n][vidx].any():
            return None, None
        cols.append(kdata[:n][vidx])
    if len(cols) == 1:
        return cols[0], None
    if len(cols[0]) == 0:
        return None, None
    cols = [c.astype(np.int64) for c in cols]
    los = [int(c.min()) for c in cols]
    spans = [int(c.max()) - lo + 1 for c, lo in zip(cols, los)]
    if math.prod(spans) > (1 << 62):
        return None, None
    strides = [math.prod(spans[i + 1:]) for i in range(len(spans))]
    packed = sum((c - lo) * st for c, lo, st in zip(cols, los, strides))
    return packed, (tuple(los), tuple(spans), tuple(strides))


def _bucket_table(copr, arrays, key_cids, pack, vidx, n):
    """A key of several columns probed through buckets on ONE of them
    (partsupp's (ps_partkey, ps_suppkey): a part has four suppliers):
    row `k - lo` of that column holds `m` slots, `m` the most rows that
    share one value of it: first the `m` rows' other key columns packed
    (-1 where a slot is empty: no probe packs to it), then their `m`
    positions (n the miss). A column may be the bucket column when its
    table (span x m slots) keeps `direct_span`'s bounds and a bucket
    has no more slots than the binary search it replaces has steps; of
    those the one with the fewest slots a bucket, which is what a probe
    pays for, then the smaller table -> ProbeTable, or None (sorted).
    Its pack layout has stride 0 at the bucket column: what the kernel
    packs is a slot's other keys."""
    los, spans, _strides = pack
    nv = len(vidx)
    cols = [arrays[cid][0][:n][vidx].astype(np.int64) - lo
            for cid, lo in zip(key_cids, los)]
    packed_span = math.prod(spans)      # `_pack_keys` held it to 62 bits
    best = None
    for k, (col, s) in enumerate(zip(cols, spans)):
        if not direct_span(copr, s, nv):
            continue
        counts = np.bincount(col, minlength=s)
        m = int(counts.max())
        # a slot is two words, as narrow as the other keys and `n` fit
        dt = np.dtype(dimfold.table_dtype(max(
            (packed_span // s - 1).bit_length(), int(n).bit_length())))
        if direct_span(copr, s * m, nv, 2 * dt.itemsize) and \
                m <= (nv - 1).bit_length() and \
                (best is None or (m, s) < best[:2]):
            best = (m, s, k, counts, dt)
    if best is None:
        return None
    m, s, bcol, counts, dt = best
    rest, acc = [0] * len(spans), 1
    for k in reversed(range(len(spans))):
        if k != bcol:
            rest[k] = acc
            acc *= spans[k]
    others = sum(col * st for col, st in zip(cols, rest))
    o = np.argsort(cols[bcol], kind="stable")
    b = cols[bcol][o]
    rank = np.arange(nv) - (np.cumsum(counts) - counts)[b]  # in its bucket
    btab = np.empty((s, 2 * m), dtype=dt)
    btab[:, :m] = -1
    btab[:, m:] = n
    btab[b, rank] = others[o]
    btab[b, m + rank] = vidx[o]
    return ProbeTable("bucket", n, btab.reshape(-1), n_sorted=nv,
                      pack=(los, spans, tuple(rest)), bucket=(bcol, m))


class ProbeTable:
    """One dimension's probe table over one table version and snapshot,
    built once on the host, kept in `copr._host_cache` and not changed.
    `n`: the dimension's rows, the miss; `table`: the positions, or the
    buckets' rows, flat (`bucket` = (bucket column, slots a bucket));
    `keys`: the sorted keys; `n_sorted`: the build keys' count; `pack`:
    a composite key's (los, spans, strides); `exists`: the table holds
    the dimension's whole mask: a hit says that a row passes, not which."""

    __slots__ = ("form", "n", "table", "keys", "lo", "n_sorted", "pack",
                 "bucket", "exists")

    def __init__(self, form, n, table, keys=None, lo=None, n_sorted=0,
                 pack=None, bucket=None, exists=False):
        self.form, self.n, self.table, self.keys = form, n, table, keys
        self.lo, self.n_sorted, self.pack = lo, n_sorted, pack
        self.bucket, self.exists = bucket, exists

    @classmethod
    def build(cls, copr, arrays, key_cids, vidx, n):
        """The join's "hash table" over the build keys `key_cids` of the
        rows `vidx` of `n` -> ProbeTable, or None where a key is NULL or
        duplicated (or a composite key's span overflows): no unique
        build side."""
        keys_v, pack = _pack_keys(arrays, key_cids, n, vidx)
        nv = 0 if keys_v is None else len(keys_v)
        if nv == 0 or len(np.unique(keys_v)) != nv:
            return None
        return cls._over(copr, keys_v, vidx, n, pack, arrays, key_cids)

    @classmethod
    def build_exists(cls, copr, keys, n):
        """The table of a semi/anti dimension of `n` rows whose passing
        keys are `keys` (unique, ascending): a hit says a row passes,
        its position is any representative's (0)."""
        table = cls.always_miss(n) if not len(keys) else cls._over(
            copr, keys, np.zeros(len(keys), dtype=np.int64), n)
        table.exists = True
        return table

    @classmethod
    def _over(cls, copr, keys, pos, n, pack=None, arrays=None, cids=None):
        """The form, from what the keys are observed to be: direct where
        their span (packed, for several columns) is dense enough (TPC-H's
        primary keys are dense 1..N: the common case), else a composite
        key's buckets where one of its columns is, else sorted."""
        nv, lo = len(keys), int(keys.min())
        span = int(keys.max()) - lo + 1
        if direct_span(copr, span, nv):
            lut = np.full(span, n, dtype=dimfold.pos_dtype(n))
            lut[keys - lo] = pos
            return cls("direct", n, lut, lo=lo, n_sorted=nv, pack=pack)
        table = pack and _bucket_table(copr, arrays, cids, pack, pos, n)
        if not table:
            o = np.argsort(keys, kind="stable")
            table = cls("sorted", n, pos[o], keys=keys[o], n_sorted=nv,
                        pack=pack)
        return table

    @classmethod
    def always_miss(cls, n):
        """One slot that no key hits (nothing passes; an empty dimension
        under a left or anti join). The hit test is `table[idx] < n`, so
        it holds n itself: less is a false hit for a key equal to `lo`."""
        return cls("direct", n, np.array([n], dimfold.pos_dtype(n)), lo=0)

    @property
    def positions(self):
        """Position by key slot or by sorted rank: what a folded root's
        tables are composed with (the fold plan declines a bucket's)."""
        assert self.form != "bucket"
        return self.table

    def with_positions(self, table):
        """This table with `table` (a folded root's: the miss where the
        chain fails) in the positions' place."""
        assert len(table) == len(self.positions)
        return ProbeTable(self.form, self.n, table, self.keys, self.lo,
                          self.n_sorted, self.pack, None, self.exists)

    @property
    def nbytes(self):
        return self.table.nbytes + getattr(self.keys, "nbytes", 0)

    def signature(self):
        """What of the table is program text (`_fused_cache_key`): form,
        `exists`, a direct or bucket table's length and type (a sorted
        one's are `n_sorted`'s, beside this in the key, and int64)."""
        return (self.form, self.exists) + (
            () if self.form == "sorted" else
            (self.bucket, len(self.table), self.table.dtype.name))

    def label(self, dim, folded):
        """`mode` of tidb_tpu_fused_dim_probe_total, what resolves the
        dimension at fact width: nothing (`folded` under its parent),
        the form whatever the dimension is (`search`, `bucket`), else
        one gather: of a table that holds the mask (`exists`), of an
        aggregate dimension's (`matdim`), of its own table or word."""
        if folded:
            return "folded"
        if self.form != "direct":
            return "bucket" if self.form == "bucket" else "search"
        return "exists" if self.exists else \
            "matdim" if dim.subplan is not None else "direct"

    def host_probe(self, pv, pnm):
        """`resolve` over the host's arrays, for the chains dimfold
        resolves there -> (position clipped into the dimension, hit)."""
        if self.form == "bucket":   # the fold plan declines composite keys
            raise NotImplementedError("no host probe of a bucket table")
        da = {"lut": self.table, "lo": self.lo, "ord": self.table,
              "sk": self.keys}
        return resolve(da, {"form": self.form}, pv, None, pnm, self.n,
                       self.n_sorted, self.n, True, xp=np)

    def upload(self, args, put, cap, valid, pack=None):
        """What the kernel's probe reads, into `args`: the composite
        key's pack layout, `valid` (None where the table holds the mask,
        `exists` or a folded root's chain: no dead copies in the HBM
        pool), and the table: of positions, or of a folded root's words
        (`pack`, dimfold.Packed) with the fields' layout as operands
        -> the layout's entries, for `resolve` and the program's key
        (`words`: the type of each table a lane gathers from by key)."""
        n = self.n
        layout = {"form": self.form, "exists": self.exists}
        if self.pack is not None:
            # small host values ride the kernel call as numpy operands:
            # jnp.asarray of a scalar or a list is a device program of its
            # own (`jit_convert_element_type`) on every statement
            args["plo"], args["pspan"], args["pstride"] = (
                np.asarray(x, dtype=np.int64) for x in self.pack)
        if valid is not None:
            args["valid"] = put("valid", valid, n, cap, False, ts_keyed=True)
        if self.form == "bucket":
            # whole rows: the buckets' count is what is padded to a bucketed
            # size (a padding row is never addressed). The table holds the
            # snapshot's visible rows alone: a hit's `valid[pos]` is true
            length = len(self.table)
            row = 2 * self.bucket[1]
            args["bt"] = put("bt", self.table, length,
                             shape_bucket(length // row) * row, fill=-1,
                             ts_keyed=True)
            layout.update(bucket=self.bucket, visible=True)
            return layout
        direct = self.form == "direct"
        length = len(self.table) if direct else self.n_sorted
        tcap = shape_bucket(length)
        if pack is not None:
            args["pk"] = [put(("pk", pack.fields, wi), t, length, tcap,
                              fill=0 if wi else dimfold.miss(t.dtype),
                              ts_keyed=True)
                          for wi, t in enumerate(pack.tables)]
            args["fshift"], args["fmask"], args["flo"] = \
                pack.shift, pack.mask, pack.lo
            layout["pack"] = pack.text
            layout["words"] = tuple(t.dtype.name for t in pack.tables)
            nullable = {idx for kind, idx, _w, _dt in pack.text
                        if kind == "null"}
            for idx, sdict in pack.sdicts.items():
                layout[idx] = (idx in nullable, sdict)
        elif direct:
            args["lut"] = put("lut", self.table, length, tcap, fill=n,
                              ts_keyed=True)
            layout["words"] = (self.table.dtype.name,)
        else:
            args["ord"] = put("ord", self.table, length, tcap,
                              ts_keyed=True)
        if direct:
            args["lo"] = np.asarray(self.lo, dtype=np.int64)
        else:
            args["sk"] = put("sk", self.keys, length, tcap, ts_keyed=True,
                             fill=np.iinfo(np.int64).max)
        return layout


def _in_span(idx, lsize):
    return (idx >= 0) & (idx < lsize)


def resolve(da, layout, pv, kidx, pnm, dn, dsn, dcap, masked, xp=jnp):
    """The device probe of one dimension, traced inside the body's
    `dim_probe` scope (`xp` numpy: the host's, of the same tables): the
    lanes' keys `pv` (a composite key's packed, `kidx` its components'
    indexes; `pnm`: NULL or out of range) against the operands `da`
    that `upload` filled -> (position clipped under `dcap`, hit), or of
    a folded root's words (`da["pk"]`) -> (words as int64, key found):
    word 0's sign bit and `pnm` are the caller's to read. `dn` / `dsn`:
    the dimension's rows and build keys; `masked`: the positions hold
    the miss where the fold's chain fails. A table is gathered as narrow
    as it is held and widened after. Each case keeps the order its
    operations were first traced in: it is program text, by which two
    trees' programs are compared (benchmarks/fold_probe_tpu.py --hlo)."""
    form, words = layout["form"], da.get("pk")
    if form == "bucket":
        # ONE gather of the bucket's row. The bucket column's stride is 0,
        # so `pv` packs the lane's other keys, which at most one of the
        # row's slots holds (unique build keys; an empty slot holds what
        # nothing packs to); the position is that slot's
        bcol, slots = layout["bucket"]
        row = da["bt"].reshape(-1, 2 * slots)[kidx[bcol]]
        eq = row[:, :slots] == pv.astype(row.dtype)[:, None]
        pos = xp.sum(xp.where(eq, row[:, slots:], 0), axis=1, dtype=xp.int64)
        hit = xp.any(eq, axis=1) & (pos < dn) & ~pnm
        return xp.minimum(pos, dcap - 1), hit
    if form == "direct":
        # dense key domain: ONE gather (a word: by key slot)
        table = da["lut"] if words is None else words[0]
        lsize = table.shape[0]
        idx = pv - da["lo"]
        if words is not None:
            at = xp.clip(idx, 0, lsize - 1)
            hit = _in_span(idx, lsize)
            return [t[at].astype(xp.int64) for t in words], hit
        inb = _in_span(idx, lsize)
        raw = table[xp.clip(idx, 0, lsize - 1)].astype(xp.int64)
        if masked:
            hit = inb & (raw < dn) & ~pnm
        pos = xp.minimum(raw, dcap - 1)
        if not masked:
            # (the slot read a second time: XLA folds the two, and the
            # program's text stays what it was)
            hit = inb & (table[xp.clip(idx, 0, lsize - 1)]
                         .astype(xp.int64) < dn) & ~pnm
        return pos, hit
    # the sorted search: the key's rank, clipped into the keys
    loc = xp.searchsorted(da["sk"], pv)
    at = xp.minimum(loc, da["sk"].shape[0] - 1)
    if words is not None:
        # (a word: by sorted rank)
        hit = (da["sk"][at] == pv) & (loc < dsn)
        return [t[at].astype(xp.int64) for t in words], hit
    pos = da["ord"][at]
    hit = (da["sk"][at] == pv) & ~pnm & (loc < dsn)
    if masked:
        # a folded row order holds the miss sentinel
        hit = hit & (pos < dn)
        pos = xp.minimum(pos, dcap - 1)
    return pos, hit
