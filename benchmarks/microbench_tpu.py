"""TPU primitive microbenchmarks for the copr kernel design.

Every sample ends in a host fetch (np.asarray) — the same round trip a
real query result pays, and a wait for the device to finish. Run
directly, on the chip:

    python benchmarks/microbench_tpu.py [section ...]

Sections: io, reduce, group, sort by default; probe (its last rows
alone: bucket), sort4m, mxu and
scatter by name (scatter is the slowest to COMPILE on a TPU — run it
last, with a long timeout).

Design inputs these numbers feed (copr/agg_lowering.py lowering choice):
- dispatch+fetch round-trip floor
- masked reductions (no-group aggs)
- broadcast-compare-reduce (tiny group domains)
- blocked one-hot matmul (medium dense domains, MXU)
- cumsum + boundary extraction (pre-clustered group keys)
- sort / argsort / top_k (compaction, ordered output)
- segment_sum scatter (the fallback the others replace)
"""
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N = 1 << 20


def fetch(r):
    for leaf in jax.tree_util.tree_leaves(r):
        np.asarray(leaf)


def bench(label, fn, *args, reps=5):
    t0 = time.time()
    r = fn(*args)
    fetch(r)
    print(f"{label}: compile+1st {time.time() - t0:.1f}s", flush=True)
    t0 = time.time()
    for _ in range(reps):
        fetch(fn(*args))
    print(f"{label}: {(time.time() - t0) / reps * 1000:.2f} ms/op",
          flush=True)


def _bucket_rows(rng):
    """The forms a probe on a two-column key can take, at q9's sizes
    (PR 40, step 0): partsupp at SF1 is 800,000 rows keyed by
    (ps_partkey, ps_suppkey), a part has four suppliers, and q9 probes
    it at lineitem's width, a 4,194,304-lane block and a
    1,835,008-lane tail. Every row returns the sum of the hit lanes'
    positions and the hits' count, so the probe's whole result is kept
    alive and nothing but two scalars is fetched; the first call's
    answer is checked against numpy's.
    (a) what the parent runs: a binary search over the packed s64 keys
        and the two gathers after it;
    (b) buckets on ps_partkey, a row of four int32 slots a gather;
    (c) four flat int32 gathers at b*4 + j;
    (d) one int64 a slot holding (other key, position)."""
    P, S, M = 200_000, 10_000, 4
    # chunk.device.shape_bucket of 800,000 rows and of 200,000 buckets
    scap, pcap = 917_504, 229_376

    def supp_of(part, j):
        return (part + j * (S // M + (part - 1) // S)) % S + 1

    part = np.repeat(np.arange(1, P + 1, dtype=np.int64), M)
    slot_j = np.tile(np.arange(M, dtype=np.int64), P)
    supp = supp_of(part, slot_j)
    nv = P * M
    row = rng.permutation(nv)          # the row a key lives at
    packed = (part - 1) * S + (supp - 1)
    o = np.argsort(packed, kind="stable")
    sk = np.full(scap, np.iinfo(np.int64).max, dtype=np.int64)
    sk[:nv] = packed[o]
    ordr = np.zeros(scap, dtype=np.int64)
    ordr[:nv] = row[o]
    kt2 = np.full((pcap, M), -1, dtype=np.int32)      # other key - lo
    pt2 = np.full((pcap, M), nv, dtype=np.int32)      # position, nv = miss
    kt2[part - 1, slot_j] = supp - 1
    pt2[part - 1, slot_j] = row
    w2 = (kt2.astype(np.int64) << 32) | pt2.astype(np.int64)
    w2[kt2 < 0] = -1
    kp2 = np.concatenate([kt2, pt2], axis=1)          # [pcap, 8]
    dev = {k: jnp.asarray(v) for k, v in dict(
        sk=sk, ord=ordr, kt2=kt2, pt2=pt2, kt=kt2.reshape(-1),
        pt=pt2.reshape(-1), ktT=np.ascontiguousarray(kt2.T),
        ptT=np.ascontiguousarray(pt2.T), w2=w2, w=w2.reshape(-1),
        kp2=kp2, kp=kp2.reshape(-1)).items()}
    kcols = [jnp.asarray(np.ascontiguousarray(kt2[:, j])) for j in range(M)]
    pcols = [jnp.asarray(np.ascontiguousarray(pt2[:, j])) for j in range(M)]

    def out(pos, hit):
        return jnp.sum(jnp.where(hit, pos.astype(jnp.int64), 0)), \
            jnp.sum(hit.astype(jnp.int64))

    def split(lp, ls):
        b = jnp.clip(lp - 1, 0, P - 1)
        return b, (ls - 1).astype(jnp.int32)

    def searched(lp, ls):
        pv = (lp - 1) * S + (ls - 1)
        loc = jnp.searchsorted(dev["sk"], pv)
        locc = jnp.minimum(loc, scap - 1)
        pos = dev["ord"][locc]
        return out(pos, (dev["sk"][locc] == pv) & (loc < nv))

    def rows_rows(lp, ls):
        b, rem = split(lp, ls)
        eq = dev["kt2"][b] == rem[:, None]
        return out(jnp.sum(jnp.where(eq, dev["pt2"][b], 0), axis=1),
                   eq.any(axis=1))

    def rows_flat(lp, ls):
        b, rem = split(lp, ls)
        eq = dev["kt2"][b] == rem[:, None]
        at = b * M + jnp.argmax(eq, axis=1)
        return out(dev["pt"][at], eq.any(axis=1))

    def rows8(lp, ls):
        b, rem = split(lp, ls)
        r = dev["kp2"][b]
        eq = r[:, :M] == rem[:, None]
        return out(jnp.sum(jnp.where(eq, r[:, M:], 0), axis=1),
                   eq.any(axis=1))

    def rows8_flat(lp, ls):
        b, rem = split(lp, ls)
        r = dev["kp"].reshape(-1, 2 * M)[b]
        eq = r[:, :M] == rem[:, None]
        return out(jnp.sum(jnp.where(eq, r[:, M:], 0), axis=1),
                   eq.any(axis=1))

    def rows_rows_flat(lp, ls):
        b, rem = split(lp, ls)
        eq = dev["kt"].reshape(-1, M)[b] == rem[:, None]
        return out(jnp.sum(jnp.where(eq, dev["pt"].reshape(-1, M)[b], 0),
                           axis=1), eq.any(axis=1))

    def colsT(lp, ls):
        b, rem = split(lp, ls)
        eq = dev["ktT"][:, b] == rem[None, :]
        at = b * M + jnp.argmax(eq, axis=0)
        return out(dev["pt"][at], eq.any(axis=0))

    def _first(eqs):
        j = jnp.zeros(eqs[0].shape, dtype=jnp.int64)
        for k in range(M - 1, 0, -1):
            j = jnp.where(eqs[k], k, j)
        hit = eqs[0]
        for e in eqs[1:]:
            hit = hit | e
        return j, hit

    def flat_flat(lp, ls):
        b, rem = split(lp, ls)
        j, hit = _first([dev["kt"][b * M + k] == rem for k in range(M)])
        return out(dev["pt"][b * M + j], hit)

    def flat_four(lp, ls):
        b, rem = split(lp, ls)
        pos = jnp.zeros(b.shape, dtype=jnp.int32)
        hit = jnp.zeros(b.shape, dtype=bool)
        for k in range(M):
            e = dev["kt"][b * M + k] == rem
            pos = jnp.where(e, dev["pt"][b * M + k], pos)
            hit = hit | e
        return out(pos, hit)

    def tables_flat(lp, ls):
        b, rem = split(lp, ls)
        j, hit = _first([kc[b] == rem for kc in kcols])
        return out(dev["pt"][b * M + j], hit)

    def tables_tables(lp, ls):
        b, rem = split(lp, ls)
        pos = jnp.zeros(b.shape, dtype=jnp.int32)
        hit = jnp.zeros(b.shape, dtype=bool)
        for kc, pc in zip(kcols, pcols):
            e = kc[b] == rem
            pos = jnp.where(e, pc[b], pos)
            hit = hit | e
        return out(pos, hit)

    def words_flat(lp, ls):
        b, rem = split(lp, ls)
        pos = jnp.zeros(b.shape, dtype=jnp.int64)
        hit = jnp.zeros(b.shape, dtype=bool)
        for k in range(M):
            w = dev["w"][b * M + k]
            e = (w >> 32) == rem
            pos = jnp.where(e, w & 0xFFFFFFFF, pos)
            hit = hit | e
        return out(pos, hit)

    def words_rows(lp, ls):
        b, rem = split(lp, ls)
        w = dev["w2"][b]
        eq = (w >> 32) == rem[:, None]
        return out(jnp.sum(jnp.where(eq, w & 0xFFFFFFFF, 0), axis=1),
                   eq.any(axis=1))

    forms = [
        ("(a) searchsorted in 800k s64 keys + ord, sk gathers", searched),
        ("(b) row of 4 int32 [P,4], keys and positions", rows_rows),
        ("(b) row of 4 int32 keys + 1 flat position gather", rows_flat),
        ("(b) row of 8 int32 [P,8], keys beside positions", rows8),
        ("(b) row of 8 int32, the table handed over flat and reshaped "
         "in the program", rows8_flat),
        ("(b) rows of 4 int32 keys and of 4 positions, both tables flat "
         "and reshaped in the program", rows_rows_flat),
        ("(b) column of 4 int32 [4,P] + 1 flat position gather", colsT),
        ("(c) 4 flat int32 at b*4+j + 1 flat position gather", flat_flat),
        ("(c) 4 flat int32 keys + 4 flat positions", flat_four),
        ("(c) 4 tables of P int32 at b + 1 flat position gather",
         tables_flat),
        ("(c) 4 tables of P int32 keys + 4 of positions", tables_tables),
        ("(d) 4 flat int64 (key<<32 | position) at b*4+j", words_flat),
        ("(d) row of 4 int64 [P,4]", words_rows),
    ]
    for n in (4_194_304, 1_835_008):
        lp = rng.integers(1, P + 1, n)
        ls = supp_of(lp, rng.integers(0, M, n))
        ls[::97] = (ls[::97] % S) + 1          # a neighbour: mostly misses
        hp = (lp - 1) * S + (ls - 1)
        loc = np.minimum(np.searchsorted(sk[:nv], hp), nv - 1)
        hit = sk[loc] == hp
        want = (int(ordr[loc][hit].sum()), int(hit.sum()))
        jlp, jls = jnp.asarray(lp), jnp.asarray(ls)
        for label, fn in forms:
            f = jax.jit(fn)
            got = tuple(int(x) for x in f(jlp, jls))
            assert got == want, (label, got, want)
            bench(f"bucket probe {n} lanes {label}", f, jlp, jls)


def main(sections):
    rng = np.random.default_rng(0)
    v64 = jnp.asarray(rng.integers(0, 1 << 22, N), dtype=jnp.int64)
    all_s = not sections

    if all_s or "io" in sections:
        h32 = rng.integers(0, 1 << 22, 1 << 22).astype(np.int64)
        t0 = time.time()
        d = jax.device_put(h32)
        np.asarray(d[:1])
        print(f"upload 32MB {time.time() - t0:.2f}s", flush=True)
        t0 = time.time()
        np.asarray(d)
        print(f"download 32MB {time.time() - t0:.2f}s", flush=True)
        bench("roundtrip tiny", jax.jit(lambda a: jnp.sum(a[:8])), v64)

    if all_s or "reduce" in sections:
        def q6like(a, b, c, d):
            m = (a > 100) & (b < (1 << 21)) & (c > 50)
            return (jnp.sum(jnp.where(m, a, 0)),
                    jnp.sum(jnp.where(m, a * d, 0)), jnp.sum(m))
        bench("q6-like masked sums 1M", jax.jit(q6like),
              v64, v64 + 1, v64 + 2, v64 + 3)

    if all_s or "group" in sections:
        slots6 = jnp.asarray(rng.integers(0, 6, N), dtype=jnp.int64)

        def bcr(v, s):
            oh = s[None, :] == jnp.arange(6)[:, None]
            return jnp.sum(jnp.where(oh, v[None, :], 0), axis=1)
        bench("bcast-cmp-reduce 1M->6 i64", jax.jit(bcr), v64, slots6)

        bench("cumsum 1M i64", jax.jit(jnp.cumsum), v64)

        slots256 = jnp.asarray(rng.integers(0, 256, N), dtype=jnp.int64)

        def ohmm(v, s):
            blk = v.reshape(-1, 4096).astype(jnp.float32)
            oh = (s.reshape(-1, 4096)[:, :, None] ==
                  jnp.arange(256)[None, None, :]).astype(jnp.float32)
            p = jnp.einsum("bn,bns->bs", blk, oh)
            return jnp.sum(p.astype(jnp.int64), axis=0)
        bench("onehot-matmul blocked 1M->256", jax.jit(ohmm), v64,
              slots256)

        keys_clustered = jnp.asarray(np.sort(np.asarray(slots256)))

        def boundary_sums(v, key):
            cum = jnp.cumsum(v)
            last = jnp.concatenate(
                [key[1:] != key[:-1], jnp.ones((1,), bool)])
            return jnp.where(last, cum, 0), last
        bench("cumsum+boundary 1M", jax.jit(boundary_sums), v64,
              keys_clustered)

    if all_s or "sort" in sections:
        bench("sort 1M i64", jax.jit(jnp.sort), v64)
        bench("sort 1M i32", jax.jit(jnp.sort), v64.astype(jnp.int32))
        bench("argsort 1M i64", jax.jit(jnp.argsort), v64)
        bench("topk1024 1M", jax.jit(lambda v: jax.lax.top_k(v, 1024)),
              v64)

    if "probe" in sections:
        # dim-probe primitives at fused-kernel scale: 4M fact rows
        # against a 2M-row build side (q5/q9/q10 shapes)
        n4 = 1 << 22
        lut = jnp.asarray(rng.permutation(1 << 21), dtype=jnp.int64)
        idx4 = jnp.asarray(rng.integers(0, 1 << 21, n4), dtype=jnp.int64)
        bench("gather 4M from 2M lut", jax.jit(lambda lu, i: lu[i]),
              lut, idx4)
        # the same gather by physical type (ROADMAP S1(d): is a 64-bit
        # gather two 32-bit ones?). The "sum" rows reduce on the device,
        # so the sample is the gather and not the 4-32 MB download; the
        # "sorted idx" rows read the table in storage order, as a probe
        # by a clustered key (l_orderkey into orders) does.
        #
        # A row is named for what its program keeps alive. The PR 26
        # rows summed `lu[i].astype(int32)`: the narrowing leaves the
        # high u32 half of an s64 gather dead, the compiler drops it,
        # and the row prices ONE u32 gather whatever the table's dtype
        # ("low half" below). The "all 64 bits" rows sum in int64, or
        # the two halves apart, so both u32 gathers of an s64 table
        # run: what a composed word or a table of positions held as
        # int64 pays in the fused programs (PR 36's traces).
        lut32, idx32 = lut.astype(jnp.int32), idx4.astype(jnp.int32)
        lutb = (lut & 1).astype(bool)
        sidx4, sidx32 = jnp.sort(idx4), jnp.sort(idx32)
        glow = jax.jit(lambda lu, i: jnp.sum(lu[i].astype(jnp.int32)))
        g64 = jax.jit(lambda lu, i: jnp.sum(lu[i].astype(jnp.int64)))

        def ghalves(lu, i):
            w = lu[i]
            return (jnp.sum((w >> 32).astype(jnp.int32)),
                    jnp.sum(w.astype(jnp.int32)))
        ghalves = jax.jit(ghalves)
        bench("gather 4M from 2M lut int32 (table and idx)",
              jax.jit(lambda lu, i: lu[i]), lut32, idx32)
        bench("gather 4M from 2M lut bool (int32 idx)",
              jax.jit(lambda lu, i: lu[i]), lutb, idx32)
        bench("read+sum 4M int32 (the floor under the sum rows)",
              jax.jit(lambda i: jnp.sum(i)), idx32)
        bench("read+sum 4M int64 (the floor under the all-64-bits rows)",
              jax.jit(lambda i: jnp.sum(i)), idx4)
        bench("gather+sum 4M int64 table, low half alone", glow, lut, idx4)
        bench("gather+sum 4M int64 table, low half alone, int32 idx",
              glow, lut, idx32)
        bench("gather+sum 4M int64 table, all 64 bits (int64 sum)",
              g64, lut, idx4)
        bench("gather+sum 4M int64 table, all 64 bits (halves apart)",
              ghalves, lut, idx4)
        bench("gather+sum 4M int64 table, all 64 bits, int32 idx",
              g64, lut, idx32)
        bench("gather+sum 4M int32 table", glow, lut32, idx32)
        bench("gather+sum 4M int32 table, widened after (int64 sum)",
              g64, lut32, idx4)
        bench("gather+sum 4M bool table", glow, lutb, idx32)
        bench("gather+sum 4M int64 table, low half alone, sorted idx",
              glow, lut, sidx4)
        bench("gather+sum 4M int64 table, all 64 bits, sorted idx",
              g64, lut, sidx4)
        bench("gather+sum 4M int32 table, sorted idx", glow, lut32, sidx32)
        # the same pair from a 6M-slot table (orders' key span at SF1:
        # two thirds of its slots a miss, three quarters of orders')
        big = np.full(6 << 20, 1 << 21, dtype=np.int64)
        big[rng.choice(6 << 20, 1 << 21, replace=False)] = \
            np.arange(1 << 21)
        big64 = jnp.asarray(big)
        big32 = big64.astype(jnp.int32)
        bidx = jnp.asarray(rng.integers(0, 6 << 20, n4), dtype=jnp.int64)
        bench("gather+sum 4M from 6M-slot int64 table, all 64 bits",
              g64, big64, bidx)
        bench("gather+sum 4M from 6M-slot int32 table, widened after",
              g64, big32, bidx)
        bench("gather+sum 4M from 6M-slot int64 table, all 64 bits, "
              "sorted idx", g64, big64, jnp.sort(bidx))
        bench("gather+sum 4M from 6M-slot int32 table, widened after, "
              "sorted idx", g64, big32, jnp.sort(bidx))
        skeys = jnp.asarray(np.sort(rng.choice(1 << 24, 1 << 21,
                                               replace=False)),
                            dtype=jnp.int64)
        bench("searchsorted 2M x 4M probes",
              jax.jit(lambda t, q: jnp.searchsorted(t, q)), skeys, idx4)
        bench("5x gather 4M (multi-dim probe)",
              jax.jit(lambda lu, i: sum(lu[(i + k) & ((1 << 21) - 1)]
                                        for k in range(5))), lut, idx4)

    if "probe" in sections or "bucket" in sections:
        _bucket_rows(rng)

    if "sort4m" in sections:
        n4 = 1 << 22
        w4 = jnp.asarray(rng.integers(0, 1 << 40, n4), dtype=jnp.int64)
        bench("sort 4M i64", jax.jit(jnp.sort), w4, reps=2)
        bench("argsort 4M i64", jax.jit(jnp.argsort), w4, reps=2)

    if "mxu" in sections:
        # exact segment-sum via one-hot int8 matmul: 7-bit value limbs
        # x one-hot -> int32 MXU accumulation (per-group row count must
        # stay < 2^24 for exactness of the recombination in f32-free
        # int32 adds; partitions cap n at 4M so it holds)
        n4 = 1 << 22
        vals = jnp.asarray(rng.integers(0, 1 << 34, n4), dtype=jnp.int64)
        s256 = jnp.asarray(rng.integers(0, 256, n4), dtype=jnp.int64)

        def oh_s8(v, s):
            blk = 8192
            vb = jnp.stack([(v >> (7 * i)) & 0x7F for i in range(5)],
                           axis=1).astype(jnp.int8).reshape(-1, blk, 5)
            ohb = (s.reshape(-1, blk)[:, :, None] ==
                   jnp.arange(256)[None, None, :]).astype(jnp.int8)
            p = jnp.einsum("bns,bnl->sl", ohb, vb,
                           preferred_element_type=jnp.int32)
            return p
        bench("onehot-s8-matmul 4M->256x5limb", jax.jit(oh_s8),
              vals, s256, reps=3)

        s2k = jnp.asarray(rng.integers(0, 2048, n4), dtype=jnp.int64)

        def oh_s8_2k(v, s):
            blk = 8192
            vb = jnp.stack([(v >> (7 * i)) & 0x7F for i in range(5)],
                           axis=1).astype(jnp.int8).reshape(-1, blk, 5)
            ohb = (s.reshape(-1, blk)[:, :, None] ==
                   jnp.arange(2048)[None, None, :]).astype(jnp.int8)
            return jnp.einsum("bns,bnl->sl", ohb, vb,
                              preferred_element_type=jnp.int32)
        bench("onehot-s8-matmul 4M->2048x5limb", jax.jit(oh_s8_2k),
              vals, s2k, reps=3)

    if "scatter" in sections:          # never in the default set
        slots = jnp.asarray(rng.integers(0, 150_000, N), dtype=jnp.int64)
        bench("segment_sum 1M->150k i64",
              jax.jit(lambda v, s: jax.ops.segment_sum(
                  v, s, num_segments=150_000)), v64, slots)


if __name__ == "__main__":
    main(set(sys.argv[1:]))
