"""TPU primitive microbenchmarks for the copr kernel design.

Every sample ends in a host fetch (np.asarray) — the same round trip a
real query result pays, and a wait for the device to finish. Run
directly, on the chip:

    python benchmarks/microbench_tpu.py [section ...]

Sections: io, reduce, group, sort by default; probe (its last rows
alone: bucket), onehot (PR 43's step 0: how a learned slot table is
reduced), select (PR 44's step 0: where the k-th set lane of a mask
is), sort4m, mxu and
scatter by name (scatter is the slowest to COMPILE on a TPU — run it
last, with a long timeout).

Design inputs these numbers feed (copr/agg_lowering.py lowering choice):
- dispatch+fetch round-trip floor
- masked reductions (no-group aggs)
- broadcast-compare-reduce (tiny group domains; `onehot`: a learned
  slot table's, linear in the slots: 8.1 ms at 256, 57 at 2,048, 889 at
  32,768 a 4,194,304-lane block, PR 43)
- blocked one-hot matmul (medium dense domains, MXU; `onehot`: the
  program's loop 13.8 / 18.2 / 169.5 ms at those sizes, with the lanes
  kept minor 3.0 / 8.9 / 110.7; the search for the slot before it,
  678.5 ms in 256 keys, is what the kind cost)
- cumsum + boundary extraction (pre-clustered group keys)
- sort / argsort / top_k (compaction, ordered output)
- segment_sum scatter (the fallback the others replace)
"""
import os
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


def bench_med(label, fn, *args, reps=5):
    """Median of `reps` calls, each timed to `block_until_ready` (no
    fetch: a row's result stays on the device) -> the median in ms. A
    call of over 3 s is repeated twice, not `reps` times."""
    t0 = time.time()
    jax.block_until_ready(fn(*args))
    print(f"{label}: compile+1st {time.time() - t0:.1f}s", flush=True)
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
        if ms[-1] > 3000 and len(ms) >= 2:
            break
    ms.sort()
    med = ms[len(ms) // 2]
    print(f"{label}: {med:.3f} ms median of {len(ms)} "
          f"({ms[0]:.3f}-{ms[-1]:.3f})", flush=True)
    return med


_OH_L10 = tuple(range(0, 63, 7)) + (63,)     # the parent's limb shifts
# limbs cut from the two 32-bit halves: 7,7,7,7,4 bits of each
_OH_HALF = (0, 7, 14, 21, 28)


def _onehot_rows(rng, scaps, lanes=None):
    """ISSUE 43's step 0: how a block of fact lanes is reduced into a
    learned slot table, at q9's sizes: a 4,194,304-lane block and the
    1,835,008-lane tail, 175 live slots of `scap` 256 (25 nations x 7
    years packed with spans 26 x 8), one int64 sum within +-2^31, its
    non-null count and the row count a slot; then the forms that are
    linear in `scap` again at 2,048 and 32,768 slots (`ONEHOT_MAX`),
    the full block alone. Every form's sums and counts are checked
    against numpy's on its first call.
    (a)  the parent's block loop verbatim (agg_lowering.onehot_agg_body
         before PR 43: int64 slot compare, int8 one-hot [blk, scap], ten
         7-bit limbs cut from an s64, dot_general over the rows);
    (a1) its parts alone: the one-hot build, the limb matrix, the dot
         over operands that are already in HBM;
    (a2) the slot: searchsorted of the packed s64 codes in `scap` sorted
         s64 keys and the compare after it;
    (b)  the dense kind's broadcast-compare-reduce at [scap, cap], slot
         ids int32 with dead lanes at -1, sums in int64 (b64: the same
         with the ids compared as int64, what calling
         `_dense_agg_states_bcr` with an int32 slot would trace);
    (c)  the same in blocks: a fori_loop over 8,192 / 65,536 lanes, and
         a [nblk, blk] reshape reduced twice;
    (d)  the matmul repaired: int32 compare, one-hot (scap, blk) and
         limbs (L, blk) with lanes minor and the contraction over them,
         limbs cut from the 32-bit halves; ten limbs, and five and a
         sign for a value within 32 bits;
    (e)  the slot without a search: the packed code itself (a cast and
         a range check), and one int32 gather from a direct table over
         the code's range.
    `lanes`: other widths than q9's, for a rehearsal off the chip."""
    out = {}

    def check(label, got, want):
        for g, w, what in zip(got, want, ("sum", "count", "rows")):
            g = np.asarray(g)[:len(w)]
            assert np.array_equal(g, w), (label, what, g[:8], w[:8])

    def decode(acc, shifts):
        """(scap, L) int32 limb accumulator -> sums, counts, rows."""
        acc = np.asarray(acc).astype(np.int64)
        n = len(shifts)
        with np.errstate(over="ignore"):
            tot = np.zeros(acc.shape[0], dtype=np.int64)
            for i, sh in enumerate(shifts):
                tot = tot + np.left_shift(acc[:, i], sh)
        return tot, acc[:, n], acc[:, n + 1]

    for scap in scaps:
        nlive = 175 if scap == 256 else (scap * 7) // 10
        sizes = lanes or ((4_194_304, 1_835_008) if scap == 256
                          else (4_194_304,))
        # the learned table: `nlive` codes of a span the power-of-two
        # cap over which is scap (q9: 26 x 8 = 208 codes under 256)
        span = 208 if scap == 256 else scap - scap // 8
        codes = np.sort(rng.choice(np.arange(1, span), nlive,
                                   replace=False)).astype(np.int64)
        sk = np.full(scap, np.iinfo(np.int64).max, dtype=np.int64)
        sk[:nlive] = codes
        tab = np.full(scap, -1, dtype=np.int32)       # code -> slot
        tab[codes] = np.arange(nlive, dtype=np.int32)
        jsk, jtab = jnp.asarray(sk), jnp.asarray(tab)
        blk = max(512, min(8192, (1 << 25) // scap))
        for cap in sizes:
            tag = f"onehot scap {scap} {cap} lanes"
            slot_h = rng.integers(0, nlive, cap)
            live_h = rng.random(cap) < 0.054          # q9's part filter
            nn_h = rng.random(cap) < 0.999            # a value's non-null
            val_h = rng.integers(-(1 << 31), 1 << 31, cap)
            ok_h = live_h & nn_h
            sums = np.zeros(nlive, dtype=np.int64)
            np.add.at(sums, slot_h[ok_h], val_h[ok_h])
            want = (sums,
                    np.bincount(slot_h[ok_h], minlength=nlive),
                    np.bincount(slot_h[live_h], minlength=nlive))
            packed = jnp.asarray(codes[slot_h])
            slot64 = jnp.asarray(slot_h)
            live, nn = jnp.asarray(live_h), jnp.asarray(nn_h)
            val = jnp.asarray(val_h)
            slot32 = jnp.asarray(np.where(live_h, slot_h, -1)
                                 .astype(np.int32))
            nblk = cap // blk

            # ---- (a) the parent's loop, verbatim -------------------
            def vecs_of(live, nn, val):
                ok = live & nn
                dv = jnp.where(ok, val, jnp.zeros((), jnp.int64))
                return [(dv, 10), (ok.astype(jnp.int64), 1),
                        (live.astype(jnp.int64), 1)]

            def limb_cols(vecs, s):
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
                return jnp.concatenate(cols8, axis=1)        # (blk, L)

            def onehot_of(slot, live, s, sl_ids):
                sl_b = jax.lax.dynamic_slice(slot, (s,), (blk,))
                lv_b = jax.lax.dynamic_slice(live, (s,), (blk,))
                return ((sl_b[:, None] == sl_ids[None, :]) &
                        lv_b[:, None]).astype(jnp.int8)

            def parent(slot, live, nn, val):
                vecs = vecs_of(live, nn, val)
                sl_ids = jnp.arange(scap, dtype=jnp.int64)

                def block(b, acc):
                    s = b * blk
                    oh = onehot_of(slot, live, s, sl_ids)
                    lm = limb_cols(vecs, s)
                    p = jax.lax.dot_general(
                        oh, lm, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    return acc + p
                return jax.lax.fori_loop(
                    0, nblk, block, jnp.zeros((scap, 12), jnp.int32))

            def run(label, fn, *args, dec=None):
                f = jax.jit(fn)
                try:
                    got = f(*args)
                    if dec is not None:
                        got = dec(got)
                    if got is not None:
                        check(label, got, want)
                    out[f"{tag} {label}"] = bench_med(
                        f"{tag} {label}", f, *args)
                except Exception as e:          # noqa: BLE001
                    # a form the compiler refuses at this width is a
                    # reading too: say so and price the others
                    print(f"{tag} {label}: FAILED "
                          f"{type(e).__name__}: {str(e)[:300]}", flush=True)

            run(f"(a) the parent's loop, blk {blk} x {nblk}", parent,
                slot64, live, nn, val, dec=lambda a: decode(a, _OH_L10))

            if scap == 256:
                # ---- (a1) its parts alone --------------------------
                def part_onehot(slot, live):
                    sl_ids = jnp.arange(scap, dtype=jnp.int64)

                    def block(b, acc):
                        return acc | onehot_of(slot, live, b * blk, sl_ids)
                    acc = jax.lax.fori_loop(
                        0, nblk, block, jnp.zeros((blk, scap), jnp.int8))
                    return jnp.sum(acc.astype(jnp.int32))

                def part_limbs(live, nn, val):
                    vecs = vecs_of(live, nn, val)

                    def block(b, acc):
                        return acc | limb_cols(vecs, b * blk)
                    acc = jax.lax.fori_loop(
                        0, nblk, block, jnp.zeros((blk, 12), jnp.int8))
                    return jnp.sum(acc.astype(jnp.int32))

                def make_operands(slot, live, nn, val):
                    oh = ((slot[:, None] ==
                           jnp.arange(scap, dtype=jnp.int64)[None, :]) &
                          live[:, None]).astype(jnp.int8)
                    vecs = vecs_of(live, nn, val)
                    lm = jnp.concatenate(
                        [jnp.stack([((v >> (7 * i)) & 0x7F).astype(jnp.int8)
                                    for i in range(9)] +
                                   [((v >> 63) & 1).astype(jnp.int8)],
                                   axis=1) if n > 1
                         else (v & 1).astype(jnp.int8)[:, None]
                         for v, n in vecs], axis=1)
                    return oh, lm

                def part_dot(oh, lm):
                    def block(b, acc):
                        o = jax.lax.dynamic_slice(oh, (b * blk, 0),
                                                  (blk, scap))
                        m = jax.lax.dynamic_slice(lm, (b * blk, 0),
                                                  (blk, 12))
                        return acc + jax.lax.dot_general(
                            o, m, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
                    return jax.lax.fori_loop(
                        0, nblk, block, jnp.zeros((scap, 12), jnp.int32))

                run("(a1) the one-hot build alone, int64 compare to "
                    "int8 [blk, scap]", lambda s, lv: (part_onehot(s, lv),),
                    slot64, live, dec=lambda g: None)
                run("(a1) the limb matrix alone, 12 int8 columns of "
                    "[blk, L]", lambda lv, n_, v: (part_limbs(lv, n_, v),),
                    live, nn, val, dec=lambda g: None)
                try:
                    oh_all, lm_all = jax.jit(make_operands)(
                        slot64, live, nn, val)
                    run("(a1) the dot_general alone over operands in HBM",
                        part_dot, oh_all, lm_all,
                        dec=lambda a: decode(a, _OH_L10))
                    del oh_all, lm_all
                except Exception as e:          # noqa: BLE001
                    print(f"{tag} (a1) dot operands: FAILED {e}",
                          flush=True)

                # ---- (a2) the slot by search -----------------------
                def searched(packed, live):
                    loc = jnp.searchsorted(jsk, packed)
                    locc = jnp.minimum(loc, scap - 1)
                    hit = (jsk[locc] == packed) & (locc < nlive)
                    return (jnp.sum(jnp.where(live & hit, locc, 0)),
                            jnp.sum((live & ~hit).astype(jnp.int64)))
                run("(a2) searchsorted of s64 codes in scap s64 keys + "
                    "sk[locc] == packed", searched, packed, live,
                    dec=lambda g: None)

                # ---- (e) the slot without a search -----------------
                def code_slot(packed, live):
                    okr = (packed >= 0) & (packed < scap)
                    sl = jnp.where(live & okr, packed, -1).astype(jnp.int32)
                    return (jnp.sum(sl.astype(jnp.int64)),
                            jnp.sum((live & ~okr).astype(jnp.int64)))

                def table_slot(packed, live):
                    okr = (packed >= 0) & (packed < scap)
                    sl = jtab[jnp.clip(packed, 0, scap - 1)
                              .astype(jnp.int32)]
                    hit = okr & (sl >= 0)
                    return (jnp.sum(jnp.where(live & hit, sl, 0)
                                    .astype(jnp.int64)),
                            jnp.sum((live & ~hit).astype(jnp.int64)))
                run("(e) the packed code as the slot: a range check and "
                    "a cast", code_slot, packed, live, dec=lambda g: None)
                run("(e) one int32 gather from a direct table of scap "
                    "codes", table_slot, packed, live, dec=lambda g: None)

            # ---- (b) broadcast-compare-reduce, whole width ---------
            def bcr(slot, nn, val, ids):
                eq = slot[None, :] == ids[:, None]           # [scap, cap]
                sel = eq & nn[None, :]
                z = jnp.zeros((), jnp.int64)
                return (jnp.sum(jnp.where(sel, val[None, :], z), axis=1),
                        jnp.sum(sel.astype(jnp.int64), axis=1),
                        jnp.sum(eq.astype(jnp.int64), axis=1))

            run("(b) compare-reduce [scap, cap], int32 ids, int64 sums",
                lambda s, n_, v: bcr(s, n_, v,
                                     jnp.arange(scap, dtype=jnp.int32)),
                slot32, nn, val)
            run("(b64) the same, ids compared as int64",
                lambda s, n_, v: bcr(s, n_, v, jnp.arange(scap)),
                slot32, nn, val)

            # ---- (c) the same in blocks ----------------------------
            def bcr_loop(cblk):
                def fn(slot, nn, val):
                    ids = jnp.arange(scap, dtype=jnp.int32)

                    def block(b, acc):
                        s = b * cblk
                        r = bcr(jax.lax.dynamic_slice(slot, (s,), (cblk,)),
                                jax.lax.dynamic_slice(nn, (s,), (cblk,)),
                                jax.lax.dynamic_slice(val, (s,), (cblk,)),
                                ids)
                        return tuple(a + x for a, x in zip(acc, r))
                    z = jnp.zeros(scap, jnp.int64)
                    return jax.lax.fori_loop(0, cap // cblk, block,
                                             (z, z, z))
                return fn

            def bcr_twice(cblk):
                def fn(slot, nn, val):
                    ids = jnp.arange(scap, dtype=jnp.int32)
                    s2 = slot.reshape(-1, cblk)
                    n2 = nn.reshape(-1, cblk)
                    v2 = val.reshape(-1, cblk)
                    eq = s2[None] == ids[:, None, None]
                    sel = eq & n2[None]
                    z = jnp.zeros((), jnp.int64)

                    def red(x):
                        return jnp.sum(jnp.sum(x, axis=2), axis=1)
                    return (red(jnp.where(sel, v2[None], z)),
                            red(sel.astype(jnp.int64)),
                            red(eq.astype(jnp.int64)))
                return fn
            for cblk in (8192, 65536):
                run(f"(c) compare-reduce in a fori_loop of {cblk} lanes",
                    bcr_loop(cblk), slot32, nn, val)
            run("(c) compare-reduce over a [nblk, 8192] reshape, "
                "reduced twice", bcr_twice(8192), slot32, nn, val)

            # ---- (d) the matmul repaired ---------------------------
            def repaired(wide):
                nl = 10 if wide else 6
                L = nl + 2

                def fn(slot, nn, val):
                    ids = jnp.arange(scap, dtype=jnp.int32)
                    lo = val.astype(jnp.uint32)
                    hi = (val >> 32).astype(jnp.uint32)
                    ok = (slot >= 0) & nn

                    def block(b, acc):
                        s = b * blk
                        sl_b = jax.lax.dynamic_slice(slot, (s,), (blk,))
                        ok_b = jax.lax.dynamic_slice(ok, (s,), (blk,))
                        lo_b = jnp.where(
                            ok_b, jax.lax.dynamic_slice(lo, (s,), (blk,)),
                            jnp.zeros((), jnp.uint32))
                        oh = (ids[:, None] == sl_b[None, :]
                              ).astype(jnp.int8)             # (scap, blk)
                        rows = [((lo_b >> sh) & 0x7F).astype(jnp.int8)
                                for sh in _OH_HALF[:4]]
                        if wide:
                            hi_b = jnp.where(
                                ok_b,
                                jax.lax.dynamic_slice(hi, (s,), (blk,)),
                                jnp.zeros((), jnp.uint32))
                            rows.append((lo_b >> 28).astype(jnp.int8))
                            rows += [((hi_b >> sh) & 0x7F).astype(jnp.int8)
                                     for sh in _OH_HALF[:4]]
                            rows.append((hi_b >> 28).astype(jnp.int8))
                        else:
                            rows.append(((lo_b >> 28) & 7).astype(jnp.int8))
                            rows.append((lo_b >> 31).astype(jnp.int8))
                        rows.append(ok_b.astype(jnp.int8))
                        rows.append((sl_b >= 0).astype(jnp.int8))
                        lm = jnp.stack(rows, axis=0)         # (L, blk)
                        p = jax.lax.dot_general(
                            oh, lm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
                        return acc + p
                    return jax.lax.fori_loop(
                        0, nblk, block, jnp.zeros((scap, L), jnp.int32))

                def dec(acc):
                    acc = np.asarray(acc).astype(np.int64)
                    with np.errstate(over="ignore"):
                        if wide:
                            sh = _OH_HALF + tuple(32 + x for x in _OH_HALF)
                            tot = sum(np.left_shift(acc[:, i], s_)
                                      for i, s_ in enumerate(sh))
                        else:
                            tot = sum(np.left_shift(acc[:, i], s_)
                                      for i, s_ in enumerate(_OH_HALF))
                            tot = tot - np.left_shift(acc[:, 5], 31)
                    return tot, acc[:, nl], acc[:, nl + 1]
                return fn, dec
            for wide in (True, False):
                fn, dec = repaired(wide)
                run("(d) the matmul repaired: int32 compare, (scap, blk) x "
                    "(L, blk) over the lanes, " +
                    ("ten limbs of the halves" if wide else
                     "five limbs and a sign"), fn, slot32, nn, val, dec=dec)
    print("onehot step 0, ms (medians):", flush=True)
    for k, v in out.items():
        print(f"  {v:10.3f}  {k}", flush=True)
    return out


def _select_rows(rng, sizes=None):
    """ISSUE 44's step 0: where the k-th set lane of a mask is, at the
    sizes of the fused pipeline's compactions and of the runs lowering:
    `N` lanes (a 4,194,304-lane block, the 1,835,008-lane tail) x `K`
    probes (81,920 and 8,192), 1.5 % of the lanes set, so that probes
    run past the count; and N = K = 81,920 with half the lanes set (the
    runs lowering after a compaction). Every form's positions are
    checked against numpy's on its first call.
    (a) the parent: an s64 cumsum and `jnp.searchsorted`, s64 probes;
    (b) the same in int32;
    (c) `agg_lowering.prefix_search` (the program's own): k-ary over
        rows of block ends, rows of 8, 16, 32 and 128 int32, and one
        long row (512, 1,024) under a wide top level;
    (d) `jnp.searchsorted(..., method="sort")` in int32;
    (h) `lax.top_k` over the set lanes' negated indexes, no count;
    (e) the cumsum alone, s64 against int32 (ROADMAP S1(e));
    (f) a run's end: `searchsorted(cs_change, rid + 1)` in s64 as the
        parent has it, against `agg_lowering.next_flag` (a reverse
        cummin and one gather);
    (g) what a row costs: K rows of 1, 8, 16, 32 and 128 int32 gathered
        at sorted indexes from the N lanes as [N / B, B] and summed
        along the row, beside their price in 32-bit look-ups of 7.4 ns
        (PERF.md section 7, PR 37).
    `sizes`: other (N, K, share set) than the program's, for a
    rehearsal off the chip."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tidb_tpu.copr import agg_lowering as al
    out = {}
    sizes = sizes or ((4_194_304, 81_920, 0.015), (4_194_304, 8_192, 0.015),
                      (1_835_008, 81_920, 0.015), (1_835_008, 8_192, 0.015),
                      (81_920, 81_920, 0.5))
    LOOKUP_NS = 7.4

    def run(tag, label, fn, args, want):
        f = jax.jit(fn)
        try:
            got = np.asarray(f(*args))
            if want is not None:
                assert got.shape == want.shape and \
                    np.array_equal(got, want), (tag, label, got[:8],
                                                want[:8])
            out[f"{tag} {label}"] = bench_med(f"{tag} {label}", f, *args)
        except Exception as e:          # noqa: BLE001
            # a form the compiler refuses at this width is a reading
            # too: say so and price the others
            print(f"{tag} {label}: FAILED {type(e).__name__}: "
                  f"{str(e)[:300]}", flush=True)

    for n, k, share in sizes:
        tag = f"select {n} lanes {k} probes"
        flags_h = rng.random(n) < share
        cs_h = np.cumsum(flags_h.astype(np.int64))
        want = np.searchsorted(cs_h, np.arange(1, k + 1)).astype(np.int64)
        flags = jnp.asarray(flags_h)
        print(f"{tag}: {int(cs_h[-1])} set, "
              f"{int((want >= n).sum())} probes past the count", flush=True)

        def parent(m):
            return jnp.searchsorted(
                jnp.cumsum(m.astype(jnp.int64)),
                jnp.arange(1, k + 1, dtype=jnp.int64))

        def binary32(m):
            return jnp.searchsorted(
                jnp.cumsum(m.astype(jnp.int32)),
                jnp.arange(1, k + 1, dtype=jnp.int32))

        def sort32(m):
            return jnp.searchsorted(
                jnp.cumsum(m.astype(jnp.int32)),
                jnp.arange(1, k + 1, dtype=jnp.int32), method="sort")

        def rows(b, top=al.SELECT_TOP):
            def fn(m):
                return al.prefix_search(
                    al.prefix_count(m), jnp.arange(1, k + 1), row=b,
                    top=top)
            return fn

        run(tag, "(a) s64 cumsum + searchsorted, the parent", parent,
            (flags,), want)
        run(tag, "(b) int32 cumsum + searchsorted", binary32, (flags,),
            want)
        for b in (8, 16, 32, 128):
            run(tag, f"(c) int32 cumsum + rows of {b} ({b * 4} bytes)",
                rows(b), (flags,), want)
        run(tag, "(c) int32 cumsum + rows of 128, at most 2,048 ends on top",
            rows(128, 2048), (flags,), want)
        # one gather of a long row under a wide top level
        run(tag, "(c) int32 cumsum + rows of 512, at most 8,192 ends on top",
            rows(512, 8192), (flags,), want)
        run(tag, "(c) int32 cumsum + rows of 1024, at most 4,096 ends on top",
            rows(1024, 4096), (flags,), want)
        run(tag, "(d) int32 cumsum + searchsorted method=sort", sort32,
            (flags,), want)
        if n > k:
            # no count at all: the k smallest lane indexes among the set
            run(tag, "(h) top_k of the set lanes' negated indexes",
                lambda m: -jax.lax.top_k(
                    jnp.where(m, -jnp.arange(n, dtype=jnp.int32), -n),
                    k)[0], (flags,), want.astype(np.int32))
        if k == 81_920:
            run(tag, "(e) cumsum alone, s64",
                lambda m: jnp.cumsum(m.astype(jnp.int64)), (flags,), cs_h)
            run(tag, "(e) cumsum alone, int32",
                lambda m: jnp.cumsum(m.astype(jnp.int32)), (flags,),
                cs_h.astype(np.int32))

            # ---- (f) a run's end -------------------------------------
            # runs of about 8 lanes; `at` a lane of each of the first k
            change_h = rng.random(n) < 0.125
            change_h[0] = True
            starts = np.flatnonzero(change_h)
            at_h = np.minimum(starts[:k] + 1, n - 1)
            at_h = np.concatenate(
                [at_h, np.full(k - len(at_h), n - 1)]).astype(np.int64)
            csc_h = np.cumsum(change_h.astype(np.int64))
            want_re = np.minimum(
                np.searchsorted(csc_h, csc_h[at_h] + 1), n) - 1
            change, at = jnp.asarray(change_h), jnp.asarray(at_h)

            def re_search(ch, at):
                csc = jnp.cumsum(ch.astype(jnp.int64))
                return jnp.minimum(
                    jnp.searchsorted(csc, csc[at] + 1), n) - 1

            def re_scan(ch, at):
                return al.next_flag(ch, at, n) - 1

            run(tag, "(f) a run's end, s64 cumsum + gather + searchsorted",
                re_search, (change, at), want_re)
            run(tag, "(f) a run's end, reverse cummin + one gather",
                re_scan, (change, at), want_re)

            # ---- (g) what a row costs --------------------------------
            tab_h = rng.integers(0, 1 << 20, n).astype(np.int32)
            tab = jnp.asarray(tab_h)
            for b in (1, 8, 16, 32, 128):
                ridx_h = np.sort(rng.integers(0, n // b, k))
                want_g = tab_h.reshape(-1, b)[ridx_h].sum(
                    axis=1, dtype=np.int32)
                label = (f"(g) {k} rows of {b} int32 ({b * 4} bytes) at "
                         "sorted indexes, summed")
                run(tag, label,
                    lambda t, i, b=b: jnp.sum(
                        t.reshape(-1, b).at[i].get(
                            mode="promise_in_bounds"), axis=1),
                    (tab, jnp.asarray(ridx_h)), want_g)
                ms = out.get(f"{tag} {label}")
                if ms is not None:
                    print(f"{tag} {label}: {ms * 1e6 / k / LOOKUP_NS:.2f} "
                          f"look-ups of {LOOKUP_NS} ns a row (dispatch "
                          "included)", flush=True)
    print("select step 0, ms (medians):", flush=True)
    for key, v in out.items():
        print(f"  {v:10.3f}  {key}", flush=True)
    return out


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

    if "onehot" in sections:
        _onehot_rows(rng, (256, 2048, 32768))

    if "select" in sections:
        _select_rows(rng)

    if "sort4m" in sections:
        n4 = 1 << 22
        w4 = jnp.asarray(rng.integers(0, 1 << 40, n4), dtype=jnp.int64)
        bench("sort 4M i64", jax.jit(jnp.sort), w4, reps=2)
        bench("argsort 4M i64", jax.jit(jnp.argsort), w4, reps=2)

    if "mxu" in sections:
        # exact segment-sum via one-hot int8 matmul: 7-bit value limbs
        # x one-hot -> int32 MXU accumulation. A limb column is exact
        # while lanes x 127 < 2^31 (agg_lowering.ONEHOT_CAP_MAX, 8M
        # lanes; a block is 4M): int32 adds, no float anywhere, so no
        # 2^24 line. These two rows feed the einsum whole columns; the
        # program's own blocked loop, its parts and what replaces it up
        # to 256 slots are the `onehot` section's rows (PR 43)
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
