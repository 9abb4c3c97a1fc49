"""TPC-H for the benchmark: the data generator, the query texts and the
plain numpy references.

The generator follows clause 4.2.3 of the specification column by
column (numpy, not dbgen's code): sparse order keys (8 of every 32),
part-derived retail and extended prices, order totals and statuses
derived from the lines, supplier keys drawn from the part's four
suppliers, phone, clerk, manufacturer and brand formats, random
addresses and comment text at the spec's lengths. What is not dbgen's
is listed under `assumed` in the configuration's file. The query texts
are copies of `tidb_tpu/bench/tpch.py`'s. The references for Q1, Q3,
Q5, Q6, Q10 and Q18 exist nowhere in the program: they are written
here, over the generated columns alone. Nothing in this file imports
the program; `load` is handed the program's bulk-load entry.

Decimals are fixed-point int64 (decimal(15,2) -> value * 100), dates
are days since 1970-01-01, string columns are int32 codes into the
value lists of `tables[DICTIONARIES]`: the stored representation. The
references compute in exact integers; `acc=np.float32` is the control
(the 32-bit lane a TPU tempts one to accumulate in), which must come
out as not correct.
"""
import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def days(s):
    y, m, d = (int(p) for p in s.split("-"))
    return datetime.date(y, m, d).toordinal() - _EPOCH


def date_text(n):
    return datetime.date.fromordinal(int(n) + _EPOCH).isoformat()


def dec_text(x, scale):
    """Scaled integer -> the wire's decimal text."""
    x = int(x)
    sign, x = ("-", -x) if x < 0 else ("", x)
    if scale == 0:
        return f"{sign}{x}"
    return f"{sign}{x // 10 ** scale}.{x % 10 ** scale:0{scale}d}"


DDL = {
    "region": """create table region (
        r_regionkey int primary key, r_name char(25), r_comment varchar(152))""",
    "nation": """create table nation (
        n_nationkey int primary key, n_name char(25), n_regionkey int,
        n_comment varchar(152))""",
    "supplier": """create table supplier (
        s_suppkey int primary key, s_name char(25), s_address varchar(40),
        s_nationkey int, s_phone char(15), s_acctbal decimal(15,2),
        s_comment varchar(101))""",
    "customer": """create table customer (
        c_custkey int primary key, c_name varchar(25), c_address varchar(40),
        c_nationkey int, c_phone char(15), c_acctbal decimal(15,2),
        c_mktsegment char(10), c_comment varchar(117))""",
    "part": """create table part (
        p_partkey int primary key, p_name varchar(55), p_mfgr char(25),
        p_brand char(10), p_type varchar(25), p_size int,
        p_container char(10), p_retailprice decimal(15,2),
        p_comment varchar(23))""",
    "partsupp": """create table partsupp (
        ps_partkey int, ps_suppkey int, ps_availqty int,
        ps_supplycost decimal(15,2), ps_comment varchar(199))""",
    "orders": """create table orders (
        o_orderkey int primary key, o_custkey int, o_orderstatus char(1),
        o_totalprice decimal(15,2), o_orderdate date,
        o_orderpriority char(15), o_clerk char(15), o_shippriority int,
        o_comment varchar(79))""",
    "lineitem": """create table lineitem (
        l_orderkey int, l_partkey int, l_suppkey int, l_linenumber int,
        l_quantity decimal(15,2), l_extendedprice decimal(15,2),
        l_discount decimal(15,2), l_tax decimal(15,2),
        l_returnflag char(1), l_linestatus char(1),
        l_shipdate date, l_commitdate date, l_receiptdate date,
        l_shipinstruct char(25), l_shipmode char(10), l_comment varchar(44))""",
}

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
STATUSES = ["F", "O", "P"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
RETURNFLAGS = ["R", "A", "N"]
LINESTATUSES = ["F", "O"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]

_D92 = days("1992-01-01")
_LAST_ORDER = days("1998-12-31") - 151   # o_orderdate's last day
_CURRENT = days("1995-06-17")            # the spec's CURRENTDATE

P_NAME_WORDS = (
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder "
    "puff purple red rose rosy royal saddle salmon sandy seashell sienna "
    "sky slate smoke snow spring steel tan thistle tomato turquoise "
    "violet wheat white yellow").split()

# the word lists of the spec's text grammar (clause 4.2.2.14); the text
# pool draws words from them without the grammar's sentence forms
TEXT_WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies "
    "excuses platelets asymptotes courts dolphins multipliers sauternes "
    "warthogs frets dinos attainments somas Tiresias' patterns forges "
    "braids hockey players frays warhorses dugouts notornis epitaphs "
    "pearls tithes waters orbits gifts sheaves depths sentiments decoys "
    "realms pains grouches escapades sleep wake are cajole haggle nag "
    "use boost affix detect integrate maintain nod was lose sublate "
    "solve thrash promise engage hinder print x-ray breach eat grow "
    "impress mold poach serve run dazzle snooze doze unwind kindle play "
    "hang believe doubt furious sly careful blithe quick fluffy slow "
    "quiet ruthless thin close dogged daring brave stealthy permanent "
    "enticing idle busy regular final ironic even bold silent sometimes "
    "always never furiously slyly carefully blithely quickly fluffily "
    "slowly quietly ruthlessly thinly closely doggedly daringly bravely "
    "stealthily permanently enticingly idly busily regularly finally "
    "ironically evenly boldly silently about above according to across "
    "after against along alongside of among around at atop before "
    "behind beneath beside besides between beyond by despite during "
    "except for from in place of inside instead of into near of on "
    "outside over past since through throughout to toward under until "
    "up upon without with within do may might shall will would can "
    "could should ought to must special pending unusual express "
    "requests packages accounts deposits . ; : ? ! --").split()
ADDRESS_CHARS = ("0123456789abcdefghijklmnopqrstuvwxyz"
                 "ABCDEFGHIJKLMNOPQRSTUVWXYZ ,")
POOL_SEED = 19920101          # the pools are the same under every seed
POOL_BYTES = 4 << 20

# where `generate` keeps each string column's value list
DICTIONARIES = "#dictionaries"


def _pools():
    rng = np.random.default_rng(POOL_SEED)
    words = np.array(TEXT_WORDS, dtype=object)
    text = " ".join(words[rng.integers(0, len(words), POOL_BYTES // 5)])
    chars = np.frombuffer(ADDRESS_CHARS.encode(), dtype=np.uint8)
    return (np.frombuffer(text.encode()[:POOL_BYTES], dtype=np.uint8),
            chars[rng.integers(0, len(chars), POOL_BYTES)])


def _substrings(rng, pool, n, lo, hi):
    """n random substrings of the pool, lengths uniform in [lo, hi]:
    -> (int32 codes, value list). Distinct (offset, length) pairs are
    found as integers, so no string is sorted or compared."""
    off = rng.integers(0, len(pool) - hi, n)
    ln = rng.integers(lo, hi + 1, n)
    uniq, codes = np.unique(off * 256 + ln, return_inverse=True)
    win = np.lib.stride_tricks.sliding_window_view(pool, hi)[uniq >> 8]
    win *= np.arange(hi, dtype=np.uint8) < (uniq & 255)[:, None]
    values = [b.decode() for b in
              np.ascontiguousarray(win).view(f"S{hi}").ravel().tolist()]
    return codes.astype(np.int32), values


def _formatted(fmt, numbers):
    """Codes and value list of fmt % number, one value each number."""
    uniq, codes = np.unique(numbers, return_inverse=True)
    return codes.astype(np.int32), [fmt % int(v) for v in uniq]


def _line_flags(shipdate, receiptdate, rng):
    """returnflag R/A for lines received by the current date, N after;
    linestatus O for lines shipped after it."""
    n = len(shipdate)
    rf = np.where(receiptdate <= _CURRENT, rng.integers(0, 2, n), 2)
    ls = np.where(shipdate > _CURRENT, 1, 0)
    return rf.astype(np.int32), ls.astype(np.int32)


# sizes are the same for every seed, contents are the seed's (dbgen's
# sizes are fixed too): how many lines each order has (1-7) and so
# lineitem's row count, which at SF1 is the spec's 6,001,215 (clause
# 4.2.5), and each line's quantity, which decides how many orders pass
# Q18's HAVING. The program sizes kernels by both (PERF.md, findings):
# `shape_seed` is the handle that keeps that visible
SHAPE_SEED = 19980802
LINEITEM_ROWS_SF1 = 6_001_215


def lines_per_order(n_ord, sf, shape_seed):
    rng = np.random.default_rng([shape_seed, n_ord])
    per = rng.integers(1, 8, n_ord)
    if shape_seed != SHAPE_SEED:
        return per                # a seed's own count, as dbgen's differs
    target = min(max(round(LINEITEM_ROWS_SF1 * sf), n_ord), 7 * n_ord)
    diff = int(target - per.sum())
    room = np.nonzero(per < 7 if diff > 0 else per > 1)[0]
    per[rng.choice(room, abs(diff), replace=False)] += 1 if diff > 0 else -1
    return per


def _phones(rng, nationkey):
    n = len(nationkey)
    a, b, c = (rng.integers(100, 1000, n), rng.integers(100, 1000, n),
               rng.integers(1000, 10000, n))
    values = [f"{10 + k}-{x}-{y}-{z}" for k, x, y, z in
              zip(nationkey.tolist(), a.tolist(), b.tolist(), c.tolist())]
    return np.arange(n, dtype=np.int32), values


def generate(sf, seed, shape_seed=SHAPE_SEED):
    """-> {table: {column: array}} in the stored representation, and
    under DICTIONARIES {table: {column: value list}} for the string
    columns (int32 codes)."""
    rng = np.random.default_rng(seed)
    text, chars = _pools()
    n_supp = max(int(10_000 * sf), 10)
    n_cust = max(int(150_000 * sf), 30)
    n_part = max(int(200_000 * sf), 40)
    n_ord = max(int(1_500_000 * sf), 150)
    i64 = np.int64
    t, d = {}, {}

    def strings(table, column, codes_values):
        d.setdefault(table, {})[column] = codes_values[1]
        return codes_values[0]

    def fixed(table, column, values, codes):
        d.setdefault(table, {})[column] = values
        return np.asarray(codes).astype(np.int32)

    t["region"] = {
        "r_regionkey": np.arange(5, dtype=i64),
        "r_name": fixed("region", "r_name", REGIONS, np.arange(5)),
        "r_comment": strings("region", "r_comment",
                             _substrings(rng, text, 5, 31, 115))}
    t["nation"] = {
        "n_nationkey": np.arange(25, dtype=i64),
        "n_name": fixed("nation", "n_name", [n for n, _ in NATIONS],
                        np.arange(25)),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=i64),
        "n_comment": strings("nation", "n_comment",
                             _substrings(rng, text, 25, 31, 114))}

    s_nat = rng.integers(0, 25, n_supp).astype(i64)
    s_codes, s_cmnt = _substrings(rng, text, n_supp, 25, 100)
    # SF x 5 suppliers each complain and recommend (clause 4.2.3)
    marked = rng.choice(n_supp, 2 * max(int(5 * sf), 1), replace=False)
    for j, row in enumerate(marked):
        word = "Complaints" if j % 2 == 0 else "Recommends"
        base = s_cmnt[s_codes[row]][:70]
        s_cmnt.append(f"{base[:8]}Customer {base[8:]}{word}")
        s_codes[row] = len(s_cmnt) - 1
    t["supplier"] = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=i64),
        "s_name": strings("supplier", "s_name", _formatted(
            "Supplier#%09d", np.arange(1, n_supp + 1))),
        "s_address": strings("supplier", "s_address",
                             _substrings(rng, chars, n_supp, 10, 40)),
        "s_nationkey": s_nat,
        "s_phone": strings("supplier", "s_phone", _phones(rng, s_nat)),
        "s_acctbal": rng.integers(-99999, 1000000, n_supp).astype(i64),
        "s_comment": strings("supplier", "s_comment", (s_codes, s_cmnt))}

    c_nat = rng.integers(0, 25, n_cust).astype(i64)
    t["customer"] = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=i64),
        "c_name": strings("customer", "c_name", _formatted(
            "Customer#%09d", np.arange(1, n_cust + 1))),
        "c_address": strings("customer", "c_address",
                             _substrings(rng, chars, n_cust, 10, 40)),
        "c_nationkey": c_nat,
        "c_phone": strings("customer", "c_phone", _phones(rng, c_nat)),
        "c_acctbal": rng.integers(-99999, 1000000, n_cust).astype(i64),
        "c_mktsegment": fixed("customer", "c_mktsegment", SEGMENTS,
                              rng.integers(0, len(SEGMENTS), n_cust)),
        "c_comment": strings("customer", "c_comment",
                             _substrings(rng, text, n_cust, 29, 116))}

    p_key = np.arange(1, n_part + 1, dtype=i64)
    name_codes = rng.integers(0, len(P_NAME_WORDS), (n_part, 5))
    names = np.array(P_NAME_WORDS, dtype=object)[name_codes]
    mfgr = rng.integers(0, 5, n_part)
    retail = 90000 + (p_key // 10) % 20001 + 100 * (p_key % 1000)
    t["part"] = {
        "p_partkey": p_key,
        "p_name": fixed("part", "p_name", [" ".join(r) for r in names],
                        np.arange(n_part)),
        "p_mfgr": fixed("part", "p_mfgr", MFGRS, mfgr),
        "p_brand": fixed("part", "p_brand", BRANDS,
                         mfgr * 5 + rng.integers(0, 5, n_part)),
        "p_type": fixed("part", "p_type", TYPES,
                        rng.integers(0, len(TYPES), n_part)),
        "p_size": rng.integers(1, 51, n_part).astype(i64),
        "p_container": fixed("part", "p_container", CONTAINERS,
                             rng.integers(0, len(CONTAINERS), n_part)),
        "p_retailprice": retail,
        "p_comment": strings("part", "p_comment",
                             _substrings(rng, text, n_part, 5, 22))}

    def supplier_of(partkey, i):
        """The part's i-th supplier (clause 4.2.3, PS_SUPPKEY)."""
        return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
            % n_supp + 1
    n_ps = n_part * 4
    ps_part = np.repeat(p_key, 4)
    t["partsupp"] = {
        "ps_partkey": ps_part,
        "ps_suppkey": supplier_of(ps_part,
                                  np.tile(np.arange(4, dtype=i64), n_part)),
        "ps_availqty": rng.integers(1, 10000, n_ps).astype(i64),
        "ps_supplycost": rng.integers(100, 100001, n_ps).astype(i64),
        "ps_comment": strings("partsupp", "ps_comment",
                              _substrings(rng, text, n_ps, 49, 198))}

    # orders and their lines: keys use 8 of every 32 (clause 4.2.3)
    seq = np.arange(1, n_ord + 1, dtype=i64)
    o_key = ((seq >> 3) << 5) | (seq & 7)
    o_date = (_D92 + rng.integers(0, _LAST_ORDER - _D92 + 1, n_ord)) \
        .astype(i64)
    # a third of the customers have no orders: no key divisible by 3
    pick = rng.integers(0, n_cust - n_cust // 3, n_ord).astype(i64)
    o_cust = pick + pick // 2 + 1
    per = lines_per_order(n_ord, sf, shape_seed)
    n_li = int(per.sum())
    first = np.cumsum(per) - per
    order_of = np.repeat(np.arange(n_ord), per)
    base = o_date[order_of]
    shipdate = base + rng.integers(1, 122, n_li)
    commitdate = base + rng.integers(30, 91, n_li)
    receiptdate = shipdate + rng.integers(1, 31, n_li)
    rf, ls = _line_flags(shipdate, receiptdate, rng)
    qty = np.random.default_rng([shape_seed, 2, n_ord]) \
        .integers(1, 51, n_li).astype(i64)
    l_part = rng.integers(1, n_part + 1, n_li).astype(i64)
    extprice = qty * retail[l_part - 1]
    discount = rng.integers(0, 11, n_li).astype(i64)
    tax = rng.integers(0, 9, n_li).astype(i64)
    # dbgen's integer arithmetic, line by line
    charge = extprice * (100 - discount) // 100 * (100 + tax) // 100
    open_lines = np.add.reduceat(ls.astype(i64), first)
    status = np.where(open_lines == 0, 0, np.where(open_lines == per, 1, 2))
    t["orders"] = {
        "o_orderkey": o_key,
        "o_custkey": o_cust,
        "o_orderstatus": fixed("orders", "o_orderstatus", STATUSES, status),
        "o_totalprice": np.add.reduceat(charge, first),
        "o_orderdate": o_date,
        "o_orderpriority": fixed("orders", "o_orderpriority", PRIORITIES,
                                 rng.integers(0, len(PRIORITIES), n_ord)),
        "o_clerk": strings("orders", "o_clerk", _formatted(
            "Clerk#%09d",
            rng.integers(1, max(int(1000 * sf), 1) + 1, n_ord))),
        "o_shippriority": np.zeros(n_ord, dtype=i64),
        "o_comment": strings("orders", "o_comment",
                             _substrings(rng, text, n_ord, 19, 78))}
    t["lineitem"] = {
        "l_orderkey": o_key[order_of],
        "l_partkey": l_part,
        "l_suppkey": supplier_of(l_part, rng.integers(0, 4, n_li)),
        "l_linenumber": np.arange(n_li, dtype=i64) - first[order_of] + 1,
        "l_quantity": qty * 100,
        "l_extendedprice": extprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": fixed("lineitem", "l_returnflag", RETURNFLAGS, rf),
        "l_linestatus": fixed("lineitem", "l_linestatus", LINESTATUSES, ls),
        "l_shipdate": shipdate.astype(i64),
        "l_commitdate": commitdate.astype(i64),
        "l_receiptdate": receiptdate.astype(i64),
        "l_shipinstruct": fixed("lineitem", "l_shipinstruct", INSTRUCTS,
                                rng.integers(0, len(INSTRUCTS), n_li)),
        "l_shipmode": fixed("lineitem", "l_shipmode", SHIPMODES,
                            rng.integers(0, len(SHIPMODES), n_li)),
        "l_comment": strings("lineitem", "l_comment",
                             _substrings(rng, text, n_li, 10, 43))}
    t[DICTIONARIES] = d
    return t


def load(tables, ddl, bulk_table):
    """Create the tables (`ddl(sql)` runs one statement over the wire)
    and hand every column to the program's bulk-load entry:
    `bulk_table(name)` -> the object whose `bulk_append(columns, n)`
    loads stored-form arrays, with its `dicts`/`table_info` so that
    int32 codes can be loaded against dictionaries seeded value by value
    (what `tidb_tpu.bench.tpch.load_tpch` does). A value list may hold
    a text twice: the codes follow the program's dictionary."""
    for name, sql in DDL.items():
        ddl(f"drop table if exists {name}")
        ddl(sql)
    for name in DDL:
        cols, ctab = dict(tables[name]), bulk_table(name)
        for col, values in tables[DICTIONARIES][name].items():
            sd = ctab.dicts[ctab.table_info.find_column(col).id]
            mapping = np.fromiter((sd.encode_one(v) for v in values),
                                  dtype=np.int32, count=len(values))
            cols[col] = mapping[cols[col]]
        ctab.bulk_append(cols, len(next(iter(cols.values()))))


def text_of(tables, table, column, row):
    """The string in `column` of the table's row (0-based)."""
    return tables[DICTIONARIES][table][column][tables[table][column][row]]


# ---- statements (the spec's validation parameters, clause 2.4) --------

Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval 90 day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""

Q5 = """
select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1994-01-01' + interval 1 year
group by n_name order by revenue desc
"""

Q6 = """
select sum(l_extendedprice * l_discount) as revenue from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval 1 year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01
  and l_quantity < 24
"""

Q10 = """
select c_custkey, c_name, sum(l_extendedprice * (1 - l_discount)) as revenue,
  c_acctbal, n_name, c_address, c_phone, c_comment
from customer, orders, lineitem, nation
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and o_orderdate >= date '1993-10-01'
  and o_orderdate < date '1993-10-01' + interval 3 month
  and l_returnflag = 'R' and c_nationkey = n_nationkey
group by c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
order by revenue desc limit 20
"""

Q18 = """
select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
  sum(l_quantity)
from customer, orders, lineitem
where o_orderkey in (select l_orderkey from lineitem
                     group by l_orderkey having sum(l_quantity) > 300)
  and c_custkey = o_custkey and o_orderkey = l_orderkey
group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
order by o_totalprice desc, o_orderdate limit 100
"""

STATEMENTS = {"q1": Q1, "q3": Q3, "q5": Q5, "q6": Q6, "q10": Q10,
              "q18": Q18}

# columns each full-scan statement must read, with their stored widths
# (int64 values, int32 dictionary codes): the bytes a scan cannot avoid
SCAN_BYTES_PER_ROW = {
    "q6": 4 * 8,            # shipdate, discount, quantity, extendedprice
    "q1": 5 * 8 + 2 * 4,    # shipdate, qty, price, disc, tax + two flags
}


# ---- the plain references ---------------------------------------------

def _sum(x, acc):
    """Exact int64 sum, or the control's float32 accumulation."""
    if acc is np.int64:
        return int(x.sum(dtype=np.int64))
    return int(np.rint(x.astype(acc).sum(dtype=acc)))


def _mul(a, b, acc):
    return a.astype(acc) * b.astype(acc)


def _group_sum(keys, vals, n, acc):
    vals = vals.astype(acc)
    if n <= 64:                   # few groups: a masked sum each
        out = np.array([vals[keys == g].sum(dtype=acc) for g in range(n)],
                       dtype=acc)
    else:
        out = np.zeros(n, dtype=acc)
        np.add.at(out, keys, vals)
    return out if acc is np.int64 else np.rint(out).astype(np.int64)


def q6_state(li, acc=np.int64):
    m = (li["l_shipdate"] >= days("1994-01-01")) & \
        (li["l_shipdate"] < days("1995-01-01")) & \
        (li["l_discount"] >= 5) & (li["l_discount"] <= 7) & \
        (li["l_quantity"] < 2400)
    return _sum(_mul(li["l_extendedprice"][m], li["l_discount"][m], acc),
                acc)


def q6_rows(state):
    return [(dec_text(state, 4),)]


def q1_state(li, acc=np.int64):
    """-> int array [6 groups, 6]: sum_qty, sum_price, sum_disc_price,
    sum_charge, sum_discount, count."""
    m = li["l_shipdate"] <= days("1998-12-01") - 90
    slot = (li["l_returnflag"][m].astype(np.int64) * 2 +
            li["l_linestatus"][m])
    price = li["l_extendedprice"][m]
    dp = _mul(price, 100 - li["l_discount"][m], acc)
    cols = (li["l_quantity"][m], price, dp,
            dp * (100 + li["l_tax"][m]).astype(acc), li["l_discount"][m],
            np.ones(len(slot), dtype=np.int64))
    return np.stack([_group_sum(slot, c, 6, acc) for c in cols], axis=1)


def _avg_text(total, count, scale):
    """decimal avg as MySQL gives it: four more digits, half away from
    zero."""
    q, r = divmod(int(total) * 10 ** 4, int(count))
    return dec_text(q + (1 if 2 * r >= count else 0), scale + 4)


def q1_rows(state):
    out = []
    for rf in sorted(range(3), key=lambda c: RETURNFLAGS[c]):
        for ls in sorted(range(2), key=lambda c: LINESTATUSES[c]):
            s = [int(v) for v in state[rf * 2 + ls]]
            if s[5] == 0:
                continue
            out.append((RETURNFLAGS[rf], LINESTATUSES[ls],
                        dec_text(s[0], 2), dec_text(s[1], 2),
                        dec_text(s[2], 4), dec_text(s[3], 6),
                        _avg_text(s[0], s[5], 2), _avg_text(s[1], s[5], 2),
                        _avg_text(s[4], s[5], 2), str(s[5])))
    return out


def _by_key(keys, values, n):
    """Dense lookup table: out[key] = value, for unique keys < n."""
    out = np.zeros(n, dtype=values.dtype)
    out[keys] = values
    return out


def _dense(t):
    """Lookup tables over the primary keys' ranges (order keys use 8 of
    every 32)."""
    o, c, s = t["orders"], t["customer"], t["supplier"]
    n_o = int(t["lineitem"]["l_orderkey"].max(initial=0)) + 1
    n_o = max(n_o, int(o["o_orderkey"].max(initial=0)) + 1)
    ok = o["o_orderkey"]
    live = np.zeros(n_o, dtype=bool)
    live[ok] = True
    return {
        "o_live": live,
        "o_cust": _by_key(ok, o["o_custkey"], n_o),
        "o_date": _by_key(ok, o["o_orderdate"], n_o),
        "o_total": _by_key(ok, o["o_totalprice"], n_o),
        "o_shipprio": _by_key(ok, o["o_shippriority"], n_o),
        "c_nat": _by_key(c["c_custkey"], c["c_nationkey"],
                         len(c["c_custkey"]) + 1),
        "c_seg": _by_key(c["c_custkey"], c["c_mktsegment"],
                         len(c["c_custkey"]) + 1),
        "c_bal": _by_key(c["c_custkey"], c["c_acctbal"],
                         len(c["c_custkey"]) + 1),
        "s_nat": _by_key(s["s_suppkey"], s["s_nationkey"],
                         len(s["s_suppkey"]) + 1),
        "n_orders": n_o}


def _revenue(li, m, acc):
    return _mul(li["l_extendedprice"][m], 100 - li["l_discount"][m], acc)


def _top(rows, n):
    """rows: (sort_key, row) pairs -> the first n by sort key."""
    rows.sort(key=lambda kr: kr[0])
    return rows[:n] if n else rows


def q3_rows(t, acc=np.int64):
    d, li = _dense(t), t["lineitem"]
    day = days("1995-03-15")
    lk = li["l_orderkey"]
    m = (li["l_shipdate"] > day) & d["o_live"][lk] & (d["o_date"][lk] < day) \
        & (d["c_seg"][d["o_cust"][lk]] == SEGMENTS.index("BUILDING"))
    rev = _group_sum(lk[m], _revenue(li, m, acc), d["n_orders"], acc)
    keys = np.unique(lk[m])
    rows = [((-int(rev[k]), int(d["o_date"][k])),
             (str(k), dec_text(rev[k], 4), date_text(d["o_date"][k]),
              str(d["o_shipprio"][k]))) for k in keys]
    return _top(rows, 10)


def q5_rows(t, acc=np.int64):
    d, li = _dense(t), t["lineitem"]
    lk = li["l_orderkey"]
    asia = np.array([REGIONS[r] == "ASIA" for _, r in NATIONS])
    snat = d["s_nat"][li["l_suppkey"]]
    m = d["o_live"][lk] & (d["o_date"][lk] >= days("1994-01-01")) & \
        (d["o_date"][lk] < days("1995-01-01")) & \
        (d["c_nat"][d["o_cust"][lk]] == snat) & asia[snat]
    rev = _group_sum(snat[m], _revenue(li, m, acc), 25, acc)
    rows = [((-int(rev[n]),), (NATIONS[n][0], dec_text(rev[n], 4)))
            for n in np.unique(snat[m])]
    return _top(rows, 0)


def q10_rows(t, acc=np.int64):
    d, li = _dense(t), t["lineitem"]
    lk = li["l_orderkey"]
    m = d["o_live"][lk] & (d["o_date"][lk] >= days("1993-10-01")) & \
        (d["o_date"][lk] < days("1994-01-01")) & \
        (li["l_returnflag"] == RETURNFLAGS.index("R"))
    ck = d["o_cust"][lk[m]]
    rev = _group_sum(ck, _revenue(li, m, acc), len(d["c_nat"]), acc)
    first = sorted((-int(rev[c]), int(c)) for c in np.unique(ck))[:20]
    rows = [((r,),
             (str(c), text_of(t, "customer", "c_name", c - 1),
              dec_text(-r, 4), dec_text(d["c_bal"][c], 2),
              NATIONS[d["c_nat"][c]][0],
              text_of(t, "customer", "c_address", c - 1),
              text_of(t, "customer", "c_phone", c - 1),
              text_of(t, "customer", "c_comment", c - 1)))
            for r, c in first]
    return _top(rows, 20)


def q18_rows(t, acc=np.int64):
    d, li = _dense(t), t["lineitem"]
    lk = li["l_orderkey"]
    qty = _group_sum(lk, li["l_quantity"], d["n_orders"], acc)
    rows = []
    for k in np.nonzero((qty > 30000) & d["o_live"])[0]:
        c = int(d["o_cust"][k])
        rows.append(((-int(d["o_total"][k]), int(d["o_date"][k])),
                     (text_of(t, "customer", "c_name", c - 1), str(c), str(k),
                      date_text(d["o_date"][k]), dec_text(d["o_total"][k], 2),
                      dec_text(qty[k], 2))))
    return _top(rows, 100)


def reference(tables, stmt, acc=np.int64):
    """-> the statement's answer as [(sort_key, wire-text row)]; the
    sort key is None where the ORDER BY is total over the rows."""
    li = tables["lineitem"]
    if stmt == "q6":
        return [(None, r) for r in q6_rows(q6_state(li, acc))]
    if stmt == "q1":
        return [(None, r) for r in q1_rows(q1_state(li, acc))]
    return {"q3": q3_rows, "q5": q5_rows, "q10": q10_rows,
            "q18": q18_rows}[stmt](tables, acc)


def answer_wrong(got, want):
    """Is the served answer `got` (wire rows) different from the
    reference `want` ([(sort_key, row)])? Rows whose sort keys tie may
    come in either order."""
    if len(got) != len(want):
        return True
    if all(k is None for k, _ in want):
        return list(got) != [r for _, r in want]
    i = 0
    while i < len(want):
        j = i
        while j < len(want) and want[j][0] == want[i][0]:
            j += 1
        if sorted(got[i:j]) != sorted(r for _, r in want[i:j]):
            return True
        i = j
    return False
