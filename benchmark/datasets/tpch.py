"""TPC-H, synthesized from `--seed` with dbgen's published distributions
(TPC-H Standard Specification v3, §4.2.3), at the configuration's
`scale_factor`: all 8 tables of §1.4, every column in the spec's type.

What follows the spec exactly:

- row counts: 5 regions, 25 nations (the spec's names and regions),
  SF x 10,000 suppliers, 150,000 customers, 200,000 parts, 800,000
  partsupp rows (4 a part), 1,500,000 orders, 1 to 7 lines an order;
- sparse order keys (8 of every 32), o_custkey never a multiple of 3;
- p_retailprice's formula, ps_suppkey's formula and l_suppkey one of its
  part's four suppliers, l_extendedprice = l_quantity x p_retailprice;
- dates: o_orderdate uniform over [1992-01-01, 1998-12-31 - 151 days],
  ship +1..121 days, commit +30..90, receipt = ship +1..30; returnflag R
  or A when received by CURRENTDATE 1995-06-17, else N; linestatus O when
  shipped after CURRENTDATE, else F; o_orderstatus F, O or P from them;
  o_totalprice dbgen's integer sum over the lines;
- the spec's word lists: p_name (5 of the 92 colours), p_type, p_container,
  segments, priorities, ship modes and instructions.

What does not (the configuration's `assumed`): free text (addresses,
comments) is random lowercase words at the spec's lengths, one text of
its own for each row as dbgen's are, but not dbgen's grammar; so the
answers are not TPC's published answer set byte for byte.

Returns the load statements (CREATE TABLE in the spec's types, COPY from
one parquet file a table), the lineitem count, and `tables`: every
column as the reference reads it (integers and scaled decimals as int64,
dates as days, strings as codes into `dictionaries`).
"""

from __future__ import annotations

import os

#: STARTDATE, ENDDATE, CURRENTDATE (days since 1970-01-01)
START, END, CURRENT = 8035, 10591, 9298

NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue "
    "blush brown burlywood burnished chartreuse chiffon chocolate coral "
    "cornflower cornsilk cream cyan dark deep dim dodger drab firebrick "
    "floral forest frosted gainsboro ghost goldenrod green grey honeydew "
    "hot indian ivory khaki lace lavender lawn lemon light lime linen "
    "magenta maroon medium metallic midnight mint misty moccasin navajo "
    "navy olive orange orchid pale papaya peach peru pink plum powder puff "
    "purple red rose rosy royal saddle salmon sandy seashell sienna sky "
    "slate smoke snow spring steel tan thistle tomato turquoise violet "
    "wheat white yellow").split()
TYPE_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONT_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONT_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]

#: the spec's column order and types (§1.4); CHAR/VARCHAR as TEXT
SCHEMA = {
    "region": [("r_regionkey", "INTEGER"), ("r_name", "TEXT"),
               ("r_comment", "TEXT")],
    "nation": [("n_nationkey", "INTEGER"), ("n_name", "TEXT"),
               ("n_regionkey", "INTEGER"), ("n_comment", "TEXT")],
    "supplier": [("s_suppkey", "INTEGER"), ("s_name", "TEXT"),
                 ("s_address", "TEXT"), ("s_nationkey", "INTEGER"),
                 ("s_phone", "TEXT"), ("s_acctbal", "DECIMAL(15,2)"),
                 ("s_comment", "TEXT")],
    "customer": [("c_custkey", "INTEGER"), ("c_name", "TEXT"),
                 ("c_address", "TEXT"), ("c_nationkey", "INTEGER"),
                 ("c_phone", "TEXT"), ("c_acctbal", "DECIMAL(15,2)"),
                 ("c_mktsegment", "TEXT"), ("c_comment", "TEXT")],
    "part": [("p_partkey", "INTEGER"), ("p_name", "TEXT"),
             ("p_mfgr", "TEXT"), ("p_brand", "TEXT"), ("p_type", "TEXT"),
             ("p_size", "INTEGER"), ("p_container", "TEXT"),
             ("p_retailprice", "DECIMAL(15,2)"), ("p_comment", "TEXT")],
    "partsupp": [("ps_partkey", "INTEGER"), ("ps_suppkey", "INTEGER"),
                 ("ps_availqty", "INTEGER"),
                 ("ps_supplycost", "DECIMAL(15,2)"), ("ps_comment", "TEXT")],
    "orders": [("o_orderkey", "INTEGER"), ("o_custkey", "INTEGER"),
               ("o_orderstatus", "TEXT"), ("o_totalprice", "DECIMAL(15,2)"),
               ("o_orderdate", "DATE"), ("o_orderpriority", "TEXT"),
               ("o_clerk", "TEXT"), ("o_shippriority", "INTEGER"),
               ("o_comment", "TEXT")],
    "lineitem": [("l_orderkey", "INTEGER"), ("l_partkey", "INTEGER"),
                 ("l_suppkey", "INTEGER"), ("l_linenumber", "INTEGER"),
                 ("l_quantity", "DECIMAL(15,2)"),
                 ("l_extendedprice", "DECIMAL(15,2)"),
                 ("l_discount", "DECIMAL(15,2)"), ("l_tax", "DECIMAL(15,2)"),
                 ("l_returnflag", "TEXT"), ("l_linestatus", "TEXT"),
                 ("l_shipdate", "DATE"), ("l_commitdate", "DATE"),
                 ("l_receiptdate", "DATE"), ("l_shipinstruct", "TEXT"),
                 ("l_shipmode", "TEXT"), ("l_comment", "TEXT")],
}
#: (min, max) lengths of the free-text columns (§4.2.3)
TEXT_LEN = {"r_comment": (31, 115), "n_comment": (31, 114),
            "s_address": (10, 40), "s_comment": (25, 100),
            "c_address": (10, 40), "c_comment": (29, 116),
            "p_comment": (5, 22), "ps_comment": (49, 198),
            "o_comment": (19, 78), "l_comment": (10, 43)}


def counts(sf: float) -> dict:
    return {"supplier": int(10_000 * sf), "customer": int(150_000 * sf),
            "part": int(200_000 * sf), "orders": int(1_500_000 * sf)}


class Texts:
    """A free-text column's texts, one a row, held as one arrow string
    array (6M `l_comment`s as Python strings would be half a GB):
    `texts[i]` is row i's text, as a dictionary's entry is."""

    def __init__(self, arr):
        self.arr = arr

    def __len__(self) -> int:
        return len(self.arr)

    def __getitem__(self, i: int) -> str:
        return self.arr[i].as_py()


def _text(rng, name: str, n: int):
    """(codes, texts) of a free-text column: n random texts of lowercase
    words, lengths uniform in the spec's range, made in bulk; row i reads
    text i."""
    import numpy as np
    import pyarrow as pa
    lo, hi = TEXT_LEN[name]
    ends = np.cumsum(rng.integers(lo, hi + 1, n))
    offsets = np.zeros(n + 1, np.int32)
    offsets[1:] = ends
    total = int(ends[-1]) if n else 0
    chars = rng.integers(ord("a"), ord("z") + 1, total, dtype=np.uint8)
    space = rng.integers(0, 100, total, dtype=np.uint8) < 17
    space[offsets[:-1]] = False          # no text starts or ends with one
    space[offsets[1:] - 1] = False
    chars[space] = ord(" ")
    arr = pa.StringArray.from_buffers(n, pa.py_buffer(offsets.tobytes()),
                                      pa.py_buffer(chars.tobytes()))
    return np.arange(n, dtype=np.int32), Texts(arr)


def _coded(values: list, codes):
    return codes.astype("int32"), list(values)


def _numbered(prefix: str, keys):
    """('Customer#000000001', ...) as codes 0..n-1 into that pool."""
    import numpy as np
    pool = [f"{prefix}#{int(k):09d}" for k in keys]
    return np.arange(len(pool), dtype=np.int32), pool


def _phones(rng, nation):
    import numpy as np
    n = len(nation)
    parts = rng.integers([100, 100, 1000], [1000, 1000, 10000], (n, 3))
    pool = [f"{int(a) + 10}-{b}-{c}-{d}"
            for a, (b, c, d) in zip(nation, parts.tolist())]
    return np.arange(n, dtype=np.int32), pool


def retail_price(partkey):
    """p_retailprice in cents (§4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def part_supplier(partkey, i, n_supp: int):
    """ps_suppkey of a part's i-th supplier (§4.2.3)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def make(sf: float, seed: int) -> tuple[dict, dict]:
    """(tables, dictionaries): tables[t][c] is an int64 / int32 array
    (strings: codes into dictionaries["t.c"])."""
    import numpy as np
    rng = np.random.default_rng([seed, 0x7C4])
    c = counts(sf)
    S, C, P, O = c["supplier"], c["customer"], c["part"], c["orders"]
    T: dict = {}
    D: dict = {}

    def put(table, col, data, pool=None):
        T.setdefault(table, {})[col] = data
        if pool is not None:
            D[f"{table}.{col}"] = pool

    # region, nation
    put("region", "r_regionkey", np.arange(5, dtype=np.int64))
    put("region", "r_name", *_coded(REGIONS, np.arange(5)))
    put("region", "r_comment", *_text(rng, "r_comment", 5))
    put("nation", "n_nationkey", np.arange(25, dtype=np.int64))
    put("nation", "n_name", *_coded([n for n, _ in NATIONS], np.arange(25)))
    put("nation", "n_regionkey",
        np.array([r for _, r in NATIONS], dtype=np.int64))
    put("nation", "n_comment", *_text(rng, "n_comment", 25))

    # supplier
    sk = np.arange(1, S + 1, dtype=np.int64)
    s_nat = rng.integers(0, 25, S)
    put("supplier", "s_suppkey", sk)
    put("supplier", "s_name", *_numbered("Supplier", sk))
    put("supplier", "s_address", *_text(rng, "s_address", S))
    put("supplier", "s_nationkey", s_nat.astype(np.int64))
    put("supplier", "s_phone", *_phones(rng, s_nat))
    put("supplier", "s_acctbal", rng.integers(-99999, 1000000, S))
    put("supplier", "s_comment", *_text(rng, "s_comment", S))

    # customer
    ck = np.arange(1, C + 1, dtype=np.int64)
    c_nat = rng.integers(0, 25, C)
    put("customer", "c_custkey", ck)
    put("customer", "c_name", *_numbered("Customer", ck))
    put("customer", "c_address", *_text(rng, "c_address", C))
    put("customer", "c_nationkey", c_nat.astype(np.int64))
    put("customer", "c_phone", *_phones(rng, c_nat))
    put("customer", "c_acctbal", rng.integers(-99999, 1000000, C))
    put("customer", "c_mktsegment", *_coded(SEGMENTS,
                                             rng.integers(0, 5, C)))
    put("customer", "c_comment", *_text(rng, "c_comment", C))

    # part
    pk = np.arange(1, P + 1, dtype=np.int64)
    words = np.argsort(rng.random((P, len(COLORS))), axis=1)[:, :5]
    names = [" ".join(COLORS[w] for w in row) for row in words.tolist()]
    uniq, inv = np.unique(np.asarray(names, dtype=object),
                          return_inverse=True)
    m = rng.integers(1, 6, P)
    brand = m * 10 + rng.integers(1, 6, P)
    ptype = rng.integers(0, 6, P) * 25 + rng.integers(0, 5, P) * 5 + \
        rng.integers(0, 5, P)
    types = [f"{a} {b} {c_}" for a in TYPE_1 for b in TYPE_2 for c_ in TYPE_3]
    cont = rng.integers(0, 5, P) * 8 + rng.integers(0, 8, P)
    conts = [f"{a} {b}" for a in CONT_1 for b in CONT_2]
    put("part", "p_partkey", pk)
    put("part", "p_name", inv.astype(np.int32), list(uniq))
    put("part", "p_mfgr", *_coded([f"Manufacturer#{i}" for i in range(6)],
                                  m))
    put("part", "p_brand", *_coded([f"Brand#{i}" for i in range(56)],
                                   brand))
    put("part", "p_type", *_coded(types, ptype))
    put("part", "p_size", rng.integers(1, 51, P).astype(np.int64))
    put("part", "p_container", *_coded(conts, cont))
    price = retail_price(pk)
    put("part", "p_retailprice", price)
    put("part", "p_comment", *_text(rng, "p_comment", P))

    # partsupp: 4 suppliers a part
    ps_part = np.repeat(pk, 4)
    ps_supp = part_supplier(ps_part, np.tile(np.arange(4), P), S)
    put("partsupp", "ps_partkey", ps_part)
    put("partsupp", "ps_suppkey", ps_supp.astype(np.int64))
    put("partsupp", "ps_availqty", rng.integers(1, 10000, 4 * P)
        .astype(np.int64))
    put("partsupp", "ps_supplycost", rng.integers(100, 100001, 4 * P)
        .astype(np.int64))
    put("partsupp", "ps_comment", *_text(rng, "ps_comment", 4 * P))

    # orders and their lines
    i = np.arange(O, dtype=np.int64)
    okey = i // 8 * 32 + i % 8 + 1
    cust = rng.integers(1, C + 1, O)
    bad = cust % 3 == 0
    while bad.any():
        cust[bad] = rng.integers(1, C + 1, int(bad.sum()))
        bad = cust % 3 == 0
    odate = rng.integers(START, END - 151 + 1, O)
    nl = rng.integers(1, 8, O)
    L = int(nl.sum())
    l_order = np.repeat(np.arange(O), nl)
    first = np.repeat(np.cumsum(nl) - nl, nl)
    l_line = np.arange(L) - first + 1
    l_part = rng.integers(1, P + 1, L)
    l_supp = part_supplier(l_part, rng.integers(0, 4, L), S)
    qty = rng.integers(1, 51, L)
    eprice = qty * retail_price(l_part)
    disc = rng.integers(0, 11, L)
    tax = rng.integers(0, 9, L)
    ship = odate[l_order] + rng.integers(1, 122, L)
    commit = odate[l_order] + rng.integers(30, 91, L)
    receipt = ship + rng.integers(1, 31, L)
    rflag = np.where(receipt <= CURRENT, rng.integers(0, 2, L), 2)
    lstatus = (ship <= CURRENT).astype(np.int32)     # 0 O, 1 F
    n_f = np.bincount(l_order, weights=lstatus, minlength=O)
    ostatus = np.where(n_f == nl, 0, np.where(n_f == 0, 1, 2))
    line_total = (eprice * (100 - disc)) // 100 * (100 + tax) // 100
    total = np.bincount(l_order, weights=line_total.astype(np.float64),
                        minlength=O).astype(np.int64)
    put("orders", "o_orderkey", okey)
    put("orders", "o_custkey", cust.astype(np.int64))
    put("orders", "o_orderstatus", *_coded(["F", "O", "P"], ostatus))
    put("orders", "o_totalprice", total)
    put("orders", "o_orderdate", odate.astype(np.int64))
    put("orders", "o_orderpriority", *_coded(PRIORITIES,
                                             rng.integers(0, 5, O)))
    clerks = max(int(1000 * sf), 1)
    put("orders", "o_clerk", *_coded(
        [f"Clerk#{k:09d}" for k in range(1, clerks + 1)],
        rng.integers(0, clerks, O)))
    put("orders", "o_shippriority", np.zeros(O, dtype=np.int64))
    put("orders", "o_comment", *_text(rng, "o_comment", O))
    put("lineitem", "l_orderkey", okey[l_order])
    put("lineitem", "l_partkey", l_part.astype(np.int64))
    put("lineitem", "l_suppkey", l_supp.astype(np.int64))
    put("lineitem", "l_linenumber", l_line.astype(np.int64))
    put("lineitem", "l_quantity", (qty * 100).astype(np.int64))
    put("lineitem", "l_extendedprice", eprice.astype(np.int64))
    put("lineitem", "l_discount", disc.astype(np.int64))
    put("lineitem", "l_tax", tax.astype(np.int64))
    put("lineitem", "l_returnflag", *_coded(["R", "A", "N"], rflag))
    put("lineitem", "l_linestatus", *_coded(["O", "F"], lstatus))
    put("lineitem", "l_shipdate", ship.astype(np.int64))
    put("lineitem", "l_commitdate", commit.astype(np.int64))
    put("lineitem", "l_receiptdate", receipt.astype(np.int64))
    put("lineitem", "l_shipinstruct", *_coded(INSTRUCTIONS,
                                              rng.integers(0, 4, L)))
    put("lineitem", "l_shipmode", *_coded(MODES, rng.integers(0, 7, L)))
    put("lineitem", "l_comment", *_text(rng, "l_comment", L))
    return T, D


def _arrow(data, sql_type: str, pool=None):
    import numpy as np
    import pyarrow as pa
    if isinstance(pool, Texts):
        return pool.arr
    if pool is not None:
        return pa.DictionaryArray.from_arrays(
            pa.array(data, pa.int32()), pa.array(pool, pa.string()))
    if sql_type == "DATE":
        return pa.array(data.astype(np.int32)).cast(pa.date32())
    if sql_type.startswith("DECIMAL"):
        # decimal128 words: the scaled int64 and its sign extension
        words = np.empty((len(data), 2), np.int64)
        words[:, 0] = data
        words[:, 1] = np.where(data < 0, -1, 0)
        return pa.Array.from_buffers(pa.decimal128(15, 2), len(data),
                                     [None, pa.py_buffer(words.tobytes())])
    return pa.array(data.astype(np.int32))


def generate(cfg: dict, seed: int, workdir: str) -> dict:
    import pyarrow as pa
    import pyarrow.parquet as pq
    T, D = make(float(cfg["scale_factor"]), seed)
    load = []
    written = 0
    for table, cols in SCHEMA.items():
        path = os.path.join(workdir, f"{table}.parquet")
        pq.write_table(pa.table({
            c: _arrow(T[table][c], t, D.get(f"{table}.{c}"))
            for c, t in cols}), path, compression="snappy")
        written += os.path.getsize(path)
        load.append(f"CREATE TABLE {table} (" +
                    ", ".join(f"{c} {t}" for c, t in cols) + ")")
        load.append(f"COPY {table} FROM '{path}' (FORMAT parquet)")
    return {"load": load,
            "count": ("SELECT count(*) FROM lineitem",
                      len(T["lineitem"]["l_orderkey"])),
            "tables": T, "dictionaries": D, "params": {},
            "bytes_written": written}
