"""Plain reference for `queries/tpch_8.json`: each TPC-H query written
out in numpy over the arrays `datasets/tpch.py` made. Independent of
`serenedb_tpu`.

Joins are `searchsorted` on the primary keys (partsupp's on its two-part
key); groups are `np.unique(return_inverse=True)`. Money is int64 cents:
sums are exact, a product keeps the sum of its factors' scales (the
spec's DECIMAL(15,2) arithmetic), and a decimal answer is the text with
exactly that many fraction digits. AVG and Q14's ratio are the exact
fraction rounded once to float64.

`check` compares every answer of the window. `join_bytes(query, tables)`
is the work count of the server's `DeviceJoinBytes` counter for the same
statement: for every table a join statement references, its rows times
the narrowest 1/2/4/8-byte integer width of each column it references
(a string column as its code); 0 for a single-table statement.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")


def day(text: str) -> int:
    return int((np.datetime64(text, "D") - EPOCH).astype(np.int64))


def dec_text(v: int, scale: int) -> str:
    v = int(v)
    sign = "-" if v < 0 else ""
    whole, frac = divmod(abs(v), 10 ** scale)
    return f"{sign}{whole}.{frac:0{scale}d}"


class Data:
    """The tables, with string columns readable as text."""

    def __init__(self, tables: dict, dictionaries: dict):
        self.t, self.d = tables, dictionaries

    def col(self, table: str, name: str):
        return self.t[table][name]

    def text(self, table: str, name: str, codes) -> list:
        pool = self.d[f"{table}.{name}"]
        return [pool[int(c)] for c in codes]

    def is_(self, table: str, name: str, value: str):
        """Boolean mask: the string column equals `value`."""
        pool = self.d[f"{table}.{name}"]
        hit = np.array([s == value for s in pool])
        return hit[self.col(table, name)]

    def where(self, table: str, name: str, pred):
        pool = self.d[f"{table}.{name}"]
        hit = np.array([bool(pred(s)) for s in pool])
        return hit[self.col(table, name)]


def _lookup(keys, probe):
    """Row of each probe key in a table whose keys are `keys` (unique),
    and whether it was found."""
    order = np.argsort(keys, kind="stable")
    at = np.clip(np.searchsorted(keys[order], probe), 0, len(keys) - 1)
    row = order[at]
    return row, keys[row] == probe


def _gsum(inv, vals, ng: int, dtype=None):
    """Per-group sums: exact int64, or accumulated in `dtype` (the
    control)."""
    if ng == 0:
        return np.zeros(0, np.int64)
    order = np.argsort(inv, kind="stable")
    starts = np.searchsorted(inv[order], np.arange(ng))
    v = vals[order]
    if dtype is not None:
        return np.add.reduceat(v.astype(dtype), starts).astype(dtype)
    return np.add.reduceat(v.astype(np.int64), starts)


def _money(x, dtype):
    """A money total as the exact int, or the control's low-precision
    total rounded back to the nearest unit."""
    return int(x) if dtype is None else int(np.rint(float(x)))


def _year(days):
    return (days.astype("datetime64[D]").astype("datetime64[Y]")
            .astype(np.int64) + 1970)


def q1(D: Data, low=None):
    L = "lineitem"
    m = D.col(L, "l_shipdate") <= day("1998-09-02")
    rft = D.d["lineitem.l_returnflag"]
    lst = D.d["lineitem.l_linestatus"]
    code = D.col(L, "l_returnflag")[m].astype(np.int64) * len(lst) + \
        D.col(L, "l_linestatus")[m]
    uniq, inv = np.unique(code, return_inverse=True)
    q, e = D.col(L, "l_quantity")[m], D.col(L, "l_extendedprice")[m]
    d, t = D.col(L, "l_discount")[m], D.col(L, "l_tax")[m]
    ng = len(uniq)
    cnt = np.bincount(inv, minlength=ng)
    sq, se = _gsum(inv, q, ng, low), _gsum(inv, e, ng, low)
    sd = _gsum(inv, e * (100 - d), ng, low)
    sc = _gsum(inv, e * (100 - d) * (100 + t), ng, low)
    sdisc = _gsum(inv, d, ng, low)

    def avg(x, n):
        x = int(x) if low is None else float(x)
        return float(Fraction(x) / (n * 100))
    rows = []
    for g, k in enumerate(uniq.tolist()):
        n = int(cnt[g])
        rows.append((rft[k // len(lst)], lst[k % len(lst)],
                     dec_text(_money(sq[g], low), 2),
                     dec_text(_money(se[g], low), 2),
                     dec_text(_money(sd[g], low), 4),
                     dec_text(_money(sc[g], low), 6),
                     avg(sq[g], n), avg(se[g], n), avg(sdisc[g], n), n))
    rows.sort(key=lambda r: (r[0], r[1]))
    return rows, ["str", "str", "dec", "dec", "dec", "dec", "float",
                  "float", "float", "int"]


def q3(D: Data, low=None):
    cut = day("1995-03-15")
    cm = D.is_("customer", "c_mktsegment", "BUILDING")
    crow, cok = _lookup(D.col("customer", "c_custkey"),
                        D.col("orders", "o_custkey"))
    om = cok & cm[crow] & (D.col("orders", "o_orderdate") < cut)
    orow, ook = _lookup(D.col("orders", "o_orderkey"),
                        D.col("lineitem", "l_orderkey"))
    lm = ook & om[orow] & (D.col("lineitem", "l_shipdate") > cut)
    key = D.col("lineitem", "l_orderkey")[lm]
    rev = (D.col("lineitem", "l_extendedprice") *
           (100 - D.col("lineitem", "l_discount")))[lm]
    uniq, inv = np.unique(key, return_inverse=True)
    s = _gsum(inv, rev, len(uniq), low)
    first = np.zeros(len(uniq), np.int64)
    first[inv] = orow[lm]
    odate = D.col("orders", "o_orderdate")[first]
    ship = D.col("orders", "o_shippriority")[first]
    money = np.array([_money(x, low) for x in s], dtype=np.int64)
    order = np.lexsort((uniq, odate, -money))[:10]
    rows = [(int(uniq[i]), dec_text(money[i], 4),
             str(EPOCH + int(odate[i])), int(ship[i])) for i in order]
    return rows, ["int", "dec", "str", "int"]


def q5(D: Data, low=None):
    asia = D.is_("region", "r_name", "ASIA")
    nrow, _ = _lookup(D.col("region", "r_regionkey"),
                      D.col("nation", "n_regionkey"))
    n_in = asia[nrow]
    crow, cok = _lookup(D.col("customer", "c_custkey"),
                        D.col("orders", "o_custkey"))
    od = D.col("orders", "o_orderdate")
    om = cok & (od >= day("1994-01-01")) & (od < day("1995-01-01"))
    orow, ook = _lookup(D.col("orders", "o_orderkey"),
                        D.col("lineitem", "l_orderkey"))
    srow, sok = _lookup(D.col("supplier", "s_suppkey"),
                        D.col("lineitem", "l_suppkey"))
    s_nat = D.col("supplier", "s_nationkey")[srow]
    c_nat = D.col("customer", "c_nationkey")[crow[orow]]
    nat_row, _ = _lookup(D.col("nation", "n_nationkey"), s_nat)
    lm = ook & om[orow] & sok & (c_nat == s_nat) & n_in[nat_row]
    rev = (D.col("lineitem", "l_extendedprice") *
           (100 - D.col("lineitem", "l_discount")))[lm]
    names = np.array(D.text("nation", "n_name",
                            D.col("nation", "n_name")), dtype=object)
    keys = names[nat_row[lm]].astype(str)
    uniq, inv = np.unique(keys, return_inverse=True)
    s = _gsum(inv, rev, len(uniq), low)
    money = np.array([_money(x, low) for x in s], dtype=np.int64)
    rank = np.arange(len(uniq))
    order = np.lexsort((rank, -money))
    return [(str(uniq[i]), dec_text(money[i], 4)) for i in order], \
        ["str", "dec"]


def q6(D: Data, low=None):
    L = "lineitem"
    sd, disc = D.col(L, "l_shipdate"), D.col(L, "l_discount")
    m = (sd >= day("1994-01-01")) & (sd < day("1995-01-01")) & \
        (disc >= 5) & (disc <= 7) & (D.col(L, "l_quantity") < 2400)
    if not m.any():
        return [(None,)], ["dec"]
    v = (D.col(L, "l_extendedprice") * disc)[m]
    total = int(v.sum()) if low is None else \
        _money(v.astype(low).sum(dtype=low), low)
    return [(dec_text(total, 4),)], ["dec"]


def q9(D: Data, low=None):
    L = "lineitem"
    green = D.where("part", "p_name", lambda s: "green" in s)
    prow, pok = _lookup(D.col("part", "p_partkey"), D.col(L, "l_partkey"))
    srow, sok = _lookup(D.col("supplier", "s_suppkey"),
                        D.col(L, "l_suppkey"))
    span = int(D.col("partsupp", "ps_suppkey").max()) + 1
    ps_code = D.col("partsupp", "ps_partkey") * span + \
        D.col("partsupp", "ps_suppkey")
    psrow, psok = _lookup(ps_code, D.col(L, "l_partkey") * span +
                          D.col(L, "l_suppkey"))
    orow, ook = _lookup(D.col("orders", "o_orderkey"),
                        D.col(L, "l_orderkey"))
    m = pok & green[prow] & sok & psok & ook
    amount = (D.col(L, "l_extendedprice") * (100 - D.col(L, "l_discount"))
              - D.col("partsupp", "ps_supplycost")[psrow] *
              D.col(L, "l_quantity"))[m]
    nat = D.col("supplier", "s_nationkey")[srow[m]]
    nrow, _ = _lookup(D.col("nation", "n_nationkey"), nat)
    year = _year(D.col("orders", "o_orderdate")[orow[m]])
    uniq, inv = np.unique(nrow.astype(np.int64) * 10000 + year,
                          return_inverse=True)
    s = _gsum(inv, amount, len(uniq), low)
    name = D.text("nation", "n_name",
                  D.col("nation", "n_name")[uniq // 10000])
    order = sorted(range(len(uniq)),
                   key=lambda i: (name[i], -int(uniq[i] % 10000)))
    return [(name[i], float(uniq[i] % 10000),
             dec_text(_money(s[i], low), 4)) for i in order], \
        ["str", "float", "dec"]


def q10(D: Data, low=None):
    L = "lineitem"
    od = D.col("orders", "o_orderdate")
    om = (od >= day("1993-10-01")) & (od < day("1994-01-01"))
    crow, cok = _lookup(D.col("customer", "c_custkey"),
                        D.col("orders", "o_custkey"))
    orow, ook = _lookup(D.col("orders", "o_orderkey"),
                        D.col(L, "l_orderkey"))
    lm = ook & om[orow] & cok[orow] & D.is_(L, "l_returnflag", "R")
    cust = crow[orow[lm]]
    rev = (D.col(L, "l_extendedprice") * (100 - D.col(L, "l_discount")))[lm]
    uniq, inv = np.unique(cust, return_inverse=True)
    s = _gsum(inv, rev, len(uniq), low)
    money = np.array([_money(x, low) for x in s], dtype=np.int64)
    ckey = D.col("customer", "c_custkey")[uniq]
    order = np.lexsort((ckey, -money))[:20]
    C = "customer"
    nrow, _ = _lookup(D.col("nation", "n_nationkey"),
                      D.col(C, "c_nationkey"))
    rows = []
    for i in order:
        r = int(uniq[i])
        rows.append((int(ckey[i]),
                     D.text(C, "c_name", [D.col(C, "c_name")[r]])[0],
                     dec_text(money[i], 4),
                     dec_text(D.col(C, "c_acctbal")[r], 2),
                     D.text("nation", "n_name",
                            [D.col("nation", "n_name")[nrow[r]]])[0],
                     D.text(C, "c_address", [D.col(C, "c_address")[r]])[0],
                     D.text(C, "c_phone", [D.col(C, "c_phone")[r]])[0],
                     D.text(C, "c_comment", [D.col(C, "c_comment")[r]])[0]))
    return rows, ["int", "str", "dec", "dec", "str", "str", "str", "str"]


def q12(D: Data, low=None):
    L = "lineitem"
    rd, cd, sd = (D.col(L, "l_receiptdate"), D.col(L, "l_commitdate"),
                  D.col(L, "l_shipdate"))
    mode = D.where(L, "l_shipmode", lambda s: s in ("MAIL", "SHIP"))
    orow, ook = _lookup(D.col("orders", "o_orderkey"),
                        D.col(L, "l_orderkey"))
    m = ook & mode & (cd < rd) & (sd < cd) & \
        (rd >= day("1994-01-01")) & (rd < day("1995-01-01"))
    high = D.where("orders", "o_orderpriority",
                   lambda s: s in ("1-URGENT", "2-HIGH"))[orow[m]]
    modes = np.array(D.text(L, "l_shipmode", D.col(L, "l_shipmode")[m]),
                     dtype=str)
    uniq, inv = np.unique(modes, return_inverse=True)
    hi = np.bincount(inv, weights=high, minlength=len(uniq))
    lo_ = np.bincount(inv, weights=~high, minlength=len(uniq))
    return [(str(u), int(h), int(w)) for u, h, w in zip(uniq, hi, lo_)], \
        ["str", "int", "int"]


def q14(D: Data, low=None):
    L = "lineitem"
    sd = D.col(L, "l_shipdate")
    prow, pok = _lookup(D.col("part", "p_partkey"), D.col(L, "l_partkey"))
    m = pok & (sd >= day("1995-09-01")) & (sd < day("1995-10-01"))
    promo = D.where("part", "p_type", lambda s: s.startswith("PROMO"))
    rev = (D.col(L, "l_extendedprice") * (100 - D.col(L, "l_discount")))[m]
    p = promo[prow[m]]
    if low is None:
        a, b = int(rev[p].sum()), int(rev.sum())
        return [(float(Fraction(100 * a, b)) if b else None,)], ["float"]
    a = rev[p].astype(low).sum(dtype=low)
    b = rev.astype(low).sum(dtype=low)
    return [(float(100 * a / b),)], ["float"]


QUERIES = {"q1": q1, "q3": q3, "q5": q5, "q6": q6, "q9": q9, "q10": q10,
           "q12": q12, "q14": q14}

#: (table, columns) each statement references, keys included
REFERENCED = {
    "q1": {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax",
                        "l_shipdate"]},
    "q3": {"customer": ["c_mktsegment", "c_custkey"],
           "orders": ["o_custkey", "o_orderkey", "o_orderdate",
                      "o_shippriority"],
           "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"]},
    "q5": {"customer": ["c_custkey", "c_nationkey"],
           "orders": ["o_custkey", "o_orderkey", "o_orderdate"],
           "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice",
                        "l_discount"],
           "supplier": ["s_suppkey", "s_nationkey"],
           "nation": ["n_nationkey", "n_regionkey", "n_name"],
           "region": ["r_regionkey", "r_name"]},
    "q6": {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]},
    "q9": {"part": ["p_partkey", "p_name"],
           "supplier": ["s_suppkey", "s_nationkey"],
           "lineitem": ["l_suppkey", "l_partkey", "l_orderkey",
                        "l_extendedprice", "l_discount", "l_quantity"],
           "partsupp": ["ps_suppkey", "ps_partkey", "ps_supplycost"],
           "orders": ["o_orderkey", "o_orderdate"],
           "nation": ["n_nationkey", "n_name"]},
    "q10": {"customer": ["c_custkey", "c_name", "c_acctbal", "c_phone",
                         "c_address", "c_comment", "c_nationkey"],
            "orders": ["o_custkey", "o_orderkey", "o_orderdate"],
            "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                         "l_returnflag"],
            "nation": ["n_nationkey", "n_name"]},
    "q12": {"orders": ["o_orderkey", "o_orderpriority"],
            "lineitem": ["l_orderkey", "l_shipmode", "l_commitdate",
                         "l_receiptdate", "l_shipdate"]},
    "q14": {"lineitem": ["l_partkey", "l_shipdate", "l_extendedprice",
                         "l_discount"],
            "part": ["p_partkey", "p_type"]},
}


def _width(lo: int, hi: int) -> int:
    span = max(int(hi) - int(lo), 0)
    for w in (1, 2, 4):
        if span < (1 << (8 * w)):
            return w
    return 8


def join_bytes(query: str, tables: dict, dictionaries: dict = None) -> int:
    """The bytes the statement has to read whatever implements it, as the
    server's `DeviceJoinBytes` counts them: 0 for one table."""
    refs = REFERENCED[query]
    if len(refs) < 2:
        return 0
    total = 0
    for table, cols in refs.items():
        w = 0
        for c in cols:
            a = tables[table][c]
            if dictionaries is not None and f"{table}.{c}" in dictionaries:
                # the server's dictionary holds the distinct texts present
                w += _width(0, max(len(np.unique(a)) - 1, 0))
            else:
                w += _width(a.min(), a.max()) if len(a) else 1
        total += len(next(iter(tables[table].values()))) * w
    return total


# -- the comparison -------------------------------------------------------------


def evaluate(data: Data, query: str, low=None):
    rows, types = QUERIES[query](data, low)
    return {"rows": rows, "types": types}


def compare(got_rows, ref) -> tuple[bool, float]:
    """(exact parts equal, largest relative error of the float columns)
    of one answer: text tuples off the wire against the reference."""
    want = ref["rows"]
    if len(got_rows) != len(want):
        return False, 0.0
    worst = 0.0
    for g, w in zip(got_rows, want):
        if len(g) != len(w):
            return False, worst
        for gv, wv, t in zip(g, w, ref["types"]):
            if gv is None or wv is None:
                if not (gv is None and wv is None):
                    return False, worst
            elif t == "float":
                try:
                    x = float(gv)
                except ValueError:
                    return False, worst
                err = abs(x - wv) if wv == 0 else abs(x - wv) / abs(wv)
                worst = max(worst, err)
            elif gv != str(wv):
                return False, worst
    return True, worst


def check(ops: list, source, dataset: dict, seed: int, check,
          control: bool = False, cfg: dict = None):
    """Every answer of the window against the written-out queries. With
    `control`, the reference's own answers with float32 money
    accumulators stand in for the program's."""
    data = Data(dataset["tables"], dataset["dictionaries"])
    distinct: dict = {}
    for op in ops:
        if op["ok"]:
            k = (op["key"], tuple(op["answer"]))
            distinct[k] = distinct.get(k, 0) + 1
    refs: dict = {}
    wrong, worst = 0, 0.0
    for (key, answer), n in distinct.items():
        query = source.by_key[key][1]["query"]
        if query not in refs:
            refs[query] = evaluate(data, query)
        if control:
            low = evaluate(data, query, low=np.float32)
            answer = tuple(tuple(None if v is None else
                                 (repr(v) if isinstance(v, float) else str(v))
                                 for v in r) for r in low["rows"])
        ok, err = compare(list(answer), refs[query])
        worst = max(worst, err)
        if not ok:
            wrong += n
    return {"wrong_answers": wrong, "float_rel_err_max": worst}, \
        sum(distinct.values())
