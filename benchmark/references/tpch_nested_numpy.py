"""Plain reference for `queries/tpch_nested.json`: TPC-H's nested queries
(Q2 Q20 Q17 Q18 Q21 Q22 Q4) written out in numpy over the arrays
`datasets/tpch.py` made. Independent of `serenedb_tpu`.

A subquery is computed as what it means, not as the server plans it: a
correlated aggregate per key with `np.unique` / `bincount`, an EXISTS as
the keys that have a row, Q21's `l2.l_suppkey <> l1.l_suppkey` as the
least and greatest supplier of each order. Money is int64 cents, and a
comparison with an average is made on exact fractions (`q < 0.2 * avg`
is `5 * q * count < sum`), as PostgreSQL's `numeric` makes it.

`check` compares every answer of the window. `join_bytes(query, tables)`
is the work count of the server's `DeviceJoinBytes` counter for the same
statement: for every table the statement references at any nesting
level, its rows times the narrowest 1/2/4/8-byte integer width of each
column referenced, each (table, column) once.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from benchmark.references.tpch_numpy import (Data, _width, compare, day,
                                             dec_text)

EPOCH = np.datetime64("1970-01-01", "D")


def _rows_of(keys, probe):
    """Row of each probe key in a table whose keys are unique (-1: none)."""
    order = np.argsort(keys, kind="stable")
    at = np.clip(np.searchsorted(keys[order], probe), 0, len(keys) - 1)
    row = order[at]
    return np.where(keys[row] == probe, row, -1)


def _per_row(idx, n: int, vals=None, low=None):
    """Per target row: the count of idx's hits, or the sum of vals
    (int64, or accumulated in `low`, the control's precision)."""
    live = idx >= 0
    if vals is None:
        return np.bincount(idx[live], minlength=n)
    if low is not None:
        out = np.zeros(n, low)
        np.add.at(out, idx[live], vals[live].astype(low))
        return out
    out = np.zeros(n, np.int64)
    np.add.at(out, idx[live], vals[live].astype(np.int64))
    return out


def _money(x, low) -> int:
    return int(x) if low is None else int(np.rint(float(x)))


def _europe_suppliers(D: Data, region: str):
    nat_region = _rows_of(D.col("region", "r_regionkey"),
                          D.col("nation", "n_regionkey"))
    in_region = D.is_("region", "r_name", region)[nat_region]
    srow_nat = _rows_of(D.col("nation", "n_nationkey"),
                        D.col("supplier", "s_nationkey"))
    return in_region[srow_nat], srow_nat


def q2(D: Data, low=None):
    ok_supp, snat = _europe_suppliers(D, "EUROPE")
    ps_s = _rows_of(D.col("supplier", "s_suppkey"),
                    D.col("partsupp", "ps_suppkey"))
    ps_p = _rows_of(D.col("part", "p_partkey"),
                    D.col("partsupp", "ps_partkey"))
    cost = D.col("partsupp", "ps_supplycost")
    eu = (ps_s >= 0) & ok_supp[np.clip(ps_s, 0, None)] & (ps_p >= 0)
    n_part = len(D.col("part", "p_partkey"))
    best = np.full(n_part, np.iinfo(np.int64).max)
    np.minimum.at(best, ps_p[eu], cost[eu].astype(np.int64))
    part_ok = (D.col("part", "p_size") == 15) & \
        D.where("part", "p_type", lambda s: s.endswith("BRASS"))
    m = eu & part_ok[np.clip(ps_p, 0, None)] & \
        (cost == best[np.clip(ps_p, 0, None)])
    S, P = "supplier", "part"
    rows = []
    for i in np.flatnonzero(m):
        s, p = int(ps_s[i]), int(ps_p[i])
        n = int(snat[s])
        rows.append((int(D.col(S, "s_acctbal")[s]),
                     D.text(S, "s_name", [D.col(S, "s_name")[s]])[0],
                     D.text("nation", "n_name",
                            [D.col("nation", "n_name")[n]])[0],
                     int(D.col(P, "p_partkey")[p]),
                     D.text(P, "p_mfgr", [D.col(P, "p_mfgr")[p]])[0],
                     D.text(S, "s_address", [D.col(S, "s_address")[s]])[0],
                     D.text(S, "s_phone", [D.col(S, "s_phone")[s]])[0],
                     D.text(S, "s_comment", [D.col(S, "s_comment")[s]])[0]))
    rows.sort(key=lambda r: (-r[0], r[2], r[1], r[3]))
    rows = [(dec_text(r[0], 2),) + r[1:] for r in rows[:100]]
    return rows, ["dec", "str", "str", "int", "str", "str", "str", "str"]


def q20(D: Data, low=None):
    L, PS = "lineitem", "partsupp"
    sd = D.col(L, "l_shipdate")
    lm = (sd >= day("1994-01-01")) & (sd < day("1995-01-01"))
    span = int(max(D.col(PS, "ps_suppkey").max(),
                   D.col(L, "l_suppkey").max())) + 1
    ps_code = D.col(PS, "ps_partkey").astype(np.int64) * span + \
        D.col(PS, "ps_suppkey")
    l_code = D.col(L, "l_partkey").astype(np.int64) * span + \
        D.col(L, "l_suppkey")
    at = np.where(lm, _rows_of(ps_code, l_code), -1)
    n = len(ps_code)
    cnt = _per_row(at, n)
    qty = _per_row(at, n, D.col(L, "l_quantity"), low)
    forest = D.where("part", "p_name", lambda s: s.startswith("forest"))
    keys = D.col("part", "p_partkey")[forest]
    in_forest = np.isin(D.col(PS, "ps_partkey"), keys)
    avail = D.col(PS, "ps_availqty").astype(np.int64)
    # ps_availqty > 0.5 * sum(l_quantity): 200 * availqty > sum in cents
    if low is None:
        more = 200 * avail > qty
    else:
        more = 200 * avail.astype(low) > qty
    ps_ok = in_forest & (cnt > 0) & more
    supp = np.unique(D.col(PS, "ps_suppkey")[ps_ok])
    S = "supplier"
    canada = D.is_("nation", "n_name", "CANADA")
    snat = _rows_of(D.col("nation", "n_nationkey"), D.col(S, "s_nationkey"))
    sm = np.isin(D.col(S, "s_suppkey"), supp) & (snat >= 0) & \
        canada[np.clip(snat, 0, None)]
    rows = sorted((D.text(S, "s_name", [D.col(S, "s_name")[i]])[0],
                   D.text(S, "s_address", [D.col(S, "s_address")[i]])[0])
                  for i in np.flatnonzero(sm))
    return rows, ["str", "str"]


def q17(D: Data, low=None):
    L, P = "lineitem", "part"
    prow = _rows_of(D.col(P, "p_partkey"), D.col(L, "l_partkey"))
    n = len(D.col(P, "p_partkey"))
    cnt = _per_row(prow, n)
    qsum = _per_row(prow, n, D.col(L, "l_quantity"))
    pm = D.is_(P, "p_brand", "Brand#23") & D.is_(P, "p_container", "MED BOX")
    r = np.clip(prow, 0, None)
    q = D.col(L, "l_quantity").astype(np.int64)
    # l_quantity < 0.2 * avg(l_quantity): 5 * q * count < sum
    m = (prow >= 0) & pm[r] & (cnt[r] > 0) & (5 * q * cnt[r] < qsum[r])
    if not m.any():
        return [(None,)], ["float"]
    price = D.col(L, "l_extendedprice")[m]
    total = int(price.astype(np.int64).sum()) if low is None else \
        float(price.astype(low).sum(dtype=low))
    return [(float(Fraction(total) / 700) if low is None
             else float(total) / 700,)], ["float"]


def q18(D: Data, low=None):
    # whole quantities: a sum of an order's lines is exact in float32 too
    L, O, C = "lineitem", "orders", "customer"
    orow = _rows_of(D.col(O, "o_orderkey"), D.col(L, "l_orderkey"))
    n = len(D.col(O, "o_orderkey"))
    qty = _per_row(orow, n, D.col(L, "l_quantity"))
    big = np.flatnonzero(qty > 30000)
    crow = _rows_of(D.col(C, "c_custkey"), D.col(O, "o_custkey"))
    price = D.col(O, "o_totalprice")
    odate = D.col(O, "o_orderdate")
    okey = D.col(O, "o_orderkey")
    big = big[crow[big] >= 0]
    order = np.lexsort((okey[big], odate[big], -price[big].astype(np.int64)))
    rows = []
    for i in big[order][:100]:
        c = int(crow[i])
        rows.append((D.text(C, "c_name", [D.col(C, "c_name")[c]])[0],
                     int(D.col(C, "c_custkey")[c]), int(okey[i]),
                     str(EPOCH + int(odate[i])), dec_text(price[i], 2),
                     dec_text(int(qty[i]), 2)))
    return rows, ["str", "int", "int", "str", "dec", "dec"]


def q21(D: Data, low=None):
    L, O, S = "lineitem", "orders", "supplier"
    orow = _rows_of(D.col(O, "o_orderkey"), D.col(L, "l_orderkey"))
    n = len(D.col(O, "o_orderkey"))
    supp = D.col(L, "l_suppkey").astype(np.int64)
    late = D.col(L, "l_receiptdate") > D.col(L, "l_commitdate")
    big = np.iinfo(np.int64).max
    lo_all, hi_all = np.full(n, big), np.full(n, -1)
    live = orow >= 0
    np.minimum.at(lo_all, orow[live], supp[live])
    np.maximum.at(hi_all, orow[live], supp[live])
    lo_late, hi_late = np.full(n, big), np.full(n, -1)
    lv = live & late
    np.minimum.at(lo_late, orow[lv], supp[lv])
    np.maximum.at(hi_late, orow[lv], supp[lv])
    saudi = D.is_("nation", "n_name", "SAUDI ARABIA")
    snat = _rows_of(D.col("nation", "n_nationkey"), D.col(S, "s_nationkey"))
    s_ok = (snat >= 0) & saudi[np.clip(snat, 0, None)]
    srow = _rows_of(D.col(S, "s_suppkey"), supp)
    r = np.clip(orow, 0, None)
    f = D.is_(O, "o_orderstatus", "F")
    # another line's supplier exists <=> min != s or max != s
    other = (lo_all[r] != supp) | (hi_all[r] != supp)
    other_late = (lo_late[r] != supp) | (hi_late[r] != supp)
    m = live & f[r] & late & (srow >= 0) & s_ok[np.clip(srow, 0, None)] & \
        other & ~other_late
    names = np.array(D.text(S, "s_name", D.col(S, "s_name")[srow[m]]),
                     dtype=object).astype(str)
    uniq, counts = np.unique(names, return_counts=True)
    order = sorted(range(len(uniq)), key=lambda i: (-counts[i], uniq[i]))
    return [(str(uniq[i]), int(counts[i])) for i in order[:100]], \
        ["str", "int"]


_CODES = ("13", "31", "23", "29", "30", "18", "17")


def q22(D: Data, low=None):
    C = "customer"
    phones = D.d["customer.c_phone"]
    code_of = np.array([p[:2] for p in phones], dtype=object)
    code = code_of[D.col(C, "c_phone")].astype(str)
    in_codes = np.isin(code, _CODES)
    bal = D.col(C, "c_acctbal").astype(np.int64)
    pos = in_codes & (bal > 0)
    total, cnt = int(bal[pos].sum()), int(pos.sum())
    has_order = np.isin(D.col(C, "c_custkey"), D.col("orders", "o_custkey"))
    # c_acctbal > avg: c * count > sum
    m = in_codes & (bal * cnt > total) & ~has_order if cnt else \
        np.zeros(len(bal), bool)
    uniq, inv = np.unique(code[m], return_inverse=True)
    rows = []
    for g, u in enumerate(uniq.tolist()):
        sel = bal[m][inv == g]
        s = int(sel.sum()) if low is None else \
            _money(sel.astype(low).sum(dtype=low), low)
        rows.append((u, int(len(sel)), dec_text(s, 2)))
    return rows, ["str", "int", "dec"]


def q4(D: Data, low=None):
    L, O = "lineitem", "orders"
    od = D.col(O, "o_orderdate")
    om = (od >= day("1993-07-01")) & (od < day("1993-10-01"))
    late = D.col(L, "l_commitdate") < D.col(L, "l_receiptdate")
    orow = _rows_of(D.col(O, "o_orderkey"), D.col(L, "l_orderkey"))
    has = np.zeros(len(od), bool)
    has[orow[(orow >= 0) & late]] = True
    m = om & has
    prio = np.array(D.text(O, "o_orderpriority",
                           D.col(O, "o_orderpriority")[m]), dtype=str)
    uniq, counts = np.unique(prio, return_counts=True)
    return [(str(u), int(c)) for u, c in zip(uniq, counts)], ["str", "int"]


QUERIES = {"q2": q2, "q20": q20, "q17": q17, "q18": q18, "q21": q21,
           "q22": q22, "q4": q4}

#: (table, columns) each statement references at any nesting level
REFERENCED = {
    "q2": {"part": ["p_partkey", "p_mfgr", "p_size", "p_type"],
           "supplier": ["s_suppkey", "s_acctbal", "s_name", "s_address",
                        "s_phone", "s_comment", "s_nationkey"],
           "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
           "nation": ["n_nationkey", "n_name", "n_regionkey"],
           "region": ["r_regionkey", "r_name"]},
    "q20": {"supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey"],
            "nation": ["n_nationkey", "n_name"],
            "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty"],
            "part": ["p_partkey", "p_name"],
            "lineitem": ["l_partkey", "l_suppkey", "l_quantity",
                         "l_shipdate"]},
    "q17": {"lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
            "part": ["p_partkey", "p_brand", "p_container"]},
    "q18": {"customer": ["c_custkey", "c_name"],
            "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                       "o_totalprice"],
            "lineitem": ["l_orderkey", "l_quantity"]},
    "q21": {"supplier": ["s_suppkey", "s_name", "s_nationkey"],
            "lineitem": ["l_suppkey", "l_orderkey", "l_receiptdate",
                         "l_commitdate"],
            "orders": ["o_orderkey", "o_orderstatus"],
            "nation": ["n_nationkey", "n_name"]},
    "q22": {"customer": ["c_phone", "c_acctbal", "c_custkey"],
            "orders": ["o_custkey"]},
    "q4": {"orders": ["o_orderdate", "o_orderkey", "o_orderpriority"],
           "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"]},
}


def join_bytes(query: str, tables: dict, dictionaries: dict = None) -> int:
    """The bytes the statement has to read whatever implements it, as the
    server's `DeviceJoinBytes` counts them."""
    total = 0
    for table, cols in REFERENCED[query].items():
        w = 0
        for c in cols:
            a = tables[table][c]
            if dictionaries is not None and f"{table}.{c}" in dictionaries:
                w += _width(0, max(len(np.unique(a)) - 1, 0))
            else:
                w += _width(a.min(), a.max()) if len(a) else 1
        total += len(next(iter(tables[table].values()))) * w
    return total


# -- the comparison -------------------------------------------------------------


def evaluate(data: Data, query: str, low=None):
    rows, types = QUERIES[query](data, low)
    return {"rows": rows, "types": types}


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
