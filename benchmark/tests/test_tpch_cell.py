"""The `tpch.power_c1` cell's own parts, on the CPU at small scale factors:

- the generator's row counts and foreign keys (datasets/tpch.py);
- the reference (references/tpch_numpy.py) against a brute-force
  evaluation over Python dicts, row by row;
- its comparison catches one altered cent and one dropped row, and the
  control (float32 money accumulators) fails `float_rel_err_max`;
- `join_bytes` is what the server's `DeviceJoinBytes` counts for each
  statement.
"""

import datetime
import json
import os
from fractions import Fraction

import numpy as np
import pytest

from benchmark.datasets import tpch as gen
from benchmark.references import tpch_numpy as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _queries():
    with open(os.path.join(BENCH, "queries", "tpch_8.json")) as f:
        return {s["id"]: s["sql"] for s in json.load(f)["statements"]}


#: the smallest scale at which ps_suppkey's formula gives each part four
#: distinct suppliers (at SF 0.005, 50 suppliers, it repeats one)
SF = 0.01


@pytest.fixture(scope="module")
def small():
    return gen.make(SF, 6022140857)


def test_row_counts_and_foreign_keys(small):
    T, _ = small
    c = gen.counts(SF)
    n = {t: len(next(iter(cols.values()))) for t, cols in T.items()}
    assert n["region"] == 5 and n["nation"] == 25
    assert n["supplier"] == c["supplier"] == 100
    assert n["customer"] == c["customer"] and n["part"] == c["part"]
    assert n["partsupp"] == 4 * n["part"] and n["orders"] == c["orders"]
    lines = np.bincount(np.searchsorted(T["orders"]["o_orderkey"],
                                        T["lineitem"]["l_orderkey"]))
    assert lines.min() >= 1 and lines.max() <= 7
    ok = T["orders"]["o_orderkey"]
    assert (((ok - 1) % 32) < 8).all()
    assert (T["orders"]["o_custkey"] % 3 != 0).all()

    def resolves(child, ckey, parent, pkey):
        keys = set(T[parent][pkey].tolist())
        return all(k in keys for k in T[child][ckey].tolist())
    assert resolves("lineitem", "l_orderkey", "orders", "o_orderkey")
    assert resolves("lineitem", "l_partkey", "part", "p_partkey")
    assert resolves("lineitem", "l_suppkey", "supplier", "s_suppkey")
    assert resolves("orders", "o_custkey", "customer", "c_custkey")
    assert resolves("customer", "c_nationkey", "nation", "n_nationkey")
    assert resolves("supplier", "s_nationkey", "nation", "n_nationkey")
    assert resolves("nation", "n_regionkey", "region", "r_regionkey")
    pairs = set(zip(T["partsupp"]["ps_partkey"].tolist(),
                    T["partsupp"]["ps_suppkey"].tolist()))
    assert len(pairs) == n["partsupp"]
    assert all(p in pairs for p in zip(T["lineitem"]["l_partkey"].tolist(),
                                       T["lineitem"]["l_suppkey"].tolist()))
    price = gen.retail_price(T["lineitem"]["l_partkey"])
    assert (T["lineitem"]["l_extendedprice"] ==
            T["lineitem"]["l_quantity"] // 100 * price).all()


# -- brute force: every table a list of dicts ----------------------------------


def _rows(T, D, table):
    cols = T[table]
    names = list(cols)
    out = []
    for i in range(len(cols[names[0]])):
        r = {}
        for c in names:
            v = cols[c][i]
            pool = D.get(f"{table}.{c}")
            r[c] = pool[int(v)] if pool is not None else int(v)
        out.append(r)
    return out


def _d(text):
    return ref.day(text)


def _brute(T, D):
    L = _rows(T, D, "lineitem")
    O = {r["o_orderkey"]: r for r in _rows(T, D, "orders")}
    C = {r["c_custkey"]: r for r in _rows(T, D, "customer")}
    P = {r["p_partkey"]: r for r in _rows(T, D, "part")}
    S = {r["s_suppkey"]: r for r in _rows(T, D, "supplier")}
    N = {r["n_nationkey"]: r for r in _rows(T, D, "nation")}
    R = {r["r_regionkey"]: r for r in _rows(T, D, "region")}
    PS = {(r["ps_partkey"], r["ps_suppkey"]): r
          for r in _rows(T, D, "partsupp")}
    out = {}

    def rev(li):
        return li["l_extendedprice"] * (100 - li["l_discount"])

    g = {}
    for li in L:
        if li["l_shipdate"] <= _d("1998-09-02"):
            a = g.setdefault((li["l_returnflag"], li["l_linestatus"]),
                             [0, 0, 0, 0, 0, 0])
            a[0] += li["l_quantity"]
            a[1] += li["l_extendedprice"]
            a[2] += rev(li)
            a[3] += rev(li) * (100 + li["l_tax"])
            a[4] += li["l_discount"]
            a[5] += 1
    out["q1"] = [(f, s, ref.dec_text(a[0], 2), ref.dec_text(a[1], 2),
                  ref.dec_text(a[2], 4), ref.dec_text(a[3], 6),
                  float(Fraction(a[0], 100 * a[5])),
                  float(Fraction(a[1], 100 * a[5])),
                  float(Fraction(a[4], 100 * a[5])), a[5])
                 for (f, s), a in sorted(g.items())]
    g = {}
    for li in L:
        o = O[li["l_orderkey"]]
        c = C[o["o_custkey"]]
        if c["c_mktsegment"] == "BUILDING" and \
                o["o_orderdate"] < _d("1995-03-15") and \
                li["l_shipdate"] > _d("1995-03-15"):
            k = (li["l_orderkey"], o["o_orderdate"], o["o_shippriority"])
            g[k] = g.get(k, 0) + rev(li)
    top = sorted(g.items(), key=lambda kv: (-kv[1], kv[0][1], kv[0][0]))
    out["q3"] = [(k[0], ref.dec_text(v, 4),
                  str(datetime.date(1970, 1, 1) +
                      datetime.timedelta(days=k[1])), k[2])
                 for k, v in top[:10]]
    g = {}
    for li in L:
        o, s = O[li["l_orderkey"]], S[li["l_suppkey"]]
        c = C[o["o_custkey"]]
        n = N[s["s_nationkey"]]
        if c["c_nationkey"] == s["s_nationkey"] and \
                R[n["n_regionkey"]]["r_name"] == "ASIA" and \
                _d("1994-01-01") <= o["o_orderdate"] < _d("1995-01-01"):
            g[n["n_name"]] = g.get(n["n_name"], 0) + rev(li)
    out["q5"] = [(k, ref.dec_text(v, 4))
                 for k, v in sorted(g.items(), key=lambda kv: (-kv[1],
                                                               kv[0]))]
    tot = [li["l_extendedprice"] * li["l_discount"] for li in L
           if _d("1994-01-01") <= li["l_shipdate"] < _d("1995-01-01")
           and 5 <= li["l_discount"] <= 7 and li["l_quantity"] < 2400]
    out["q6"] = [(ref.dec_text(sum(tot), 4) if tot else None,)]
    g = {}
    for li in L:
        if "green" not in P[li["l_partkey"]]["p_name"]:
            continue
        ps = PS[(li["l_partkey"], li["l_suppkey"])]
        o = O[li["l_orderkey"]]
        n = N[S[li["l_suppkey"]]["s_nationkey"]]["n_name"]
        y = (datetime.date(1970, 1, 1) +
             datetime.timedelta(days=o["o_orderdate"])).year
        g[(n, y)] = g.get((n, y), 0) + rev(li) - \
            ps["ps_supplycost"] * li["l_quantity"]
    out["q9"] = [(n, float(y), ref.dec_text(v, 4)) for (n, y), v in
                 sorted(g.items(), key=lambda kv: (kv[0][0], -kv[0][1]))]
    g = {}
    for li in L:
        o = O[li["l_orderkey"]]
        if li["l_returnflag"] == "R" and \
                _d("1993-10-01") <= o["o_orderdate"] < _d("1994-01-01"):
            g[o["o_custkey"]] = g.get(o["o_custkey"], 0) + rev(li)
    top = sorted(g.items(), key=lambda kv: (-kv[1], kv[0]))[:20]
    out["q10"] = [(k, C[k]["c_name"], ref.dec_text(v, 4),
                   ref.dec_text(C[k]["c_acctbal"], 2),
                   N[C[k]["c_nationkey"]]["n_name"], C[k]["c_address"],
                   C[k]["c_phone"], C[k]["c_comment"]) for k, v in top]
    g = {}
    for li in L:
        if li["l_shipmode"] in ("MAIL", "SHIP") and \
                li["l_commitdate"] < li["l_receiptdate"] and \
                li["l_shipdate"] < li["l_commitdate"] and \
                _d("1994-01-01") <= li["l_receiptdate"] < _d("1995-01-01"):
            hi = O[li["l_orderkey"]]["o_orderpriority"] in ("1-URGENT",
                                                            "2-HIGH")
            a = g.setdefault(li["l_shipmode"], [0, 0])
            a[0 if hi else 1] += 1
    out["q12"] = [(k, a[0], a[1]) for k, a in sorted(g.items())]
    promo = total = 0
    for li in L:
        if _d("1995-09-01") <= li["l_shipdate"] < _d("1995-10-01"):
            total += rev(li)
            if P[li["l_partkey"]]["p_type"].startswith("PROMO"):
                promo += rev(li)
    out["q14"] = [(float(Fraction(100 * promo, total)) if total else None,)]
    return out


def test_reference_matches_brute_force(small):
    T, D = small
    brute = _brute(T, D)
    data = ref.Data(T, D)
    for q in ref.QUERIES:
        got = ref.evaluate(data, q)["rows"]
        assert got == brute[q], q


def _answers(data, q):
    return [tuple(None if v is None else
                  (repr(v) if isinstance(v, float) else str(v)) for v in r)
            for r in ref.evaluate(data, q)["rows"]]


class _Source:
    def __init__(self, qids):
        self.by_key = {q: ("", {"query": q}) for q in qids}


def _check(small, answers, control=False):
    T, D = small
    ops = [{"ok": True, "key": q, "answer": a} for q, a in answers.items()]
    return ref.check(ops, _Source(answers), {"tables": T, "dictionaries": D},
                     0, "all", control=control)[0]


def test_exact_answers_pass_and_faults_fail(small):
    T, D = small
    data = ref.Data(T, D)
    answers = {q: _answers(data, q) for q in ref.QUERIES}
    assert _check(small, answers) == {"wrong_answers": 0,
                                      "float_rel_err_max": 0.0}
    # one altered cent in Q5's revenue
    bad = dict(answers)
    row = list(bad["q5"][0])
    cents = int(row[1].replace(".", "")) + 100
    row[1] = ref.dec_text(cents, 4)
    bad["q5"] = [tuple(row)] + bad["q5"][1:]
    assert _check(small, bad)["wrong_answers"] > 0
    # one dropped row of Q1
    bad = dict(answers)
    bad["q1"] = bad["q1"][:-1]
    assert _check(small, bad)["wrong_answers"] > 0


def test_control_fails_float_rel_err_max(small):
    numbers = _check(small, {q: [] for q in ref.QUERIES}, control=True)
    assert numbers["float_rel_err_max"] > 1e-9
    assert numbers["wrong_answers"] > 0


def test_join_bytes_is_the_servers_count(tmp_path):
    from serenedb_tpu.engine import Database
    from serenedb_tpu.utils import metrics
    ds = gen.generate({"scale_factor": SF}, 1618033988, str(tmp_path))
    c = Database().connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_result_cache = off")
    for q, sql in _queries().items():
        before = metrics.DEVICE_JOIN_BYTES.value
        c.execute(sql)
        got = metrics.DEVICE_JOIN_BYTES.value - before
        assert got == ref.join_bytes(q, ds["tables"], ds["dictionaries"]), q
