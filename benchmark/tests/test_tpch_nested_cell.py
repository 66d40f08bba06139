"""The `tpch.nested_c1` cell's own parts, on the CPU at a small scale
factor:

- the reference (references/tpch_nested_numpy.py) against a brute-force
  evaluation of each subquery as written, over Python dicts, row by row;
- its comparison catches one altered cent, one dropped row and one added
  row, and the control (float32 money accumulators) comes out not
  correct;
- `join_bytes` is what the server's `DeviceJoinBytes` counts for each
  statement.
"""

import datetime
import json
import os
from fractions import Fraction

import pytest

from benchmark.datasets import tpch as gen
from benchmark.references import tpch_nested_numpy as ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SF = 0.01


def _queries():
    with open(os.path.join(BENCH, "queries", "tpch_nested.json")) as f:
        return {s["id"]: s["sql"] for s in json.load(f)["statements"]}


@pytest.fixture(scope="module")
def small():
    return gen.make(SF, 6022140857)


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
    O = _rows(T, D, "orders")
    C = _rows(T, D, "customer")
    P = {r["p_partkey"]: r for r in _rows(T, D, "part")}
    S = {r["s_suppkey"]: r for r in _rows(T, D, "supplier")}
    N = {r["n_nationkey"]: r for r in _rows(T, D, "nation")}
    R = {r["r_regionkey"]: r for r in _rows(T, D, "region")}
    PS = _rows(T, D, "partsupp")
    lines_of: dict = {}
    for li in L:
        lines_of.setdefault(li["l_orderkey"], []).append(li)
    out = {}

    def region_of(s):
        return R[N[S[s]["s_nationkey"]]["n_regionkey"]]["r_name"]
    rows = []
    for ps in PS:
        p = P[ps["ps_partkey"]]
        if not (p["p_size"] == 15 and p["p_type"].endswith("BRASS") and
                region_of(ps["ps_suppkey"]) == "EUROPE"):
            continue
        least = min(x["ps_supplycost"] for x in PS
                    if x["ps_partkey"] == p["p_partkey"] and
                    region_of(x["ps_suppkey"]) == "EUROPE")
        if ps["ps_supplycost"] == least:
            s = S[ps["ps_suppkey"]]
            rows.append((s["s_acctbal"], s["s_name"],
                         N[s["s_nationkey"]]["n_name"], p["p_partkey"],
                         p["p_mfgr"], s["s_address"], s["s_phone"],
                         s["s_comment"]))
    rows.sort(key=lambda r: (-r[0], r[2], r[1], r[3]))
    out["q2"] = [(ref.dec_text(r[0], 2),) + r[1:] for r in rows[:100]]

    forest = {k for k, p in P.items() if p["p_name"].startswith("forest")}
    qty: dict = {}
    for li in L:
        if _d("1994-01-01") <= li["l_shipdate"] < _d("1995-01-01"):
            k = (li["l_partkey"], li["l_suppkey"])
            qty[k] = qty.get(k, 0) + li["l_quantity"]
    supp = {ps["ps_suppkey"] for ps in PS if ps["ps_partkey"] in forest and
            (ps["ps_partkey"], ps["ps_suppkey"]) in qty and
            Fraction(ps["ps_availqty"]) >
            Fraction(1, 2) * Fraction(qty[(ps["ps_partkey"],
                                           ps["ps_suppkey"])], 100)}
    out["q20"] = sorted((s["s_name"], s["s_address"]) for k, s in S.items()
                        if k in supp and
                        N[s["s_nationkey"]]["n_name"] == "CANADA")

    by_part: dict = {}
    for li in L:
        by_part.setdefault(li["l_partkey"], []).append(li["l_quantity"])
    total = None
    for li in L:
        p = P[li["l_partkey"]]
        if p["p_brand"] == "Brand#23" and p["p_container"] == "MED BOX":
            qs = by_part[li["l_partkey"]]
            if Fraction(li["l_quantity"]) < \
                    Fraction(1, 5) * Fraction(sum(qs), len(qs)):
                total = (total or 0) + li["l_extendedprice"]
    out["q17"] = [(None if total is None else
                   float(Fraction(total, 700)),)]

    big = {k for k, ls in lines_of.items()
           if sum(x["l_quantity"] for x in ls) > 30000}
    cust = {r["c_custkey"]: r for r in C}
    rows = []
    for o in O:
        if o["o_orderkey"] in big:
            c = cust[o["o_custkey"]]
            rows.append((c["c_name"], c["c_custkey"], o["o_orderkey"],
                         o["o_orderdate"], o["o_totalprice"],
                         sum(x["l_quantity"]
                             for x in lines_of[o["o_orderkey"]])))
    rows.sort(key=lambda r: (-r[4], r[3], r[2]))
    out["q18"] = [(r[0], r[1], r[2], str(datetime.date(1970, 1, 1) +
                                         datetime.timedelta(days=r[3])),
                   ref.dec_text(r[4], 2), ref.dec_text(r[5], 2))
                  for r in rows[:100]]

    orders = {o["o_orderkey"]: o for o in O}
    wait: dict = {}
    for l1 in L:
        s = S[l1["l_suppkey"]]
        if orders[l1["l_orderkey"]]["o_orderstatus"] != "F" or \
                l1["l_receiptdate"] <= l1["l_commitdate"] or \
                N[s["s_nationkey"]]["n_name"] != "SAUDI ARABIA":
            continue
        mates = lines_of[l1["l_orderkey"]]
        if any(l2["l_suppkey"] != l1["l_suppkey"] for l2 in mates) and \
                not any(l3["l_suppkey"] != l1["l_suppkey"] and
                        l3["l_receiptdate"] > l3["l_commitdate"]
                        for l3 in mates):
            wait[s["s_name"]] = wait.get(s["s_name"], 0) + 1
    out["q21"] = sorted(wait.items(), key=lambda kv: (-kv[1], kv[0]))[:100]

    codes = ("13", "31", "23", "29", "30", "18", "17")
    pos = [c["c_acctbal"] for c in C
           if c["c_phone"][:2] in codes and c["c_acctbal"] > 0]
    avg = Fraction(sum(pos), len(pos))
    with_orders = {o["o_custkey"] for o in O}
    g: dict = {}
    for c in C:
        if c["c_phone"][:2] in codes and c["c_acctbal"] > avg and \
                c["c_custkey"] not in with_orders:
            a = g.setdefault(c["c_phone"][:2], [0, 0])
            a[0] += 1
            a[1] += c["c_acctbal"]
    out["q22"] = [(k, a[0], ref.dec_text(a[1], 2))
                  for k, a in sorted(g.items())]

    g = {}
    for o in O:
        if _d("1993-07-01") <= o["o_orderdate"] < _d("1993-10-01") and \
                any(x["l_commitdate"] < x["l_receiptdate"]
                    for x in lines_of.get(o["o_orderkey"], [])):
            g[o["o_orderpriority"]] = g.get(o["o_orderpriority"], 0) + 1
    out["q4"] = sorted(g.items())
    return out


def test_reference_matches_brute_force(small):
    T, D = small
    brute = _brute(T, D)
    data = ref.Data(T, D)
    for q in ref.QUERIES:
        got = ref.evaluate(data, q)["rows"]
        assert got == brute[q], q
        assert got or q == "q18", q


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
    # one altered cent in Q22's total balance
    bad = dict(answers)
    row = list(bad["q22"][0])
    row[2] = ref.dec_text(int(row[2].replace(".", "")) + 1, 2)
    bad["q22"] = [tuple(row)] + bad["q22"][1:]
    assert _check(small, bad)["wrong_answers"] > 0
    # one dropped row of Q4
    bad = dict(answers)
    bad["q4"] = bad["q4"][:-1]
    assert _check(small, bad)["wrong_answers"] > 0
    # one added row of Q21
    bad = dict(answers)
    bad["q21"] = bad["q21"] + [("Supplier#999999999", "1")]
    assert _check(small, bad)["wrong_answers"] > 0


def test_control_is_not_correct():
    """At SF 0.05 a country code's balances pass float32's 2^24 cents."""
    numbers = _check(gen.make(0.05, 6022140857),
                     {q: [] for q in ref.QUERIES}, control=True)
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
