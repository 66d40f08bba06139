"""A FROM list under a WHERE plans as a join graph (sql/planner.py
`_plan_join_graph`): TPC-H's comma-join text gives the same rows as the
same statements written with JOIN ... ON, no plan holds a cross join, the
comma form is no slower than the explicit one, and explicit, outer and
derived-table joins keep their semantics."""

import json
import os
import time

import pytest

from serenedb_tpu.engine import Database

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the 8 statements of benchmark/queries/tpch_8.json in JOIN ... ON form
EXPLICIT = {
    "q14": "SELECT 100.00 * sum(CASE WHEN p_type LIKE 'PROMO%' THEN "
           "l_extendedprice * (1 - l_discount) ELSE 0 END) / "
           "sum(l_extendedprice * (1 - l_discount)) AS promo_revenue "
           "FROM lineitem JOIN part ON l_partkey = p_partkey "
           "WHERE l_shipdate >= DATE '1995-09-01' "
           "AND l_shipdate < DATE '1995-10-01'",
    "q9": "SELECT nation, o_year, sum(amount) AS sum_profit FROM (SELECT "
          "n_name AS nation, extract(year FROM o_orderdate) AS o_year, "
          "l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity "
          "AS amount FROM lineitem JOIN part ON p_partkey = l_partkey "
          "JOIN supplier ON s_suppkey = l_suppkey JOIN partsupp ON "
          "ps_suppkey = l_suppkey AND ps_partkey = l_partkey JOIN orders "
          "ON o_orderkey = l_orderkey JOIN nation ON s_nationkey = "
          "n_nationkey WHERE p_name LIKE '%green%') AS profit "
          "GROUP BY nation, o_year ORDER BY nation, o_year DESC",
    "q6": None,                      # one table: the text is the same
    "q3": "SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS "
          "revenue, o_orderdate, o_shippriority FROM lineitem JOIN orders "
          "ON l_orderkey = o_orderkey JOIN customer ON c_custkey = "
          "o_custkey WHERE c_mktsegment = 'BUILDING' AND o_orderdate < "
          "DATE '1995-03-15' AND l_shipdate > DATE '1995-03-15' GROUP BY "
          "l_orderkey, o_orderdate, o_shippriority ORDER BY revenue DESC, "
          "o_orderdate, l_orderkey LIMIT 10",
    "q1": None,
    "q10": "SELECT c_custkey, c_name, sum(l_extendedprice * (1 - "
           "l_discount)) AS revenue, c_acctbal, n_name, c_address, c_phone, "
           "c_comment FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
           "JOIN customer ON c_custkey = o_custkey JOIN nation ON "
           "c_nationkey = n_nationkey WHERE o_orderdate >= DATE "
           "'1993-10-01' AND o_orderdate < DATE '1994-01-01' AND "
           "l_returnflag = 'R' GROUP BY c_custkey, c_name, c_acctbal, "
           "c_phone, n_name, c_address, c_comment ORDER BY revenue DESC, "
           "c_custkey LIMIT 20",
    "q5": "SELECT n_name, sum(l_extendedprice * (1 - l_discount)) AS revenue "
          "FROM lineitem JOIN orders ON l_orderkey = o_orderkey JOIN "
          "customer ON c_custkey = o_custkey JOIN supplier ON l_suppkey = "
          "s_suppkey AND c_nationkey = s_nationkey JOIN nation ON "
          "s_nationkey = n_nationkey JOIN region ON n_regionkey = "
          "r_regionkey WHERE r_name = 'ASIA' AND o_orderdate >= DATE "
          "'1994-01-01' AND o_orderdate < DATE '1995-01-01' GROUP BY n_name "
          "ORDER BY revenue DESC, n_name",
    "q12": "SELECT l_shipmode, sum(CASE WHEN o_orderpriority = '1-URGENT' OR "
           "o_orderpriority = '2-HIGH' THEN 1 ELSE 0 END) AS "
           "high_line_count, sum(CASE WHEN o_orderpriority <> '1-URGENT' "
           "AND o_orderpriority <> '2-HIGH' THEN 1 ELSE 0 END) AS "
           "low_line_count FROM orders JOIN lineitem ON o_orderkey = "
           "l_orderkey WHERE l_shipmode IN ('MAIL', 'SHIP') AND "
           "l_commitdate < l_receiptdate AND l_shipdate < l_commitdate AND "
           "l_receiptdate >= DATE '1994-01-01' AND l_receiptdate < DATE "
           "'1995-01-01' GROUP BY l_shipmode ORDER BY l_shipmode",
}


def _statements():
    with open(os.path.join(ROOT, "benchmark", "queries",
                           "tpch_8.json")) as f:
        return {s["id"]: s["sql"] for s in json.load(f)["statements"]}


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    from benchmark.datasets import tpch as gen
    work = tmp_path_factory.mktemp("tpch")
    ds = gen.generate({"scale_factor": 0.01}, 2718281828, str(work))
    c = Database().connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    c.execute("SET serene_result_cache = off")
    c.execute("SET serene_device = 'cpu'")
    return c


@pytest.mark.parametrize("qid", list(EXPLICIT))
def test_comma_and_explicit_forms_agree(tpch, qid):
    text = _statements()[qid]
    explicit = EXPLICIT[qid] or text
    assert tpch.execute(text).rows() == tpch.execute(explicit).rows()


@pytest.mark.parametrize("qid", list(EXPLICIT))
def test_no_cross_join_and_keys_shown(tpch, qid):
    plan = [r[0] for r in tpch.execute("EXPLAIN " + _statements()[qid])
            .rows()]
    joins = [ln for ln in plan if "HashJoin" in ln]
    assert not any("cross" in ln for ln in plan), plan
    assert all(" on (" in ln for ln in joins), plan
    # lineitem, the largest relation, is the probe of every join chain
    if joins:
        scans = [ln.strip() for ln in plan if ln.strip().startswith("Scan")]
        assert scans[0].startswith("Scan lineitem"), plan


def test_q5_cycle_edge_stays_above_the_joins(tpch):
    plan = "\n".join(r[0] for r in tpch.execute(
        "EXPLAIN " + _statements()["q5"]).rows())
    assert "c_nationkey = s_nationkey" not in plan
    assert "l_suppkey = s_suppkey" in plan
    assert "Filter" in plan


def _pair():
    c = Database().connect()
    c.execute("CREATE TABLE a (k INT, v INT)")
    c.execute("CREATE TABLE b (k INT, w INT)")
    c.execute("INSERT INTO a SELECT g, g % 7 FROM generate_series(1, 20000) g")
    c.execute("INSERT INTO b SELECT g, g % 5 FROM generate_series(1, 20000) g")
    c.execute("SET serene_result_cache = off")
    return c


def test_comma_join_as_fast_as_explicit():
    c = _pair()
    comma = "SELECT count(*), sum(v) FROM a, b WHERE a.k = b.k AND v > 2"
    expl = "SELECT count(*), sum(v) FROM a JOIN b ON a.k = b.k WHERE v > 2"
    assert c.execute(comma).rows() == c.execute(expl).rows() == \
        [(11428, 51426)]

    def best(q):
        out = []
        for _ in range(3):
            t0 = time.perf_counter()
            c.execute(q)
            out.append(time.perf_counter() - t0)
        return min(out)
    assert best(comma) <= 3 * best(expl) + 0.05


def test_explicit_outer_and_derived_joins_unchanged():
    c = Database().connect()
    c.execute("CREATE TABLE x (k INT, a TEXT)")
    c.execute("CREATE TABLE y (k INT, b TEXT)")
    c.execute("INSERT INTO x VALUES (1, 'p'), (2, 'q'), (3, 'r')")
    c.execute("INSERT INTO y VALUES (1, 'u'), (1, 'v'), (4, 'w')")
    assert c.execute("SELECT x.k, b FROM x LEFT JOIN y ON x.k = y.k "
                     "WHERE x.k < 3 ORDER BY x.k, b").rows() == \
        [(1, "u"), (1, "v"), (2, None)]
    assert c.execute("SELECT a, n FROM x, (SELECT k, count(*) AS n FROM y "
                     "GROUP BY k) AS t WHERE x.k = t.k").rows() == [("p", 2)]
    # no WHERE: still the cross product
    assert c.execute("SELECT count(*) FROM x, y").scalar() == 9
    assert c.execute("SELECT count(*) FROM x CROSS JOIN y WHERE "
                     "x.k = y.k").scalar() == 2
    # a predicate over both sides that is not an equality stays a filter
    assert c.execute("SELECT count(*) FROM x, y WHERE x.k < y.k").scalar() \
        == 3
    # a disconnected relation joins as a cross product
    assert c.execute("SELECT count(*) FROM x, y, x AS z WHERE x.k = y.k"
                     ).scalar() == 6
    plan = "\n".join(r[0] for r in c.execute(
        "EXPLAIN SELECT * FROM x, y WHERE x.k = y.k").rows())
    assert "HashJoin inner on (k = k)" in plan
    # SELECT * keeps FROM order whatever the join order
    assert c.execute("SELECT * FROM x, y WHERE x.k = y.k ORDER BY b"
                     ).rows() == [(1, "p", 1, "u"), (1, "p", 1, "v")]
