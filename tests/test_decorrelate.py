"""Subqueries flattened into joins (sql/decorrelate.py) on small hand-made
tables: each statement's flattened plan against the binder's per-row
substitution (the oracle: `flatten` stubbed out, so every subquery keeps
the path it took before) and against an answer written out by hand,
under the host tiers and under the device tier (`serene_device =
'tpu'`, exec/device_chain.py's reduction edges, on the CPU backend)."""

import pytest

from serenedb_tpu import errors
from serenedb_tpu.engine import Database
from serenedb_tpu.exec.plan import JoinNode
from serenedb_tpu.sql import planner as planner_mod
from serenedb_tpu.utils import metrics

SETUP = [
    "CREATE TABLE t (x INT)",
    "INSERT INTO t VALUES (1), (2), (3), (NULL)",
    "CREATE TABLE s (y INT)",
    "INSERT INTO s VALUES (2), (NULL)",
    "CREATE TABLE s2 (y INT)",
    "INSERT INTO s2 VALUES (2), (3)",
    "CREATE TABLE e (y INT)",
    "CREATE TABLE a (k INT, v INT)",
    "INSERT INTO a VALUES (1, 10), (2, 20), (3, 30), (4, 40)",
    "CREATE TABLE b (k INT, w INT)",
    "INSERT INTO b VALUES (1, 5), (1, 6), (1, 7), (3, 1), (3, NULL)",
    # lines of orders with their suppliers (TPC-H Q21's shape)
    "CREATE TABLE l (o INT, s INT, late BOOLEAN)",
    "INSERT INTO l VALUES (1, 10, true), (1, 10, false), (2, 10, true), "
    "(2, 20, false), (3, 10, true), (3, 20, true), (4, 30, true)",
    "CREATE TABLE p (pk INT, brand TEXT)",
    "INSERT INTO p VALUES (1, 'x'), (2, 'y'), (3, 'x')",
    "CREATE TABLE q (pk INT, qty DECIMAL(15,2))",
    "INSERT INTO q VALUES (1, 0.01), (1, 0.09), (2, 1.00), (2, 3.00), "
    "(3, 0.50), (3, 0.50)",
    # two tables whose product is 9M pairs
    "CREATE TABLE big1 (k INT)",
    "INSERT INTO big1 SELECT g FROM generate_series(1, 3000) g",
    "CREATE TABLE big2 (k INT)",
    "INSERT INTO big2 SELECT g FROM generate_series(1, 3000) g",
]

#: (name, statement, the answer written out by hand, sorted)
CASES = [
    ("not_in_set_with_null",
     "SELECT x FROM t WHERE x NOT IN (SELECT y FROM s)", []),
    ("not_in_set_without_null",
     "SELECT x FROM t WHERE x NOT IN (SELECT y FROM s2)", [(1,)]),
    ("in_null_operand",
     "SELECT x FROM t WHERE x IN (SELECT y FROM s2)", [(2,), (3,)]),
    ("in_empty", "SELECT x FROM t WHERE x IN (SELECT y FROM e)", []),
    ("not_in_empty_keeps_null",
     "SELECT x FROM t WHERE x NOT IN (SELECT y FROM e)",
     [(1,), (2,), (3,), (None,)]),
    ("not_exists_empty",
     "SELECT k FROM a WHERE NOT EXISTS (SELECT 1 FROM e WHERE e.y = a.k)",
     [(1,), (2,), (3,), (4,)]),
    ("scalar_empty",
     "SELECT k FROM a WHERE v > (SELECT max(w) FROM b WHERE b.k = a.k "
     "AND w > 100)", []),
    ("count_bug",
     "SELECT k FROM a WHERE 0 = (SELECT count(*) FROM b WHERE b.k = a.k)",
     [(2,), (4,)]),
    ("count_of_values",
     "SELECT k FROM a WHERE (SELECT count(w) FROM b WHERE b.k = a.k) = 1",
     [(3,)]),
    ("semi_emits_once",
     "SELECT k FROM a WHERE EXISTS (SELECT * FROM b WHERE b.k = a.k)",
     [(1,), (3,)]),
    ("anti",
     "SELECT k FROM a WHERE NOT EXISTS (SELECT * FROM b WHERE b.k = a.k)",
     [(2,), (4,)]),
    ("residual_exists",
     "SELECT o, s FROM l l1 WHERE EXISTS (SELECT * FROM l l2 WHERE "
     "l2.o = l1.o AND l2.s <> l1.s)",
     [(2, 10), (2, 20), (3, 10), (3, 20)]),
    ("residual_q21",
     "SELECT l1.o, l1.s FROM l l1 WHERE l1.late AND EXISTS (SELECT * "
     "FROM l l2 WHERE l2.o = l1.o AND l2.s <> l1.s) AND NOT EXISTS "
     "(SELECT * FROM l l3 WHERE l3.o = l1.o AND l3.s <> l1.s AND l3.late)",
     [(2, 10)]),
    ("correlated_over_a_from_list",
     "SELECT a.k, p.brand FROM a, p WHERE a.k = p.pk AND a.v = (SELECT "
     "max(v) FROM a a2 WHERE a2.k = p.pk)",
     [(1, "x"), (2, "y"), (3, "x")]),
    ("scalar_sum_against_a_column",
     "SELECT k FROM a WHERE v < (SELECT 2 * sum(w) FROM b WHERE b.k = a.k)",
     [(1,)]),
    ("in_over_grouped_having",
     "SELECT k FROM a WHERE k IN (SELECT k FROM b GROUP BY k "
     "HAVING count(*) > 2)", [(1,)]),
    ("uncorrelated_avg",
     "SELECT k FROM a WHERE v > (SELECT avg(v) FROM a)", [(3,), (4,)]),
]

#: (name, statement, answer, whether it runs per row): an EXISTS with no
#: equality key is no join. Uncorrelated, the binder computes it once a
#: statement; correlated by a non-equality alone, it runs per row
NOT_JOINS = [
    ("exists_empty",
     "SELECT x FROM t WHERE EXISTS (SELECT 1 FROM e)", [], False),
    ("exists_uncorrelated",
     "SELECT k FROM a WHERE EXISTS (SELECT 1 FROM s2)",
     [(1,), (2,), (3,), (4,)], False),
    ("not_exists_uncorrelated",
     "SELECT k FROM a WHERE NOT EXISTS (SELECT 1 FROM s2)", [], False),
    ("exists_residual_only",
     "SELECT k FROM a WHERE EXISTS (SELECT 1 FROM s2 WHERE s2.y > a.k)",
     [(1,), (2,)], True),
    ("exists_uncorrelated_over_a_product",
     "SELECT count(*) FROM big1 WHERE EXISTS (SELECT 1 FROM big2 "
     "WHERE big2.k > 2990)", [(3000,)], False),
    ("not_exists_residual_over_a_product",
     "SELECT count(*) FROM big1 WHERE big1.k > 2700 AND NOT EXISTS (SELECT "
     "1 FROM big2 WHERE big2.k > big1.k + 290)", [(291,)], True),
]

#: exact where float64 is not: avg(0.01, 0.09) = 0.05, and 0.2 * 0.05
#: is 0.01 exactly, so 0.01 < 0.2 * avg is false (a float reads
#: 0.010000000000000002 and says true)
TIE = ("SELECT q.pk, q.qty FROM q, p WHERE q.pk = p.pk AND q.qty < "
       "(SELECT 0.2 * avg(q2.qty) FROM q q2 WHERE q2.pk = p.pk)",
       [])
TIE_UNCORRELATED = ("SELECT pk FROM q WHERE qty * 1 < (SELECT 0.2 * avg(qty) "
                    "FROM q WHERE pk = 1)", [])


@pytest.fixture(scope="module")
def conn():
    c = Database().connect()
    for stmt in SETUP:
        c.execute(stmt)
    c.execute("SET serene_result_cache = off")
    return c


def _rows(c, sql):
    return sorted(c.execute(sql).rows(),
                  key=lambda r: tuple((v is None, v) for v in r))


def _per_row(monkeypatch, c, sql):
    """The answer of the binder's per-row substitution alone."""
    with monkeypatch.context() as m:
        m.setattr(planner_mod, "flatten",
                  lambda planner, plan, scope, nested:
                  (plan, scope, [], nested))
        return _rows(c, sql)


@pytest.mark.parametrize("device", ["cpu", "tpu"])
@pytest.mark.parametrize("name,sql,want", CASES, ids=[c[0] for c in CASES])
def test_flattened_equals_per_row_and_hand(conn, monkeypatch, device,
                                           name, sql, want):
    conn.execute(f"SET serene_device = '{device}'")
    flat0 = metrics.SUBQUERIES_FLATTENED.value
    per0 = metrics.SUBQUERIES_PER_ROW.value
    got = _rows(conn, sql)
    assert metrics.SUBQUERIES_FLATTENED.value > flat0
    assert metrics.SUBQUERIES_PER_ROW.value == per0
    assert got == sorted(want, key=lambda r: tuple((v is None, v)
                                                   for v in r))
    assert _per_row(monkeypatch, conn, sql) == got


@pytest.mark.parametrize("device", ["cpu", "tpu"])
@pytest.mark.parametrize("name,sql,want,per_row", NOT_JOINS,
                         ids=[c[0] for c in NOT_JOINS])
def test_an_exists_without_a_key_builds_no_pairs(conn, monkeypatch, device,
                                                name, sql, want, per_row):
    conn.execute(f"SET serene_device = '{device}'")

    def no_pairs(*a, **k):
        raise AssertionError("a keyless semi join paired every row")
    flat0 = metrics.SUBQUERIES_FLATTENED.value
    per0 = metrics.SUBQUERIES_PER_ROW.value
    with monkeypatch.context() as m:
        m.setattr(JoinNode, "_pairs", no_pairs)
        got = _rows(conn, sql)
    assert got == want
    assert metrics.SUBQUERIES_FLATTENED.value == flat0
    assert (metrics.SUBQUERIES_PER_ROW.value > per0) == per_row
    assert _per_row(monkeypatch, conn, sql) == got


@pytest.mark.parametrize("device", ["cpu", "tpu"])
@pytest.mark.parametrize("sql,want", [TIE, TIE_UNCORRELATED],
                         ids=["correlated", "uncorrelated"])
def test_a_tie_with_an_average_is_decided_exactly(conn, device, sql, want):
    conn.execute(f"SET serene_device = '{device}'")
    assert _rows(conn, sql) == want
    # one side of the tie moves by a cent: now it holds
    moved = sql.replace("q.qty <", "q.qty - 0.01 <").replace(
        "qty * 1 <", "qty - 0.01 <")
    assert len(_rows(conn, moved)) >= 1


@pytest.mark.parametrize("sql,want", [
    # an aggregate without GROUP BY is one row whatever matches
    ("SELECT k FROM a WHERE EXISTS (SELECT count(*) FROM b WHERE b.k = a.k)",
     [(1,), (2,), (3,), (4,)]),
    # a select list EXISTS drops still has to bind
    ("SELECT k FROM a WHERE EXISTS (SELECT nosuch FROM b WHERE b.k = a.k)",
     "42703"),
], ids=["exists_aggregate", "exists_bad_column"])
def test_what_does_not_flatten_keeps_the_per_row_path(conn, sql, want):
    conn.execute("SET serene_device = 'cpu'")
    per0 = metrics.SUBQUERIES_PER_ROW.value
    if isinstance(want, str):
        with pytest.raises(errors.SqlError) as e:
            conn.execute(sql)
        assert e.value.sqlstate == want
        return
    assert _rows(conn, sql) == want
    assert metrics.SUBQUERIES_PER_ROW.value > per0


def test_a_non_aggregate_scalar_of_two_rows_still_raises(conn):
    conn.execute("SET serene_device = 'cpu'")
    per0 = metrics.SUBQUERIES_PER_ROW.value
    with pytest.raises(errors.SqlError) as e:
        conn.execute("SELECT k FROM a WHERE v = (SELECT w FROM b "
                     "WHERE b.k = a.k)")
    assert e.value.sqlstate == "21000"
    assert metrics.SUBQUERIES_PER_ROW.value > per0


def test_explain_names_the_joins(conn):
    conn.execute("SET serene_device = 'cpu'")
    plan = "\n".join(r[0] for r in conn.execute(
        "EXPLAIN SELECT k FROM a WHERE EXISTS (SELECT 1 FROM b WHERE "
        "b.k = a.k) AND NOT EXISTS (SELECT 1 FROM l WHERE l.o = a.k) AND "
        "k NOT IN (SELECT y FROM s2) AND v > (SELECT sum(w) FROM b b2 "
        "WHERE b2.k = a.k)").rows())
    for word in ("SemiJoin", "AntiJoin", "MarkJoin", "HashJoin left",
                 "Aggregate groups=1"):
        assert word in plan, plan


def test_host_semi_joins_are_counted(conn):
    conn.execute("SET serene_device = 'cpu'")
    before = metrics.HOST_FLATTENED_JOINS.value
    conn.execute("SELECT k FROM a WHERE EXISTS (SELECT 1 FROM b "
                 "WHERE b.k = a.k)")
    assert metrics.HOST_FLATTENED_JOINS.value == before + 1


@pytest.mark.parametrize("sql,joins", [
    ("SELECT k FROM a WHERE v > (SELECT max(w) FROM b WHERE b.k = a.k)", 1),
    ("SELECT k FROM a WHERE k NOT IN (SELECT y FROM s2)", 1),
    # a join the user wrote is no flattened subquery
    ("SELECT a.k FROM a LEFT JOIN b ON a.k = b.k", 0),
], ids=["left_joined_aggregate", "mark", "user_left_join"])
def test_every_flattened_join_on_the_host_is_counted(conn, sql, joins):
    conn.execute("SET serene_device = 'cpu'")
    before = metrics.HOST_FLATTENED_JOINS.value
    conn.execute(sql)
    assert metrics.HOST_FLATTENED_JOINS.value == before + joins


@pytest.mark.parametrize("sql,want", [
    ("SELECT CASE WHEN k > 5 THEN (SELECT k FROM a) ELSE 0 END FROM a",
     [(0,)] * 4),
    ("SELECT coalesce(k, (SELECT w FROM b)) FROM a",
     [(1,), (2,), (3,), (4,)]),
    ("SELECT CASE WHEN k > 5 THEN (SELECT 1 / (k - k) FROM a WHERE k = 1) "
     "ELSE 0 END FROM a", [(0,)] * 4),
    ("SELECT k FROM a WHERE v = (SELECT k FROM a)", "21000"),
    ("SELECT k, (SELECT k FROM a) FROM a", "21000"),
], ids=["case_not_taken", "coalesce_not_reached", "error_not_reached",
        "where_two_rows", "select_two_rows"])
def test_an_uncorrelated_scalar_fails_only_where_evaluated(conn, sql, want):
    conn.execute("SET serene_device = 'cpu'")
    if isinstance(want, str):
        with pytest.raises(errors.SqlError) as e:
            conn.execute(sql)
        assert e.value.sqlstate == want
        return
    assert _rows(conn, sql) == want


@pytest.mark.parametrize("sql", [
    "SELECT k FROM a WHERE v > (SELECT max(w) FROM b)",
    "SELECT k, (SELECT max(w) FROM b) FROM a",
    "SELECT k FROM a WHERE v > (SELECT max(w) FROM b) + k",
], ids=["where", "select_list", "inside_an_expression"])
def test_an_uncorrelated_scalar_is_one_literal(conn, monkeypatch, sql):
    """Computed once, while binding: the plan holds its value."""
    from serenedb_tpu.exec import plan as plan_mod
    from serenedb_tpu.sql.expr import BoundFunc, BoundLiteral
    from serenedb_tpu.sql.parser import parse_one
    runs = []
    real = plan_mod.PlanNode.execute

    def execute(self, ctx):
        runs.append(self)
        return real(self, ctx)
    with monkeypatch.context() as m:
        m.setattr(plan_mod.PlanNode, "execute", execute)
        plan = conn._plan(parse_one(sql), [])
    assert len(runs) == 1
    exprs, nodes = [], [plan]
    while nodes:
        n = nodes.pop()
        nodes.extend(n.children())
        exprs += [getattr(n, "filter", None), getattr(n, "pred", None)]
        exprs += list(getattr(n, "exprs", []))
    found = [x for e in exprs if e is not None for x in e.walk()]
    assert any(isinstance(x, BoundLiteral) and x.value == 7 for x in found)
    assert not any(isinstance(x, BoundFunc) and x.name == "scalar_subquery"
                   for x in found)
