"""Host DISTINCT aggregates on typed arrays (exec/plan.py): `_ScalarAcc`,
`_cpu_group_distinct` / `_distinct_pairs`, and the serial aggregate's
group codes from `morsel._group_codes`.

Every expectation is a plain python reference (sets, dicts) or, for the
float and string arguments that keep the object path, the answer the
parent commit gave."""

import math
import zlib

import numpy as np
import pytest

from serenedb_tpu import errors
from serenedb_tpu.columnar import dtypes as dt
from serenedb_tpu.columnar.column import Batch, Column
from serenedb_tpu.engine import Database
from serenedb_tpu.exec import morsel, plan
from serenedb_tpu.exec.plan import (AggregateNode, ExecContext, PlanNode,
                                    _ScalarAcc, _distinct_pairs)
from serenedb_tpu.exec.tables import DEFAULT_BATCH_ROWS
from serenedb_tpu.sql.binder import _agg_result_type
from serenedb_tpu.sql.expr import AggSpec, BoundColumn
from serenedb_tpu.utils import metrics as sdb_metrics
from serenedb_tpu.obs.export import prometheus_text

I64_MAX = (1 << 63) - 1


def _gauges():
    return (sdb_metrics.HOST_DISTINCT_SORTED.value,
            sdb_metrics.HOST_DISTINCT_OBJECTS.value)


class _Batches(PlanNode):
    """A child that yields the batches it was given, one by one."""

    def __init__(self, batches, names, types):
        self._batches = batches
        self.names = names
        self.types = types

    def batches(self, ctx):
        yield from self._batches


def _no_pylist(monkeypatch):
    def refuse(self):
        raise AssertionError("to_pylist() reached on the typed path")
    monkeypatch.setattr(Column, "to_pylist", refuse)


# -- scalar COUNT / SUM / AVG(DISTINCT x) over integer-like arguments -------

_TYPED = {
    # name: (type, batches of python values; None = NULL)
    "int16": (dt.SMALLINT, [[3, -7, None, 3, 32767], [-32768, 3, 9]]),
    "int32": (dt.INT, [[1 << 30, -(1 << 31), None], [(1 << 31) - 1, 1 << 30]]),
    "int64": (dt.BIGINT, [[5, None, 1 << 40, 5], [-(1 << 40), 5, 77]]),
    "int64_extremes": (dt.BIGINT, [[I64_MAX, -I64_MAX, 5], [I64_MAX, None]]),
    "int64_sum_past_int64": (dt.BIGINT, [[I64_MAX, I64_MAX - 1], [I64_MAX]]),
    "bool": (dt.BOOL, [[True, None, True], [False, True]]),
    "bool_one_value": (dt.BOOL, [[True, True]]),
    "date": (dt.DATE, [[18262, 18263, None], [18262]]),
    "timestamp": (dt.TIMESTAMP, [[1_600_000_000_000_000, None],
                                 [1_600_000_000_000_000, 1]]),
    "all_null": (dt.BIGINT, [[None, None], [None]]),
    "no_rows": (dt.BIGINT, []),
    "empty_batches": (dt.INT, [[], [4, 4], []]),
    "overlapping_batches": (dt.INT, [list(range(0, 60)), list(range(40, 90)),
                                     list(range(0, 90, 3)), [None, 89, 0]]),
}


def _funcs_for(t):
    return ("count", "sum", "avg") if t.is_numeric else ("count",)


@pytest.mark.parametrize("func,case", [
    (f, name) for name, (t, _b) in _TYPED.items() for f in _funcs_for(t)])
def test_scalar_distinct_typed(func, case, monkeypatch):
    t, raw = _TYPED[case]
    batches = [Batch(["x"], [Column.from_pylist(vs, t)]) for vs in raw]
    spec = AggSpec(func, BoundColumn(0, t, "x"), True,
                   _agg_result_type(func, t))
    node = AggregateNode(_Batches(batches, ["x"], [t]), [], [spec])
    distinct = {v for vs in raw for v in vs if v is not None}
    n, s = len(distinct), sum(distinct)
    want = {"count": n, "sum": s if n else None,
            "avg": s / n if n else None}[func]
    if want is not None and spec.type == dt.DOUBLE:
        want = float(want)          # SUM(bool) binds as DOUBLE
    before = _gauges()
    _no_pylist(monkeypatch)
    if func == "sum" and n and not -(1 << 63) <= s <= I64_MAX \
            and spec.type.is_integer:
        # as before this path existed: the exact sum does not fit BIGINT
        with pytest.raises(errors.SqlError, match="out of range"):
            node._cpu_aggregate(ExecContext())
        return
    out = node._cpu_aggregate(ExecContext())
    monkeypatch.undo()
    got = out.columns[0].to_pylist()
    assert got == [want]
    assert type(got[0]) is type(want)
    assert out.columns[0].type == spec.type
    after = _gauges()
    assert after[0] - before[0] == (1 if n else 0)
    assert after[1] == before[1]


def test_scalar_distinct_merges_while_it_streams(monkeypatch):
    """Past `_DISTINCT_MERGE_ROWS` waiting values the accumulator sorts
    what it holds: at no point does it keep more than the distinct values
    plus as many again (or the threshold) unmerged."""
    monkeypatch.setattr(plan, "_DISTINCT_MERGE_ROWS", 50)
    rng = np.random.default_rng(27)
    spec = AggSpec("count", BoundColumn(0, dt.BIGINT, "x"), True, dt.BIGINT)
    acc = _ScalarAcc(spec)
    seen = set()
    for _ in range(40):
        vals = rng.integers(-300, 300, 20)
        seen.update(vals.tolist())
        acc.update(Batch(["x"], [Column.from_numpy(vals)]))
        held = 0 if acc.uniq is None else len(acc.uniq)
        assert held <= len(seen)
        assert acc.pending_rows < max(50, held) + 20
    assert acc.uniq is not None and len(acc.uniq) > 50
    assert acc.result().to_pylist() == [len(seen)]


@pytest.mark.parametrize("func", ["count", "sum", "avg"])
def test_scalar_distinct_filter(func, monkeypatch):
    t = dt.INT
    x = [1, 2, 2, None, 9, 9, 4]
    keep = [True, True, False, True, None, True, False]
    b = Batch(["x", "p"], [Column.from_pylist(x, t),
                           Column.from_pylist(keep, dt.BOOL)])
    spec = AggSpec(func, BoundColumn(0, t, "x"), True,
                   _agg_result_type(func, t),
                   filter=BoundColumn(1, dt.BOOL, "p"))
    _no_pylist(monkeypatch)
    out = AggregateNode(_Batches([b, b], ["x", "p"], [t, dt.BOOL]),
                        [], [spec])._cpu_aggregate(ExecContext())
    monkeypatch.undo()
    distinct = {v for v, k in zip(x, keep) if k and v is not None}
    assert distinct == {1, 2, 9}
    want = {"count": 3, "sum": 12, "avg": 4.0}[func]
    assert out.columns[0].to_pylist() == [want]


# -- float and string arguments keep the object path and its answers -------

@pytest.fixture(scope="module")
def mixed():
    db = Database()
    c = db.connect()
    c.execute("SET serene_device = 'cpu'")
    c.execute("CREATE TABLE t (a BIGINT, b DOUBLE, s TEXT, d DATE, "
              "f BOOLEAN, g INT, h SMALLINT)")
    c.execute(
        "INSERT INTO t VALUES "
        "(9223372036854775807, 'NaN', 'x', '2020-01-01', true, 1, 1), "
        "(9223372036854775806, 'NaN', 'y', '2020-01-02', false, 1, 2), "
        "(-9223372036854775807, -0.0, 'x', '2020-01-01', NULL, 2, NULL), "
        "(NULL, 0.0, NULL, NULL, true, NULL, 3), "
        "(9223372036854775807, 1.5, 'z', '2020-01-02', false, 2, 3)")
    return c


def _same(got, want):
    if isinstance(want, float) and math.isnan(want):
        return isinstance(got, float) and math.isnan(got)
    return got == want and type(got) is type(want)


#: (statement, the parent commit's answer, (sorted, objects) it moves)
_MIXED = {
    # two NaN are two values (NaN ≠ NaN in a python set), -0.0 and 0.0 one
    "count_float": ("SELECT COUNT(DISTINCT b) FROM t", [(4,)], (0, 1)),
    "sum_float": ("SELECT SUM(DISTINCT b) FROM t", [(math.nan,)], (0, 1)),
    "avg_float": ("SELECT AVG(DISTINCT b) FROM t", [(math.nan,)], (0, 1)),
    "count_string": ("SELECT COUNT(DISTINCT s) FROM t", [(3,)], (0, 1)),
    "count_date": ("SELECT COUNT(DISTINCT d) FROM t", [(2,)], (1, 0)),
    "count_bool": ("SELECT COUNT(DISTINCT f) FROM t", [(2,)], (1, 0)),
    "sum_bool": ("SELECT SUM(DISTINCT f) FROM t", [(1.0,)], (1, 0)),
    "avg_bool": ("SELECT AVG(DISTINCT f) FROM t", [(0.5,)], (1, 0)),
    "count_int64": ("SELECT COUNT(DISTINCT a) FROM t", [(3,)], (1, 0)),
    "sum_int64": ("SELECT SUM(DISTINCT a) FROM t",
                  [(9223372036854775806,)], (1, 0)),
    "avg_int64": ("SELECT AVG(DISTINCT a) FROM t",
                  [(3.0744573456182584e+18,)], (1, 0)),
    "filter": ("SELECT COUNT(DISTINCT a) FILTER (WHERE g = 1), "
               "SUM(DISTINCT h) FILTER (WHERE g = 2) FROM t",
               [(2, 3)], (2, 0)),
    "no_rows": ("SELECT COUNT(DISTINCT a), SUM(DISTINCT a), "
                "AVG(DISTINCT a) FROM t WHERE g = 99",
                [(0, None, None)], (0, 0)),
    "all_null": ("SELECT COUNT(DISTINCT a), SUM(DISTINCT a), "
                 "AVG(DISTINCT a) FROM t WHERE a IS NULL",
                 [(0, None, None)], (0, 0)),
    # grouped: the int64 SUM wraps as the grouped path always did
    "grouped": ("SELECT g, COUNT(DISTINCT a), SUM(DISTINCT a), "
                "AVG(DISTINCT a), COUNT(DISTINCT b), SUM(DISTINCT b), "
                "COUNT(DISTINCT s), COUNT(DISTINCT f), SUM(DISTINCT h) "
                "FROM t GROUP BY g",
                [(1, 2, -3, 9.223372036854776e+18, 2, math.nan, 2, 2, 3),
                 (2, 2, 0, 0.0, 2, 1.5, 2, 1, 3),
                 (None, 0, None, None, 1, 0.0, 0, 1, 3)], (5, 3)),
}


@pytest.mark.parametrize("case", list(_MIXED))
def test_distinct_answers_and_paths(mixed, case):
    sql, want, moved = _MIXED[case]
    before = _gauges()
    got = mixed.execute(sql).rows()
    after = _gauges()
    assert len(got) == len(want)
    for grow, wrow in zip(got, want):
        assert len(grow) == len(wrow)
        assert all(_same(g, w) for g, w in zip(grow, wrow)), (got, want)
    assert (after[0] - before[0], after[1] - before[1]) == moved


# -- grouped DISTINCT against a brute-force reference ------------------------

def _pairs_case(name):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    n = 4000
    g = 37
    vc = rng.integers(0, g, n).astype(np.int32)
    if name == "wide_int64":        # span x groups passes 63 bits: argsort
        pool = rng.integers(-(1 << 62), 1 << 62, 300, dtype=np.int64)
        vals = pool[rng.integers(0, 300, n)]
    elif name == "extremes_int64":
        vals = rng.choice(np.array([I64_MAX, -I64_MAX - 1, 0, -1, 1],
                                   np.int64), n)
    elif name == "small_int16":     # packs directly
        vals = rng.integers(-50, 50, n).astype(np.int16)
    elif name == "negative_int32":
        vals = rng.integers(-(1 << 31), -(1 << 31) + 90, n).astype(np.int32)
    elif name == "bool":
        vals = rng.random(n) < 0.5
    elif name == "one_value":
        vals = np.full(n, 7, np.int64)
    elif name == "one_row":
        vc, vals, g = vc[:1], np.array([I64_MAX], np.int64), 37
    elif name == "one_group":
        vc = np.zeros(n, np.int32)
        g = 1
        vals = rng.integers(0, 1 << 62, n, dtype=np.int64)
    elif name == "many_groups":     # more groups than a uint16 holds
        g = 70_000
        vc = rng.integers(0, g, n).astype(np.int32)
        vals = rng.integers(0, 1 << 62, 50, dtype=np.int64)[
            rng.integers(0, 50, n)]
    return vc, vals, g


# wide values choose between partitioning by group and ranking the values
# by rows per group: each case under the choice the code makes and under
# both forced
@pytest.mark.parametrize("rows_per_group", [None, 0, 1 << 40],
                         ids=["as_chosen", "partition", "rank"])
@pytest.mark.parametrize("name", [
    "wide_int64", "extremes_int64", "small_int16", "negative_int32", "bool",
    "one_value", "one_row", "one_group", "many_groups"])
def test_distinct_pairs_against_sets(name, rows_per_group, monkeypatch):
    if rows_per_group is not None:
        monkeypatch.setattr(plan, "_PARTITION_ROWS_PER_GROUP",
                            rows_per_group)
    vc, vals, g = _pairs_case(name)
    want = sorted({(int(c), v) for c, v in zip(vc.tolist(), vals.tolist())})
    uc, uv = _distinct_pairs(vc, vals, g, True)
    assert uv.dtype == vals.dtype
    assert list(zip(uc.tolist(), uv.tolist())) == want
    uc2, none = _distinct_pairs(vc, vals, g, False)
    assert none is None and uc2.tolist() == uc.tolist()


@pytest.fixture(scope="module")
def grouped():
    rng = np.random.default_rng(2027)
    n = DEFAULT_BATCH_ROWS + 5_000      # more than one scan batch
    region = rng.zipf(1.4, n).clip(max=300).astype(np.int32) - 20
    user = (rng.integers(1, 3000, n, dtype=np.int64) - 1500) * (1 << 40)
    # span x groups passes 63 bits: ranks by argsort, not by subtraction
    wide = rng.integers(-(1 << 62), 1 << 62, 500, dtype=np.int64)[
        rng.integers(0, 500, n)]
    small = rng.integers(-5, 40, n).astype(np.int16)
    flag = rng.random(n) < 0.3
    day = rng.integers(18000, 18030, n).astype(np.int32)
    null_v = rng.random(n) < 0.1
    null_k = rng.random(n) < 0.05
    cols = {
        "k": Column(dt.INT, region, ~null_k),
        "u": Column(dt.BIGINT, user, ~null_v),
        "w": Column(dt.BIGINT, wide, ~null_v),
        "s": Column(dt.SMALLINT, small, ~null_v),
        "f": Column(dt.BOOL, flag, ~null_v),
        "d": Column(dt.DATE, day, ~null_v),
    }
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE g (k INT, u BIGINT, w BIGINT, s SMALLINT, "
              "f BOOLEAN, d DATE)")
    db.schemas["main"].tables["g"].replace(
        Batch(list(cols), list(cols.values())))
    c.execute("SET serene_result_cache = off")
    py = {name: [v if ok else None for v, ok in
                 zip(col.data.tolist(), col.valid_mask().tolist())]
          for name, col in cols.items()}
    return c, py


def _group_sets(py, arg, where=None):
    groups = {}
    for i, k in enumerate(py["k"]):
        s = groups.setdefault(k, set())
        v = py[arg][i]
        if v is not None and (where is None or where(i)):
            s.add(v)
    # valid keys ascending, the NULL key last
    return sorted(groups.items(), key=lambda kv: (kv[0] is None, kv[0] or 0))


@pytest.mark.parametrize("arg,funcs", [
    ("u", ("count", "sum", "avg")), ("w", ("count", "sum", "avg")),
    ("s", ("count", "sum", "avg")),
    ("f", ("count", "sum", "avg")), ("d", ("count",))])
def test_grouped_distinct_against_sets(grouped, arg, funcs, monkeypatch):
    c, py = grouped
    c.execute("SET serene_device = 'cpu'")
    sel = ", ".join(f"{f.upper()}(DISTINCT {arg})" for f in funcs)
    before = _gauges()
    _no_pylist(monkeypatch)
    out = c.execute(f"SELECT k, {sel} FROM g GROUP BY k ORDER BY k")
    monkeypatch.undo()
    got = out.rows()
    after = _gauges()
    want = []
    for k, s in _group_sets(py, arg):
        row = [k]
        for f in funcs:
            if f == "count":
                row.append(len(s))
            elif not s:
                row.append(None)
            elif f == "sum" and arg == "f":
                row.append(float(sum(s)))       # SUM(bool) is DOUBLE
            elif f == "sum":
                # the grouped int64 sum wraps, as it always did
                row.append((sum(s) + (1 << 63)) % (1 << 64) - (1 << 63))
            else:
                row.append(sum(s) / len(s))
        want.append(tuple(row))
    assert len(got) == len(want)
    for grow, wrow in zip(got, want):
        assert grow[0] == wrow[0]
        for g_, w in zip(grow[1:], wrow[1:]):
            if isinstance(w, float):
                # float64 accumulation of up to 500 values near 2^62
                assert g_ == pytest.approx(w, rel=1e-12, abs=2.0 ** 22)
            else:
                assert g_ == w and type(g_) is type(w)
    assert (after[0] - before[0], after[1] - before[1]) == (len(funcs), 0)


def test_grouped_distinct_filter(grouped):
    c, py = grouped
    c.execute("SET serene_device = 'cpu'")
    got = c.execute("SELECT k, COUNT(DISTINCT u) FILTER (WHERE s > 10) "
                    "FROM g GROUP BY k ORDER BY k").rows()
    want = [(k, len(s)) for k, s in _group_sets(
        py, "u", lambda i: py["s"][i] is not None and py["s"][i] > 10)]
    assert got == want


# -- the serial aggregate: the same rows in the same order whichever way
# -- `_group_codes` coded the keys -------------------------------------------

def _key_case(name):
    """→ (key columns, value column) of one batch."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    n = 600
    cap = morsel._DIRECT_SPACE_CAP
    v = Column.from_numpy(rng.integers(-9, 9, n).astype(np.int64))

    def nulls(col, share=0.1):
        return Column(col.type, col.data, rng.random(len(col)) >= share,
                      col.dictionary)
    if name == "negative_int":
        keys = [Column.from_numpy(rng.integers(-40, 5, n).astype(np.int32))]
    elif name == "null_keys":
        keys = [nulls(Column.from_numpy(
            rng.integers(3, 30, n).astype(np.int64)))]
    elif name == "all_null_keys":
        keys = [nulls(Column.from_numpy(np.zeros(n, np.int32)), 1.1)]
    elif name == "bool":
        keys = [nulls(Column.from_numpy(rng.random(n) < 0.4))]
    elif name == "string_codes":
        keys = [nulls(Column.from_pylist(
            [f"w{int(i)}" for i in rng.integers(0, 25, n)]))]
    elif name == "all_null_strings":
        keys = [Column.from_pylist([None] * n, dt.VARCHAR)]
    elif name == "one_group":
        keys = [Column.from_numpy(np.full(n, 12, np.int16))]
    elif name == "one_row":
        keys = [Column.from_numpy(np.array([-3], np.int64))]
        v = Column.from_numpy(np.array([4], np.int64))
    elif name == "no_rows":
        keys = [Column.from_numpy(np.empty(0, np.int64))]
        v = Column.from_numpy(np.empty(0, np.int64))
    elif name == "span_under_cap":
        # slots = span + 1 (NULL) = the cap exactly: the direct plan
        k = rng.integers(0, cap - 1, n).astype(np.int32)
        k[:2] = (0, cap - 2)
        keys = [Column.from_numpy(k)]
    elif name == "span_over_cap":
        # one more: falls through to factorize_keys
        k = rng.integers(0, cap, n).astype(np.int32)
        k[:2] = (0, cap - 1)
        keys = [Column.from_numpy(k)]
    elif name == "int64_extremes":
        keys = [Column.from_numpy(rng.choice(
            np.array([I64_MAX, -I64_MAX - 1, 0], np.int64), n))]
    elif name == "float_key":
        keys = [nulls(Column.from_numpy(
            rng.choice(np.array([1.5, -2.0, 0.25, 7.0]), n)))]
    elif name == "two_keys":
        keys = [nulls(Column.from_numpy(
                    rng.integers(-3, 4, n).astype(np.int16))),
                nulls(Column.from_pylist(
                    [f"p{int(i)}" for i in rng.integers(0, 6, n)]))]
    elif name == "two_keys_over_cap":
        keys = [Column.from_numpy(rng.integers(0, 300, n).astype(np.int32)),
                Column.from_numpy(rng.integers(0, 300, n).astype(np.int32))]
    return keys, v


_KEY_CASES = [
    "negative_int", "null_keys", "all_null_keys", "bool", "string_codes",
    "all_null_strings", "one_group", "one_row", "no_rows", "span_under_cap",
    "span_over_cap", "int64_extremes", "float_key", "two_keys",
    "two_keys_over_cap"]

#: which cases the direct plan codes (the others fall through)
_DIRECT = {"negative_int", "null_keys", "all_null_keys", "string_codes",
           "all_null_strings", "one_group", "one_row", "span_under_cap",
           "two_keys"}


def _serial_rows(keys, v, pieces=3):
    names = [f"k{i}" for i in range(len(keys))] + ["v"]
    cols = keys + [v]
    n = len(v)
    cuts = sorted({0, n} | {n * i // pieces for i in range(1, pieces)})
    batches = [Batch(names, [c.slice(a, b) for c in cols])
               for a, b in zip(cuts, cuts[1:])] if n else \
        [Batch(names, cols)]
    types = [c.type for c in cols]
    vi = len(keys)
    aggs = [AggSpec("count_star", None, False, dt.BIGINT),
            AggSpec("sum", BoundColumn(vi, dt.BIGINT, "v"), False, dt.BIGINT),
            AggSpec("count", BoundColumn(vi, dt.BIGINT, "v"), True,
                    dt.BIGINT),
            AggSpec("min", BoundColumn(vi, dt.BIGINT, "v"), False,
                    dt.BIGINT)]
    node = AggregateNode(
        _Batches(batches, names, types),
        [BoundColumn(i, k.type, f"k{i}") for i, k in enumerate(keys)], aggs)
    out = node._cpu_aggregate(ExecContext())
    return list(zip(*(c.to_pylist() for c in out.columns))), \
        [c.type for c in out.columns]


@pytest.mark.parametrize("name", _KEY_CASES)
def test_serial_aggregate_same_rows_either_coding(name, monkeypatch):
    keys, v = _key_case(name)
    calls = []
    real = morsel._direct_codes
    monkeypatch.setattr(morsel, "_direct_codes",
                        lambda *a: calls.append(1) or real(*a))
    got, got_types = _serial_rows(keys, v)
    assert bool(calls) == (name in _DIRECT)
    # the parent's coding: every key through factorize_keys
    monkeypatch.setattr(morsel, "_direct_key_plan", lambda key_cols: None)
    want, want_types = _serial_rows(keys, v)
    assert got == want
    assert got_types == want_types
    # and both against plain python: valid keys ascending, NULL last
    kv = [k.to_pylist() for k in keys]
    vv = v.to_pylist()
    groups = {}
    for i in range(len(vv)):
        groups.setdefault(tuple(k[i] for k in kv), []).append(vv[i])
    ref = [key + (len(vs), sum(vs), len(set(vs)), min(vs))
           for key, vs in sorted(
               groups.items(),
               key=lambda kv_: tuple((x is None, x if x is not None else 0)
                                     for x in kv_[0]))]
    assert got == ref


# -- the counters, through SQL and on /metrics -----------------------------

def test_counters_by_statement_shape_and_on_metrics():
    rng = np.random.default_rng(4)
    n = DEFAULT_BATCH_ROWS + 1_000
    user = rng.integers(0, 1 << 62, n // 2, dtype=np.int64)[
        rng.integers(0, n // 2, n)]
    db = Database()
    c = db.connect()
    c.execute('CREATE TABLE hits ("UserID" BIGINT, "RegionID" INT, '
              '"Score" DOUBLE)')
    db.schemas["main"].tables["hits"].replace(Batch.from_pydict({
        "UserID": Column.from_numpy(user),
        "RegionID": Column.from_numpy((user % 9000).astype(np.int32)),
        "Score": Column.from_numpy((user % 1000) / 8.0)}))
    c.execute("SET serene_result_cache = off")
    b0 = _gauges()
    q4 = c.execute('SELECT COUNT(DISTINCT "UserID") FROM hits').rows()
    b1 = _gauges()
    q8 = c.execute('SELECT "RegionID", COUNT(DISTINCT "UserID") AS u '
                   'FROM hits GROUP BY "RegionID" ORDER BY u DESC, '
                   '"RegionID" LIMIT 10').rows()
    b2 = _gauges()
    fl = c.execute('SELECT COUNT(DISTINCT "Score") FROM hits').rows()
    b3 = _gauges()
    assert q4 == [(len(set(user.tolist())),)]
    per_region = {}
    for u in set(user.tolist()):
        per_region[u % 9000] = per_region.get(u % 9000, 0) + 1
    assert q8 == sorted(per_region.items(), key=lambda r: (-r[1], r[0]))[:10]
    assert fl == [(len(set(((user % 1000) / 8.0).tolist())),)]
    assert (b1[0] - b0[0], b1[1] - b0[1]) == (1, 0)
    assert (b2[0] - b1[0], b2[1] - b1[1]) == (1, 0)
    assert (b3[0] - b2[0], b3[1] - b2[1]) == (0, 1)
    text = prometheus_text()
    values = {}
    for line in text.splitlines():
        name, _, value = line.partition(" ")
        if name in ("serenedb_host_distinct_sorted",
                    "serenedb_host_distinct_objects"):
            values[name] = int(value)
    assert values == {"serenedb_host_distinct_sorted": b3[0],
                      "serenedb_host_distinct_objects": b3[1]}
    for name in values:
        assert f"# HELP {name} " in text
