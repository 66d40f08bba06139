"""The copied plain reference against the repo's own host oracle at a
small size (the engine in-process, `serene_device = 'cpu'`), its CONTROL
coming out not correct, and the generated table keeping to what the
configuration says of it."""

import json
import os

import numpy as np
import pytest

from benchmark.datasets import hits as hits_gen
from benchmark.harness import correctness, traffic
from benchmark.references import sql_numpy
from benchmark.references.sql_numpy import Table, evaluate

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
QSETS = {"clickbench_15": 15, "clickbench_light": 12}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def hits(tmp_path_factory):
    from serenedb_tpu.engine import Database
    cfg = dict(_config("clickbench-hits"), rows=30_000)
    data = hits_gen.generate(cfg, 7, str(tmp_path_factory.mktemp("hits")))
    conn = Database().connect()
    conn.execute("SET serene_device = 'cpu'")
    for stmt in data["load"]:
        conn.execute(stmt)
    return cfg, data, conn


def _source(qset_name, data):
    qset = traffic.load_named("queries", qset_name)
    return traffic.make_source(
        qset, {"pick": "file_order", "clients": 1}, data, 7)


def _text(rows):
    return [tuple(None if v is None else
                  (repr(float(v)) if isinstance(v, float) else str(v))
                  for v in r) for r in rows]


@pytest.mark.parametrize("qset_name", sorted(QSETS))
def test_sql_reference_agrees_with_the_host_oracle(hits, qset_name):
    cfg, data, conn = hits
    src = _source(qset_name, data)
    table = Table(data["columns"], data["dictionaries"])
    assert len(src.statements) == QSETS[qset_name]
    for key, sql, spec in src.statements:
        got = _text(conn.execute(sql).rows())
        ok, err = sql_numpy.compare_rows(got, evaluate(table, spec))
        assert ok, f"{key}: engine {got[:3]} != reference"
        assert err <= cfg["limits"]["float_rel_err_max"], key


@pytest.mark.parametrize("qset_name", sorted(QSETS))
def test_sql_control_is_not_correct(hits, qset_name):
    cfg, data, _ = hits
    big = dict(data)
    # the control loses bits only once a sum passes 2^24: at this size
    # the column has to be widened for that
    big["columns"] = dict(data["columns"],
                          ResolutionWidth=data["columns"]["ResolutionWidth"]
                          * 1000)
    src = _source(qset_name, big)
    ops = [{"ok": True, "key": k, "answer": []} for k, _, _ in
           src.statements]
    numbers, n = sql_numpy.check(ops, src, big, 7, "all", control=True,
                                 cfg=cfg)
    numbers.update(correctness.window_numbers(ops, 0))
    correct, _ = correctness.judge(numbers, cfg["limits"])
    assert n == QSETS[qset_name] and not correct
    assert numbers["float_rel_err_max"] > 1e-9


def test_failed_operations_and_compiles_in_the_window_are_not_correct(hits):
    cfg, data, _ = hits
    src = _source("clickbench_light", data)
    table = Table(data["columns"], data["dictionaries"])
    ops = [{"ok": True, "key": k,
            "answer": _text(evaluate(table, spec)["rows"])}
           for k, _, spec in src.statements if k != "q19"]
    numbers, _ = sql_numpy.check(ops, src, data, 7, "all", cfg=cfg)
    good = dict(numbers, **correctness.window_numbers(ops, 0))
    assert correctness.judge(good, cfg["limits"])[0]
    # every statement errors: nothing is left to compare, and that is
    # not a pass
    dead = [{"ok": False, "key": k, "error": "boom"}
            for k, _, _ in src.statements]
    numbers, n = sql_numpy.check(dead, src, data, 7, "all", cfg=cfg)
    own = correctness.window_numbers(dead, 0)
    assert n == 0 and own["failed_ops"] == 12
    assert not correctness.judge({**numbers, **own}, cfg["limits"])[0]
    # a program built inside the window
    built = dict(numbers, **correctness.window_numbers(ops, 1))
    assert not correctness.judge(built, cfg["limits"])[0]
    with pytest.raises(ValueError):         # a number without a limit
        correctness.judge(numbers, cfg["limits"])


def test_the_fitted_tails_give_the_sources_rows_and_distinct_keys():
    fit, src = _config("clickbench-hits")["fitted"], hits_gen.SOURCE
    for col, total, distinct in (
            ("UserID", src["rows"], src["distinct_UserID"]),
            ("SearchPhrase", src["rows_with_SearchPhrase"],
             src["distinct_SearchPhrase"] - 1)):
        rows, keys = hits_gen.full_table(src[f"top_{col}_rows"],
                                         fit[col]["s"], fit[col]["n_keys"])
        assert rows == pytest.approx(total, rel=1e-4)
        assert keys == pytest.approx(distinct, rel=1e-3)
    adv = src["AdvEngineID_rows"]
    assert sum(adv.values()) == 630_500                  # Q1
    assert sum(k * v for k, v in adv.items()) == 7_280_088   # Q2
    w = hits_gen.WIDTHS
    assert sum(v * s for v, s in w) / sum(s for _, s in w) == \
        pytest.approx(src["avg_ResolutionWidth"], rel=2e-3)


def test_every_seed_gets_the_same_sizes_in_another_order(tmp_path):
    cfg = dict(_config("clickbench-hits"), rows=200_000)
    a = hits_gen.generate(cfg, 1, str(tmp_path))["columns"]
    b = hits_gen.generate(cfg, 2 ** 31 + 9, str(tmp_path))["columns"]
    assert not np.array_equal(a["UserID"], b["UserID"])
    for col in ("UserID", "SearchPhrase", "AdvEngineID"):
        ca = np.sort(np.unique(a[col], return_counts=True)[1])
        cb = np.sort(np.unique(b[col], return_counts=True)[1])
        assert np.array_equal(ca, cb), col
    for col in ("RegionID", "ResolutionWidth", "SearchEngineID"):
        assert a[col].min() == b[col].min() or col == "RegionID"
    # the source's own types go to the server (create.sql)
    load = hits_gen.generate(cfg, 1, str(tmp_path))["load"][0]
    for col, typ in cfg["columns"].items():
        assert f'"{col}" {typ}' in load
    # the ten most active users hold the source's share of the rows
    top = np.sort(np.unique(a["UserID"], return_counts=True)[1])[::-1][:2]
    assert list(top) == [round(29097 * 200_000 / 99_997_497),
                         round(25333 * 200_000 / 99_997_497)]
