"""TPC-H's nested statements (benchmark/queries/tpch_nested.json) at SF
0.01 on the CPU backend: each subquery flattened into a join
(sql/decorrelate.py), and the statement ONE join chain program whose
flattened subqueries are reduction edges or lookups
(exec/device_chain.py), against the host oracle (`serene_device_fused =
off`: host semi / anti joins and grouped aggregates) and against the plain
reference (benchmark/references/tpch_nested_numpy.py)."""

import json
import os

import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.server.pgwire import pg_text
from serenedb_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: per statement: subqueries flattened, reduction edges and lookups,
#: join edges of all its chains
SHAPE = {"q2": (1, 1, 7), "q20": (3, 3, 1), "q17": (1, 1, 1),
         "q18": (1, 1, 2), "q21": (2, 2, 3), "q22": (2, 1, 0),
         "q4": (1, 1, 0)}


def _statements():
    with open(os.path.join(ROOT, "benchmark", "queries",
                           "tpch_nested.json")) as f:
        return {s["id"]: s["sql"] for s in json.load(f)["statements"]}


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    from benchmark.datasets import tpch as gen
    work = tmp_path_factory.mktemp("tpch_nested")
    ds = gen.generate({"scale_factor": 0.01}, 2718281828, str(work))
    c = Database().connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    c.execute("SET serene_result_cache = off")
    return c, ds


def _text_rows(res):
    return [tuple(None if v is None else pg_text(v, col.type).decode()
                  for v, col in zip(row, res.batch.columns))
            for row in res.rows()]


@pytest.mark.parametrize("qid", list(SHAPE))
def test_chain_equals_host_and_reference(tpch, qid):
    from benchmark.references import tpch_nested_numpy as ref
    c, ds = tpch
    sql = _statements()[qid]
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = off")
    flat = metrics.HOST_FLATTENED_JOINS.value
    host = _text_rows(c.execute(sql))
    assert metrics.HOST_FLATTENED_JOINS.value - flat == SHAPE[qid][1]
    c.execute("SET serene_device_fused = on")
    c.execute(sql)                       # the first call compiles
    declines = dict(obs_device.fused_declines())
    counts = {m: getattr(metrics, m).value for m in (
        "DEVICE_OFFLOADS", "DEVICE_JOINS_FUSED", "DEVICE_REDUCTIONS_FUSED",
        "HOST_JOINS", "HOST_FLATTENED_JOINS", "SUBQUERIES_FLATTENED",
        "SUBQUERIES_PER_ROW", "DEVICE_JOIN_BYTES")}
    hits = obs_device.PROGRAMS.family("join_chain")["hits"]
    dev = _text_rows(c.execute(sql))

    def moved(m):
        return getattr(metrics, m).value - counts[m]
    flattened, reductions, edges = SHAPE[qid]
    assert obs_device.fused_declines() == declines
    assert moved("DEVICE_OFFLOADS") == 1
    assert obs_device.PROGRAMS.family("join_chain")["hits"] == hits + 1
    assert moved("SUBQUERIES_FLATTENED") == flattened
    assert moved("SUBQUERIES_PER_ROW") == 0
    assert moved("DEVICE_REDUCTIONS_FUSED") == reductions
    assert moved("DEVICE_JOINS_FUSED") == edges
    assert moved("HOST_JOINS") == 0 and moved("HOST_FLATTENED_JOINS") == 0
    assert moved("DEVICE_JOIN_BYTES") == ref.join_bytes(
        qid, ds["tables"], ds["dictionaries"])
    assert dev == host
    want = ref.evaluate(ref.Data(ds["tables"], ds["dictionaries"]), qid)
    ok, err = ref.compare(dev, want)
    assert ok and err <= 1e-9, (dev[:3], want["rows"][:3])


def test_explain_shows_the_flattened_joins(tpch):
    c, _ = tpch
    c.execute("SET serene_device = 'tpu'")
    lines = [r[0] for r in c.execute(
        "EXPLAIN " + _statements()["q21"]).rows()]
    assert any(ln.strip().startswith("SemiJoin") for ln in lines), lines
    assert any(ln.strip().startswith("AntiJoin") for ln in lines), lines
    lines = [r[0] for r in c.execute(
        "EXPLAIN " + _statements()["q17"]).rows()]
    assert any("HashJoin left" in ln for ln in lines), lines
    assert any("Aggregate groups=1 aggs=[sum, count]" in ln
               for ln in lines), lines


@pytest.mark.parametrize("qid", ["q2", "q17", "q20"])
def test_a_declined_flattened_aggregate_reads_below_100(tpch, monkeypatch,
                                                       qid):
    """`reduce_device_pct.nested` counts a correlated aggregate that the
    chain declines (its left join runs on the host), beside Q4's
    reduction that stays on the device."""
    from benchmark.harness import gauges
    from benchmark.harness.metric_eval import Evaluator
    from serenedb_tpu.exec import device_chain
    c, _ = tpch
    stmts = _statements()
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    names = {"DeviceReductionsFused": metrics.DEVICE_REDUCTIONS_FUSED,
             "HostFlattenedJoins": metrics.HOST_FLATTENED_JOINS}
    before = {n: g.value for n, g in names.items()}
    c.execute(stmts["q4"])
    with monkeypatch.context() as m:
        m.setattr(device_chain, "_reduction", lambda join: None)
        c.execute(stmts[qid])
    delta = {"gauges": {gauges.prom_name(n): g.value - before[n]
                        for n, g in names.items()}, "hists": {}}

    def load(name):
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            return json.load(f)
    got = Evaluator(delta, {}, load).metric("reduce_device_pct.nested")
    assert got is not None and got < 100
