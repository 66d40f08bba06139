"""TPC-H's 8 statements (benchmark/queries/tpch_8.json) at SF 0.01 on the
CPU backend: the device tiers (`serene_device = 'tpu'`: a join statement
through the chain program, exec/device_chain.py; Q1 and Q6, one table
each, through exec/device_agg.py as every single-table aggregate) against
the host oracle (`serene_device_fused = off`) and against the plain
reference (benchmark/references/tpch_numpy.py). Each statement takes ONE
device dispatch and no decline; a join statement counts one fused join
per edge. Q3 and Q10 group by a row id, past the masked reductions' few
groups: their surviving rows are compacted into a rung of the chain's
ladder before they are scattered, or all rows are scattered past it."""

import json
import os

import numpy as np
import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.exec import device_chain
from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.server.pgwire import pg_text
from serenedb_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: join edges of each statement's chain
EDGES = {"q14": 1, "q9": 5, "q6": 0, "q3": 2, "q1": 0, "q10": 3, "q5": 5,
         "q12": 1}


def _statements():
    with open(os.path.join(ROOT, "benchmark", "queries",
                           "tpch_8.json")) as f:
        return {s["id"]: s["sql"] for s in json.load(f)["statements"]}


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    from benchmark.datasets import tpch as gen
    work = tmp_path_factory.mktemp("tpch")
    ds = gen.generate({"scale_factor": 0.01}, 31415926535, str(work))
    c = Database().connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    c.execute("SET serene_result_cache = off")
    return c, ds


def _text_rows(res):
    return [tuple(None if v is None else pg_text(v, col.type).decode()
                  for v, col in zip(row, res.batch.columns))
            for row in res.rows()]


@pytest.mark.parametrize("qid", list(EDGES))
def test_chain_equals_host_and_reference(tpch, qid):
    from benchmark.references import tpch_numpy as ref
    c, ds = tpch
    sql = _statements()[qid]
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = off")
    host = _text_rows(c.execute(sql))
    c.execute("SET serene_device_fused = on")
    c.execute(sql)                       # the first call compiles
    family = "join_chain" if EDGES[qid] else "device_agg"
    declines = dict(obs_device.fused_declines())
    offloads = metrics.DEVICE_OFFLOADS.value
    joins = metrics.DEVICE_JOINS_FUSED.value
    host_joins = metrics.HOST_JOINS.value
    hits = obs_device.PROGRAMS.family(family)["hits"]
    dev = _text_rows(c.execute(sql))
    assert obs_device.fused_declines() == declines
    assert metrics.DEVICE_OFFLOADS.value - offloads == 1
    assert obs_device.PROGRAMS.family(family)["hits"] == hits + 1
    assert metrics.DEVICE_JOINS_FUSED.value - joins == EDGES[qid]
    assert metrics.HOST_JOINS.value == host_joins
    assert dev == host
    want = ref.evaluate(ref.Data(ds["tables"], ds["dictionaries"]), qid)
    ok, err = ref.compare(dev, want)
    assert ok and err <= 1e-9, (dev[:3], want["rows"][:3])
    assert want["rows"], "the statement should answer something at SF 0.01"


def test_chain_explain_names_its_program(tpch):
    c, _ = tpch
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    sql = _statements()["q5"]
    c.execute(sql)
    before = obs_device.PROGRAMS.family("join_chain")
    lines = [r[0] for r in c.execute("EXPLAIN ANALYZE " + sql).rows()]
    assert not any("declined=" in ln for ln in lines), lines
    after = obs_device.PROGRAMS.family("join_chain")
    assert after["hits"] > before["hits"]


def test_a_build_side_not_unique_declines_to_the_pair_program():
    c = Database().connect()
    c.execute("CREATE TABLE f (k INT, v DECIMAL(10,2))")
    c.execute("CREATE TABLE g (k INT, w INT)")
    c.execute("INSERT INTO f SELECT g % 50, g FROM generate_series(1, 2000) g")
    c.execute("INSERT INTO g SELECT g % 50, g FROM generate_series(1, 200) g")
    c.execute("SET serene_device = 'tpu'")
    sql = "SELECT sum(v), count(*) FROM f, g WHERE f.k = g.k"
    got = c.execute(sql).rows()
    c.execute("SET serene_device = 'cpu'")
    assert got == c.execute(sql).rows()


@pytest.mark.parametrize("grouped", [True, False])
def test_wide_products_stay_exact(grouped):
    """Q1's three-factor product leaves int32: device_agg sums it as two
    int32 halves (ops/agg.py's limb sums, grouped, or its tile partials)
    and the host recombines them exactly."""
    c = Database().connect()
    c.execute("CREATE TABLE w (e DECIMAL(15,2), d DECIMAL(15,2), "
              "t DECIMAL(15,2), g INT)")
    rng = np.random.default_rng(7)
    rows = ", ".join(f"({int(e) / 100:.2f}, {int(d) / 100:.2f}, "
                     f"{int(t) / 100:.2f}, {int(g)})"
                     for e, d, t, g in zip(rng.integers(90000, 10495000, 3000),
                                           rng.integers(0, 11, 3000),
                                           rng.integers(0, 9, 3000),
                                           rng.integers(0, 3, 3000)))
    c.execute(f"INSERT INTO w VALUES {rows}")
    sql = ("SELECT g, sum(e * (1 - d) * (1 + t)), avg(e) FROM w "
           "GROUP BY g ORDER BY g" if grouped else
           "SELECT sum(e * (1 - d) * (1 + t)), avg(e * (1 - d) * (1 + t)) "
           "FROM w WHERE g < 2")
    c.execute("SET serene_result_cache = off")
    c.execute("SET serene_device = 'tpu'")
    hits = obs_device.PROGRAMS.family("device_agg")["hits"]
    c.execute(sql)
    dev = c.execute(sql).rows()
    assert obs_device.PROGRAMS.family("device_agg")["hits"] == hits + 1
    c.execute("SET serene_device = 'cpu'")
    assert dev == c.execute(sql).rows()


#: ladders (divisors of the padded probe rows: 65,536 at SF 0.01) that put
#: Q3 (about 300 surviving rows) and Q10 (about 1,150) on each branch of
#: the many-group reduction: rung 0, 1 or 2, or past the last (every row)
LADDERS = {0: (16, 8, 4), 1: (65536, 16, 8), 2: (65536, 32768, 16),
           3: (65536, 32768, 16384)}
#: the statements with a date range no order falls in: no row survives
NO_SURVIVOR = {"q3": ("o_orderdate < DATE '1995-03-15'",
                      "o_orderdate < DATE '1990-01-01'"),
               "q10": ("o_orderdate >= DATE '1993-10-01'",
                       "o_orderdate >= DATE '1999-10-01'")}


def _spy_rungs(monkeypatch) -> list:
    """(ladder, surviving rows) of every chain dispatch, as its finalize
    reads them."""
    seen = []
    real = device_chain._finalize

    def finalize(*args):
        rungs, results = args[-1], args[6]
        seen.append((rungs, int(np.asarray(results[0])[:, 0].sum())))
        return real(*args)
    monkeypatch.setattr(device_chain, "_finalize", finalize)
    return seen


def _rung(rungs: tuple, survivors: int) -> int:
    """The branch the program takes: a rung's index, len(rungs) past them."""
    return sum(survivors > b for b in rungs)


def _fused_tick(c, sql):
    """(rows, compacted ticks, full-scatter ticks) of one fused run."""
    compacted = metrics.DEVICE_CHAIN_COMPACTED.value
    full = metrics.DEVICE_CHAIN_SCATTERED_FULL.value
    rows = _text_rows(c.execute(sql))
    return (rows, metrics.DEVICE_CHAIN_COMPACTED.value - compacted,
            metrics.DEVICE_CHAIN_SCATTERED_FULL.value - full)


@pytest.mark.parametrize("branch", [0, 1, 2, 3, "none"])
@pytest.mark.parametrize("qid", ["q3", "q10"])
def test_many_group_ladder_branches(tpch, monkeypatch, qid, branch):
    """Every branch of the many-group reduction answers as the host plan
    and the reference do, and ticks one of its two counters once."""
    from benchmark.references import tpch_numpy as ref
    c, ds = tpch
    sql = _statements()[qid]
    if branch == "none":
        sql = sql.replace(*NO_SURVIVOR[qid])
    else:
        monkeypatch.setattr(device_chain, "COMPACT_RUNGS", LADDERS[branch])
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = off")
    host = _text_rows(c.execute(sql))
    c.execute("SET serene_device_fused = on")
    seen = _spy_rungs(monkeypatch)
    dev, compacted, full = _fused_tick(c, sql)
    assert len(seen) == 1
    rungs, survivors = seen[0]
    assert dev == host
    if branch == "none":
        assert survivors == 0 and dev == []
        assert _rung(rungs, survivors) == 0
    else:
        assert survivors > 4
        assert _rung(rungs, survivors) == branch
        want = ref.evaluate(ref.Data(ds["tables"], ds["dictionaries"]), qid)
        ok, err = ref.compare(dev, want)
        assert ok and err <= 1e-9, (dev[:3], want["rows"][:3])
    assert (compacted, full) == ((0, 1) if branch == 3 else (1, 0))


@pytest.mark.parametrize("qid", ["q5", "q9", "q12", "q14"])
def test_few_group_statements_tick_neither_ladder_counter(tpch, qid):
    c, _ = tpch
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    _rows, compacted, full = _fused_tick(c, _statements()[qid])
    assert (compacted, full) == (0, 0)


def test_rungs_share_one_program(tpch, monkeypatch):
    """Q3 and Q10 land on different rungs of the default ladder, and
    neither builds a program after its first run: every branch is built
    with the program."""
    c, _ = tpch
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    sts = _statements()
    for qid in ("q3", "q10"):
        c.execute(sts[qid])
    compiles = obs_device.PROGRAMS.family("join_chain")["compiles"]
    seen = _spy_rungs(monkeypatch)
    for qid in ("q3", "q10", "q3"):
        c.execute(sts[qid])
    assert obs_device.PROGRAMS.family("join_chain")["compiles"] == compiles
    branches = [_rung(r, n) for r, n in seen]
    assert branches[0] != branches[1] and branches[0] == branches[2]
