"""The cells `vectors.knn_c8` and `msmarco.search_c1`, here on the CPU
backend at a tiny size: a whole run of each through `run()`'s `overrides`
(the knn cell traced, with the control), what the knn comparison catches,
and `knn_roofline`'s counter against the reference's own byte function.
"""

import argparse
import copy
import json
import os

import numpy as np
import pytest

from benchmark.datasets import msmarco, msmarco_dense
from benchmark.references import knn_numpy
from benchmark.sources.knn_questions import Source

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "vectors.knn_c8"
N = 2000


def _load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_run():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload=CELL, seed=2**31 + 31, seconds=3.0,
                              trace=1, control=1)
    return bench_run.run(args, require_tpu=False,
                         overrides={"passages": N})


def test_the_cell_runs_end_to_end_and_is_correct(traced_run):
    res = traced_run
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["compared"]) == {
        "wrong_hits", "wrong_totals", "score_rel_err_max", "failed_ops",
        "programs_built_in_window"}
    assert res["compared"]["programs_built_in_window"]["value"] == 0
    assert res["window"]["ledger_compiles"] == 0
    assert 0 < res["compared"]["score_rel_err_max"]["value"] <= 1e-5
    assert res["window"]["answers_compared"] == min(1024, res["attempted"])
    assert set(res["window"]["by_statement"]) == {"knn"}
    m = res["metrics"]
    for name in ("knn_batch_mean", "knn_flat_pct", "batch_wait_p50_ms.knn",
                 "device_wait_p50_ms.knn", "request_p50_ms.knn",
                 "unattributed_pct.knn"):
        assert name in m, (name, sorted(m))
    assert m["knn_flat_pct"]["value"] == 100.0
    assert m["knn_batch_mean"]["value"] >= 1.0
    assert m["unattributed_pct.knn"]["value"] < 50
    # no chip here: nothing on a device plane, so the two device-trace
    # metrics return nothing and are left out, never reported as 0
    assert "knn_roofline" not in m and "device_idle_pct.knn" not in m
    # nor anything of another cell's
    assert not [k for k in m if k.endswith((".search", ".c1", ".light"))]


def test_the_control_is_judged_not_correct(traced_run):
    ok, compared = traced_run["control"]["correct"], \
        traced_run["control"]["compared"]
    assert ok is False
    assert compared["score_rel_err_max"]["value"] > 1e-5
    assert compared["wrong_totals"]["value"] == 0    # by one limit, not each


def test_end_to_end_line_has_only_the_cells_metrics():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload=CELL, seed=7, seconds=1.5, trace=0,
                              control=0)
    res = bench_run.run(args, require_tpu=False,
                        overrides={"passages": N})
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}
    assert res["correct"] is True, res["compared"]


def test_search_c1_resolves_and_runs():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload="msmarco.search_c1", seed=11,
                              seconds=2.0, trace=1, control=0)
    res = bench_run.run(args, require_tpu=False,
                        overrides={"passages": 1500})
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] >= 3 and res["failed"] == 0
    # one connection: every answer of the window is compared
    assert res["window"]["answers_compared"] == res["attempted"]
    m = res["metrics"]
    for name in ("search_plan_p50_ms.c1", "device_wait_p50_ms.c1",
                 "request_p50_ms.c1", "search_device_pct.c1",
                 "unattributed_pct.c1"):
        assert name in m, (name, sorted(m))
    assert "batch_wait_p50_ms" not in m and "search_batch_mean" not in m
    assert _load("traffic/search_c1.json")["clients"] == 1
    c32 = _load("traffic/search_c32.json")
    c1 = _load("traffic/search_c1.json")
    assert {k: v for k, v in c1.items() if k not in ("clients", "about")} \
        == {k: v for k, v in c32.items() if k not in ("clients", "about")}


@pytest.fixture(scope="module")
def answered(tmp_path_factory):
    """A tiny collection, 40 questions and their right answers (the
    reference's own top-10, as the wire would carry them)."""
    cfg = dict(_load("configs/msmarco-passage-dense.json"), passages=1200)
    ds = msmarco_dense.generate(cfg, 99, str(tmp_path_factory.mktemp("m")))
    src = Source(_load("queries/knn_questions.json"),
                 _load("traffic/knn_c8.json"), ds, 99)
    ops = []
    for i in range(40):
        key, (path, body) = src.next_op(i % 8)
        assert key == "knn" and path == "/passages/_search"
        q = src.sent[i % 8][-1]
        ids, cos = knn_numpy.topk(ds["emb"], q[None, :], 10)
        ops.append({"client": i % 8, "key": key, "ok": True, "answer": {
            "total": 10, "relation": "eq",
            "hits": [(str(int(d)), float(knn_numpy.es_score(c)))
                     for d, c in zip(ids[0], cos[0])]}})
    return cfg, ds, src, ops


def test_generator_and_source_keep_their_stated_shapes(answered):
    cfg, ds, src, _ops = answered
    emb = ds["emb"]
    assert emb.shape == (1200, 768) and emb.dtype == np.float32
    assert np.allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-5)
    # same seed -> the same text as msmarco-passage's generator
    assert ds["n_docs"] == 1200 and len(ds["lens"]) == 1200
    same = ds["topic_of"][:, None] == ds["topic_of"][None, :]
    cos = emb.astype(np.float64) @ emb.astype(np.float64).T
    off = ~np.eye(1200, dtype=bool)
    assert 0.20 < cos[~same].mean() < 0.30        # anisotropy
    assert 0.58 < cos[same & off].mean() < 0.70   # one topic
    # a question: the wire's digits read back as the float32 sent, and
    # its target is (nearly always) the nearest passage
    key, (_path, body) = src.next_op(0)
    sent = src.sent[0][-1]
    wire = np.asarray(json.loads(body)["knn"]["query_vector"], np.float32)
    assert wire.view(np.uint32).tolist() == sent.view(np.uint32).tolist()
    assert 8000 < len(body) < 11000
    req = json.loads(body)
    assert (req["knn"]["k"], req["knn"]["num_candidates"], req["size"]) \
        == (10, 100, 10)
    ids, top = knn_numpy.topk(emb, sent[None, :], 2)
    assert top[0][0] > 0.85 and top[0][1] < 0.75
    assert len(src.distinct_ops()) == 16
    assert "serenedb_tpu" not in open(knn_numpy.__file__).read() \
        .split('"""', 2)[2]


def _check(answered, ops, **kw):
    cfg, ds, src, _ = answered
    return knn_numpy.check(ops, src, ds, 5, {"sample": 1024}, cfg=cfg,
                           **kw)[0]


def test_right_answers_pass_and_the_sample_is_drawn(answered):
    cfg, ds, src, ops = answered
    numbers, n = knn_numpy.check(ops, src, ds, 5, {"sample": 16}, cfg=cfg)
    assert n == 16
    assert numbers["wrong_hits"] == 0 and numbers["wrong_totals"] == 0
    assert numbers["score_rel_err_max"] < 1e-12
    assert _check(answered, ops, control=True)["score_rel_err_max"] > 1e-5


@pytest.mark.parametrize("fault, number", [
    ("id", "wrong_hits"), ("total", "wrong_totals"),
    ("relation", "wrong_totals"), ("score", "score_rel_err_max"),
    ("order", "wrong_hits"), ("missing", "wrong_hits"),
    ("repeated", "wrong_hits"), ("out_of_range", "wrong_hits")])
def test_an_altered_answer_on_the_wire_is_caught(answered, fault, number):
    cfg, ds, src, ops = answered
    ops = copy.deepcopy(ops)
    a = ops[3]["answer"]
    hits = a["hits"]
    if fault == "id":        # a passage that is not among the nearest
        best = {h for h, _ in hits}
        other = next(str(d) for d in range(ds["n_docs"])
                     if str(d) not in best)
        hits[0] = (other, hits[0][1])
    elif fault == "total":
        a["total"] += 1
    elif fault == "relation":
        a["relation"] = "gte"
    elif fault == "score":
        hits[2] = (hits[2][0], hits[2][1] * (1 + 1e-3))
    elif fault == "order":
        hits[0], hits[-1] = hits[-1], hits[0]
    elif fault == "missing":
        hits.pop()
    elif fault == "repeated":
        hits[1] = hits[0]
    elif fault == "out_of_range":
        hits[4] = (str(ds["n_docs"]), hits[4][1])
    numbers = _check(answered, ops)
    assert numbers[number] > (1e-5 if number == "score_rel_err_max" else 0)


def test_knn_rooflines_counter_is_the_references_bytes(answered):
    """`VectorRowsScanned` moves by the rows of the index once per
    dispatch, and the roofline's bytes are `scan_bytes` of it (3,072 B a
    row at 768 dims, as the expression in metrics/knn_roofline.json
    says)."""
    from serenedb_tpu.engine import Database
    from serenedb_tpu.server.es_api import EsApi
    from serenedb_tpu.utils import metrics
    cfg, ds, src, _ = answered
    db = Database()
    c = db.connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    c.execute("SET serene_result_cache = off")
    es = EsApi(db)
    for q in src.sent[0][:2]:
        before = metrics.VECTOR_ROWS_SCANNED.value
        res = es.search("passages", {"knn": {
            "field": "emb", "query_vector": [float(v) for v in q],
            "k": 10, "num_candidates": 100}, "size": 10})
        moved = metrics.VECTOR_ROWS_SCANNED.value - before
        assert moved == ds["n_docs"]
        assert knn_numpy.scan_bytes(moved, 768) == 3072 * moved
        ids, _cos = knn_numpy.topk(ds["emb"], q[None, :], 10)
        assert [int(h["_id"]) for h in res["hits"]["hits"]] == \
            ids[0].tolist()
    assert " * 3072 " in _load("metrics/knn_roofline.json")["expr"]
