"""The cell `msmarco.search_c32`, here on the CPU backend at a tiny size:
a whole run through `run()`'s `overrides` (traced, with the control),
what its comparison catches, and `score_roofline`'s counter against the
reference's own count.
"""

import argparse
import copy
import json
import os

import pytest

from benchmark.datasets import msmarco
from benchmark.references import bm25_numpy
from benchmark.sources.match_questions import Source

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "msmarco.search_c32"


def _load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_run():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload=CELL, seed=2**31 + 26, seconds=3.0,
                              trace=1, control=1)
    return bench_run.run(args, require_tpu=False,
                         overrides={"passages": 1500})


def test_the_cell_runs_end_to_end_and_is_correct(traced_run):
    res = traced_run
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 32
    assert set(res["compared"]) == {
        "wrong_hits", "wrong_totals", "score_rel_err_max", "failed_ops",
        "programs_built_in_window"}
    assert res["compared"]["programs_built_in_window"]["value"] == 0
    assert res["window"]["ledger_compiles"] == 0
    assert 0 < res["compared"]["score_rel_err_max"]["value"] <= 1e-5
    assert res["window"]["answers_compared"] == min(1024, res["attempted"])
    # every key of the window was warmed, and read by query length
    assert set(res["window"]["by_statement"]) <= {
        f"t{n}" for n in range(2, 16)}
    m = res["metrics"]
    for name in ("search_device_pct", "search_batch_mean",
                 "batch_wait_p50_ms", "search_plan_p50_ms",
                 "search_host_score_pct", "device_wait_p50_ms.search",
                 "request_p50_ms.search", "unattributed_pct.search"):
        assert name in m, (name, sorted(m))
    assert m["search_batch_mean"]["value"] > 1.0     # 32 clients coalesce
    assert 0 <= m["search_device_pct"]["value"] <= 100
    assert m["unattributed_pct.search"]["value"] < 50
    # no chip here: nothing on a device plane, so the two device-trace
    # metrics return nothing and are left out, never reported as 0
    assert "score_roofline" not in m and "device_idle_pct.search" not in m


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
                        overrides={"passages": 1500})
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}
    assert res["correct"] is True, res["compared"]


@pytest.fixture(scope="module")
def answered(tmp_path_factory):
    """A tiny collection, 40 questions and their right answers (the
    reference's own top-10, as the wire would carry them)."""
    cfg = dict(_load("configs/msmarco-passage.json"), passages=1200)
    ds = msmarco.generate(cfg, 99, str(tmp_path_factory.mktemp("m")))
    src = Source(_load("queries/msmarco_questions.json"),
                 _load("traffic/search_c32.json"), ds, 99)
    index = bm25_numpy.Index(ds, cfg["bm25"])
    ops = []
    for i in range(40):
        key, _payload = src.next_op(i % 32)
        scores, matched = index.score(src.sent[i % 32][-1])
        ids, sc = index.topk(scores, matched, 10)
        ops.append({"client": i % 32, "key": key, "ok": True, "answer": {
            "total": int(matched.sum()), "relation": "eq",
            "hits": [(str(int(d)), float(s)) for d, s in zip(ids, sc)]}})
    return cfg, ds, src, ops


def _check(answered, ops, **kw):
    cfg, ds, src, _ = answered
    return bm25_numpy.check(ops, src, ds, 5, {"sample": 1024}, cfg=cfg,
                            **kw)[0]


def test_right_answers_pass_and_the_sample_is_drawn(answered):
    cfg, ds, src, ops = answered
    numbers, n = bm25_numpy.check(ops, src, ds, 5, {"sample": 16}, cfg=cfg)
    assert n == 16
    assert numbers == {"wrong_hits": 0, "wrong_totals": 0,
                       "score_rel_err_max": 0.0}
    assert _check(answered, ops, control=True)["score_rel_err_max"] > 1e-3


@pytest.mark.parametrize("fault, number", [
    ("id", "wrong_hits"), ("total", "wrong_totals"),
    ("relation", "wrong_totals"), ("score", "score_rel_err_max"),
    ("order", "wrong_hits"), ("missing", "wrong_hits"),
    ("repeated", "wrong_hits")])
def test_an_altered_answer_on_the_wire_is_caught(answered, fault, number):
    cfg, ds, src, ops = answered
    ops = copy.deepcopy(ops)
    a = ops[3]["answer"]
    hits = a["hits"]
    if fault == "id":        # a passage that does not hold the best score
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
    numbers = _check(answered, ops)
    assert numbers[number] > (1e-5 if number == "score_rel_err_max" else 0)


def test_score_rooflines_counter_is_the_references_count(answered):
    """With pruning defeated (size >= passages) a question hands every
    posting of its terms to the device: `SearchPostingsDispatched` moves
    by the reference's sum of document frequencies, and the roofline's
    bytes are `posting_bytes` of it (5 B a posting, as the expression
    in metrics/score_roofline.json says)."""
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
    index = bm25_numpy.Index(ds, cfg["bm25"])
    for terms in src.sent[0][:1] + src.sent[5][:1]:
        before = metrics.SEARCH_POSTINGS_DISPATCHED.value
        es.search("passages", {"query": {"match": {"body": " ".join(
            ds["words"][t] for t in terms)}}, "size": ds["n_docs"]})
        moved = metrics.SEARCH_POSTINGS_DISPATCHED.value - before
        want = sum(index.df(t) for t in terms)
        assert moved == want
        assert bm25_numpy.posting_bytes(want) == 5 * moved
    assert " * 5 " in _load("metrics/score_roofline.json")["expr"]
