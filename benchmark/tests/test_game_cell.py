"""The cell `wiki.game_c1`, here on the CPU backend at a tiny size: a
whole run through `run()`'s `overrides` (traced, with the controls), the
two controls each by its own number, and what the comparison catches.
"""

import argparse
import copy
import json
import os

import pytest

from benchmark.datasets import wiki
from benchmark.protocols import es_http_total_optional
from benchmark.references import game_numpy
from benchmark.sources.game_queries import Source

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "wiki.game_c1"
KEYS = {f"{s}.{c}" for s in ("term", "intersection", "union", "phrase")
        for c in game_numpy.COMMANDS}
GAME_METRICS = (
    "request_p50_ms.game", "search_plan_p50_ms.game",
    "device_wait_p50_ms.game", "search_device_pct.game",
    "unattributed_pct.game", "phrase_match_pct.game", "host_scan_pct.game",
    "count_materialized_pct.game", "phrase_rescored_pct.game",
    "search_host_score_pct.game")


def _load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced_run():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload=CELL, seed=2**31 + 33, seconds=4.0,
                              trace=1, control=1)
    return bench_run.run(args, require_tpu=False, overrides={"docs": 1500})


def test_the_cell_runs_end_to_end_and_is_correct(traced_run):
    res = traced_run
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 100
    assert set(res["compared"]) == {
        "wrong_hits", "wrong_totals", "score_rel_err_max", "failed_ops",
        "programs_built_in_window"}
    assert res["compared"]["programs_built_in_window"]["value"] == 0
    assert res["window"]["ledger_compiles"] == 0
    assert 0 < res["compared"]["score_rel_err_max"]["value"] <= 1e-5
    assert res["window"]["answers_compared"] == min(1024, res["attempted"])
    # every key of the window was warmed, and is read by shape and command
    assert set(res["window"]["by_statement"]) <= KEYS
    assert len(res["window"]["by_statement"]) >= 10
    m = res["metrics"]
    for name in GAME_METRICS:
        assert name in m, (name, sorted(m))
    assert m["count_materialized_pct.game"]["value"] == 0
    assert m["phrase_rescored_pct.game"]["value"] == 0
    assert 0 < m["phrase_match_pct.game"]["value"] < 50
    assert 0 < m["host_scan_pct.game"]["value"] < 50
    assert m["unattributed_pct.game"]["value"] < 75
    # no chip here: nothing on a device plane, so the device-trace metrics
    # return nothing and are left out, never reported as 0
    assert "score_roofline.game" not in m
    assert "device_idle_pct.game" not in m


def test_both_controls_are_judged_not_correct(traced_run):
    ok, compared = traced_run["control"]["correct"], \
        traced_run["control"]["compared"]
    assert ok is False
    assert compared["score_rel_err_max"]["value"] > 1e-5     # bfloat16
    assert compared["wrong_totals"]["value"] > 0             # no adjacency
    assert compared["failed_ops"]["value"] == 0      # by some, not by each


def test_end_to_end_line_has_only_the_cells_metrics():
    from benchmark import run as bench_run
    args = argparse.Namespace(workload=CELL, seed=7, seconds=1.5, trace=0,
                              control=0)
    res = bench_run.run(args, require_tpu=False, overrides={"docs": 1500})
    assert set(res["metrics"]) == {"ops_per_s", "setup_s"}
    assert res["correct"] is True, res["compared"]


@pytest.fixture(scope="module")
def answered(tmp_path_factory):
    """A tiny corpus, 400 operations and their right answers (the
    reference's own, as the wire would carry them)."""
    cfg = dict(_load("configs/search-game-wiki.json"), docs=1200)
    ds = wiki.generate(cfg, 99, str(tmp_path_factory.mktemp("w")))
    src = Source(_load("queries/game_queries.json"),
                 _load("traffic/game_c1.json"), ds, 99)
    game = game_numpy.Game(ds, cfg["bm25"])
    ops = []
    for _ in range(400):
        key, _payload = src.next_op(0)
        terms, shape, cmd = src.sent[0][-1]
        want_hits, want_total = game_numpy.COMMANDS[cmd]
        matched, _, ids, sc = game.answer(terms, shape, 10)
        ops.append({"client": 0, "key": key, "ok": True, "answer": {
            "total": int(matched.sum()) if want_total else None,
            "relation": "eq" if want_total else None,
            "hits": [(str(int(d)), float(s))
                     for d, s in zip(ids, sc)] if want_hits else []}})
    return cfg, ds, src, ops


def _check(answered, ops, **kw):
    cfg, ds, src, _ = answered
    return game_numpy.check(ops, src, ds, 5, {"sample": 1024}, cfg=cfg,
                            **kw)[0]


def test_right_answers_pass_and_the_sample_is_drawn(answered):
    cfg, ds, src, ops = answered
    numbers, n = game_numpy.check(ops, src, ds, 5, {"sample": 16}, cfg=cfg)
    assert n == 16
    assert numbers == {"wrong_hits": 0, "wrong_totals": 0,
                       "score_rel_err_max": 0.0}


@pytest.mark.parametrize("control, fails, holds", [
    ("bf16", "score_rel_err_max", "wrong_totals"),
    ("no_adjacency", "wrong_totals", "score_rel_err_max")])
def test_each_control_fails_by_its_own_number(answered, control, fails,
                                              holds):
    """The reference in bfloat16 moves no total; the reference without
    adjacency moves no score: each control is caught by one limit the
    other cannot reach."""
    numbers = _check(answered, answered[3], control=control)
    assert numbers[fails] > (1e-5 if fails == "score_rel_err_max" else 0)
    assert numbers[holds] == 0


def _first(ops, src, shape=None, cmd=None, hits=0):
    for i, o in enumerate(ops):
        _t, s, c = src.sent[0][i]
        if shape in (None, s) and cmd in (None, c) and \
                len(o["answer"]["hits"]) >= hits:
            return o["answer"]
    raise AssertionError((shape, cmd))


@pytest.mark.parametrize("fault, number", [
    ("id", "wrong_hits"), ("total", "wrong_totals"),
    ("relation", "wrong_totals"), ("score", "score_rel_err_max"),
    ("order", "wrong_hits"), ("missing", "wrong_hits"),
    ("repeated", "wrong_hits"), ("total_under_top_10", "wrong_totals"),
    ("no_total_under_count", "wrong_totals"),
    ("hits_under_count", "wrong_hits"),
    ("a_phrases_total_is_its_intersections", "wrong_totals")])
def test_an_altered_answer_on_the_wire_is_caught(answered, fault, number):
    cfg, ds, src, ops = answered
    ops = copy.deepcopy(ops)
    if fault == "id":        # an article that does not hold the best score
        a = _first(ops, src, cmd="TOP_10", hits=3)
        best = {h for h, _ in a["hits"]}
        other = next(str(d) for d in range(ds["n_docs"])
                     if str(d) not in best)
        a["hits"][0] = (other, a["hits"][0][1])
    elif fault == "total":
        _first(ops, src, cmd="COUNT")["total"] += 1
    elif fault == "relation":
        _first(ops, src, cmd="TOP_10_COUNT")["relation"] = "gte"
    elif fault == "score":
        a = _first(ops, src, cmd="TOP_10", hits=3)
        a["hits"][2] = (a["hits"][2][0], a["hits"][2][1] * (1 - 1e-3))
    elif fault == "order":
        a = _first(ops, src, cmd="TOP_10_COUNT", hits=3)
        a["hits"][0], a["hits"][-1] = a["hits"][-1], a["hits"][0]
    elif fault == "missing":
        _first(ops, src, cmd="TOP_10", hits=3)["hits"].pop()
    elif fault == "repeated":
        a = _first(ops, src, cmd="TOP_10", hits=3)
        a["hits"][1] = a["hits"][0]
    elif fault == "total_under_top_10":
        a = _first(ops, src, cmd="TOP_10")
        a["total"], a["relation"] = len(a["hits"]), "eq"
    elif fault == "no_total_under_count":
        a = _first(ops, src, cmd="COUNT")
        a["total"] = a["relation"] = None
    elif fault == "hits_under_count":
        _first(ops, src, cmd="COUNT")["hits"] = \
            list(_first(ops, src, cmd="TOP_10", hits=3)["hits"])
    else:
        game = game_numpy.Game(ds, cfg["bm25"])
        for i, o in enumerate(ops):
            terms, shape, cmd = src.sent[0][i]
            inter = int(game.match(terms, "intersection").sum())
            if shape == "phrase" and cmd != "TOP_10" and \
                    inter != o["answer"]["total"]:
                o["answer"]["total"] = inter
                break
        else:
            raise AssertionError("no phrase narrower than its words")
    numbers = _check(answered, ops)
    assert numbers[number] > (1e-5 if number == "score_rel_err_max" else 0)


def test_the_protocol_carries_an_answer_without_a_total():
    """`es_http` reads hits.total of every answer; this one's reduction
    takes the answer as it is."""
    reduce_search = es_http_total_optional.reduce_search
    hit = {"_id": "7", "_score": 1.5, "_source": {}}
    assert reduce_search({"hits": {"max_score": 1.5, "hits": [hit]}}) == \
        {"total": None, "relation": None, "hits": [("7", 1.5)]}
    assert reduce_search({"hits": {"total": {"value": 3, "relation": "eq"},
                                   "max_score": None, "hits": []}}) == \
        {"total": 3, "relation": "eq", "hits": []}
    traffic = _load("traffic/game_c1.json")
    assert traffic["protocol"] == "es_http_total_optional"
    assert traffic["clients"] == 1 and traffic["check"] == {"sample": 1024}
    qs = _load("queries/game_queries.json")
    assert qs["lengths"] == {"2": 0.6, "3": 0.3, "4": 0.1}
    assert qs["shapes"] == {"term": 0.1, "intersection": 0.3, "union": 0.3,
                            "phrase": 0.3}
    assert qs["commands"]["TOP_10"] == {"size": 10,
                                        "track_total_hits": False}
    assert game_numpy.posting_bytes(7) == 35.0


def test_a_server_that_outgrows_the_machine_fails_the_run_cleanly(
        monkeypatch):
    """The commit before PR 33 needs 62 GB inside COPY for this corpus;
    the machine would end the whole command. The dataset ends the server
    child instead: `run` raises BenchError (exit 1, no result)."""
    from benchmark import run as bench_run
    monkeypatch.setattr(wiki, "SERVER_MEMORY_SHARE", 1e-3)
    args = argparse.Namespace(workload=CELL, seed=5, seconds=1.0, trace=0,
                              control=0)
    with pytest.raises(bench_run.BenchError, match="closed|exited"):
        bench_run.run(args, require_tpu=False, overrides={"docs": 1500})
