"""The benchmark's MS MARCO deployment against its plain reference, in
process: `es_api.search` over 2,000 generated passages
(benchmark/datasets/msmarco.py) must give the ids, scores (within 1e-5)
and exact totals of benchmark/references/bm25_numpy.py on seeded
questions — through the dense steps a small corpus gets, and through the
plane kernel with its host MaxScore tier, which the full-size cell runs.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datasets import msmarco                       # noqa: E402
from benchmark.protocols.es_http import reduce_search        # noqa: E402
from benchmark.references import bm25_numpy                  # noqa: E402
from benchmark.sources.match_questions import Source         # noqa: E402
from serenedb_tpu.engine import Database                     # noqa: E402
from serenedb_tpu.ops import bm25 as bm25_ops                # noqa: E402
from serenedb_tpu.server.es_api import EsApi                 # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")


def _load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def collection(tmp_path_factory):
    cfg = dict(_load("configs/msmarco-passage.json"), passages=2000)
    work = tmp_path_factory.mktemp("msmarco")
    return cfg, msmarco.generate(cfg, 20261001, str(work))


def test_generator_keeps_the_sources_shapes(collection):
    cfg, ds = collection
    lens = ds["lens"]
    assert ds["n_docs"] == 2000 and lens.min() >= 10 and lens.max() <= 250
    assert 50 < lens.mean() < 62
    top = np.bincount(ds["toks"], minlength=100)[:100].sum() / len(ds["toks"])
    assert 0.42 < top < 0.48                 # the function words' share
    assert len(set(ds["words"][:5000])) == 5000
    assert all(w.isalpha() and w.islower() for w in ds["words"][::997])
    src = Source(_load("queries/msmarco_questions.json"),
                 _load("traffic/search_c32.json"), ds, 7)
    ns, func = [], 0
    for i in range(600):
        key, (path, body) = src.next_op(i % 32)
        terms = src.sent[i % 32][-1]
        assert key == f"t{len(terms)}" == f"t{len(set(terms))}"
        assert path == "/passages/_search"
        ns.append(len(terms))
        func += sum(t < 100 for t in terms)
    assert 2 <= min(ns) and max(ns) <= 15 and 5.3 < np.mean(ns) < 6.5
    assert 0.33 < func / sum(ns) < 0.47
    warm = src.distinct_ops()
    assert len(warm) == 14 * 16
    assert {k for k, _ in warm} == {f"t{n}" for n in range(2, 16)} \
        >= {f"t{n}" for n in ns}


@pytest.mark.parametrize("regime", ["dense", "plane"])
def test_search_equals_the_plain_reference(collection, regime, monkeypatch):
    cfg, ds = collection
    if regime == "plane":
        monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    db = Database()
    c = db.connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    assert c.execute(ds["count"][0]).scalar() == ds["count"][1]
    c.execute("SET serene_result_cache = off")
    es = EsApi(db)
    src = Source(_load("queries/msmarco_questions.json"),
                 _load("traffic/search_c32.json"), ds, 11)
    index = bm25_numpy.Index(ds, cfg["bm25"])
    worst = 0.0
    for i in range(120):
        _key, (_path, body) = src.next_op(i % 32)
        answer = reduce_search(es.search("passages", json.loads(body)))
        terms = src.sent[i % 32][-1]
        assert bm25_numpy.shape_faults(answer, ds["n_docs"], 10) == 0
        wrong, bad_total, err = bm25_numpy.compare(answer, terms, index, 10)
        assert (wrong, bad_total) == (0, 0), (terms, answer)
        worst = max(worst, err)
        # the ids themselves, where no two scores are within the tolerance
        ref, matched = index.score(terms)
        ids, sc = index.topk(ref, matched, 10)
        if np.all(np.diff(sc) < -2e-5 * sc[:-1]):
            assert [int(h) for h, _ in answer["hits"]] == ids.tolist()
    assert worst <= cfg["limits"]["score_rel_err_max"]
    # the control — the reference in bfloat16 in the program's place — is
    # caught by the same comparison
    c_wrong = c_err = 0
    for i in range(32):
        w, _t, e = bm25_numpy.compare(None, src.sent[i][0], index, 10,
                                      low_precision=True)
        c_wrong += w
        c_err = max(c_err, e)
    assert c_err > cfg["limits"]["score_rel_err_max"]
