"""The benchmark's Search-Benchmark-the-Game deployment against its plain
reference, in process, and the mechanisms it forced:

- every one of the twelve keys (four shapes x three commands) through
  `EsApi.search` over 2,000 generated articles (benchmark/datasets/wiki.py)
  must give the ids, scores (within 1e-5), totals and the command's shape
  of benchmark/references/game_numpy.py — through the dense steps a small
  corpus gets, and through the plane kernel the full-size cell runs;
- the array phrase matcher (`_phrase_docs`) against the per-document form
  it replaced, kept HERE as the oracle;
- a phrase whose match set is too large for the host rung goes into the
  scoring dispatch as a doc mask: scored on the device, never rescored,
  by programs `prebuild` built;
- `_search` runs only what the body asks for: `track_total_hits` true /
  false / integer, `size: 0`, one evaluation of a phrase's match set for
  the page and the total.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datasets import wiki                                # noqa: E402
from benchmark.protocols.es_http_total_optional import \
    reduce_search                                                   # noqa: E402
from benchmark.references import game_numpy                        # noqa: E402
from benchmark.sources.game_queries import Source                  # noqa: E402
from serenedb_tpu.engine import Database                           # noqa: E402
from serenedb_tpu.obs import device as obs_device                  # noqa: E402
from serenedb_tpu.ops import bm25 as bm25_ops                      # noqa: E402
from serenedb_tpu.search.analysis import get_analyzer              # noqa: E402
from serenedb_tpu.search.query import QPhrase                      # noqa: E402
from serenedb_tpu.search.searcher import (MultiSearcher,           # noqa: E402
                                          SegmentSearcher)
from serenedb_tpu.search.segment import build_field_index          # noqa: E402
from serenedb_tpu.server.es_api import EsApi                       # noqa: E402
from serenedb_tpu.utils import metrics                             # noqa: E402
from serenedb_tpu.utils.config import REGISTRY as SETTINGS         # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
SHAPES = ("term", "intersection", "union", "phrase")
COMMANDS = ("COUNT", "TOP_10", "TOP_10_COUNT")


def _load(name):
    with open(os.path.join(BENCH, name)) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def result_cache_off():
    """Off, as the cell runs: nothing is answered from an earlier answer."""
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    yield
    SETTINGS.set_global("serene_result_cache", prior)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    cfg = dict(_load("configs/search-game-wiki.json"), docs=2000)
    work = tmp_path_factory.mktemp("wiki")
    ds = wiki.generate(cfg, 20261004, str(work))
    return cfg, ds, game_numpy.Game(ds, cfg["bm25"])


def _served(ds, budget=None):
    mp = pytest.MonkeyPatch()
    if budget is not None:
        mp.setattr(bm25_ops, "DENSE_HBM_BUDGET", budget)
    db = Database()
    c = db.connect()
    for stmt in ds["load"]:
        c.execute(stmt)
    return EsApi(db), mp


#: a dense budget of 64 rows of the corpus's 2,048 padded articles: the
#: whole matrix does not fit, the 64 most frequent dense terms' rows do
RESIDENT_BUDGET = 64 * 2048 * 4


@pytest.fixture(scope="module", params=["dense", "plane", "resident"])
def served(request, corpus):
    """(EsApi over the loaded articles, the regime): the small corpus's
    dense steps; (dense budget 0 BEFORE the index is built) the plane
    kernel alone; or, as the full-size cell runs, the plane kernel with
    the resident rows of the store's most frequent terms."""
    es, mp = _served(corpus[1], {"dense": None, "plane": 0,
                                 "resident": RESIDENT_BUDGET}[
                                     request.param])
    yield es, request.param
    mp.undo()


# -- the generator and the stream ---------------------------------------------


def test_generator_keeps_the_sources_shapes(corpus):
    cfg, ds, game = corpus
    lens = ds["lens"]
    assert ds["n_docs"] == 2000 and lens.min() >= 20 and lens.max() <= 8000
    assert 230 < lens.mean() < 340 and 140 < np.median(lens) < 200
    assert lens.max() > 10 * np.median(lens)           # the long tail
    assert 0.10 < ds["colloc_token_share"] < 0.20
    at, ln = ds["colloc_at"], ds["colloc_len"]
    assert set(np.unique(ln)) == {2, 3, 4}
    assert (at[1:] >= at[:-1] + ln[:-1]).all()         # none overlaps
    assert (ds["doc_of"][at] == ds["doc_of"][at + ln - 1]).all()
    # first and last word of a collocation are not function words
    assert (ds["toks"][at] >= 100).all()
    assert (ds["toks"][at + ln - 1] >= 100).all()
    src = Source(_load("queries/game_queries.json"),
                 _load("traffic/game_c1.json"), ds, 7)
    seen, ns, keys = set(), [], []
    for _ in range(900):
        key, (path, body) = src.next_op(0)
        terms, shape, cmd = src.sent[0][-1]
        assert key == f"{shape}.{cmd}" and path == "/wiki/_search"
        assert (tuple(terms), shape, cmd) not in seen     # never twice
        seen.add((tuple(terms), shape, cmd))
        body = json.loads(body)
        assert {k: body[k] for k in ("size", "track_total_hits")} == \
            src.commands[cmd]
        if shape != "term":
            ns.append(len(terms))
            assert terms[0] >= 100 and terms[-1] >= 100
        keys.append(key)
    assert 0.52 < np.mean(np.asarray(ns) == 2) < 0.68
    share = {s: np.mean([k.startswith(s + ".") for k in keys])
             for s in SHAPES}
    assert 0.06 < share["term"] < 0.14
    assert all(0.25 < share[s] < 0.35 for s in SHAPES[1:])
    warm = src.distinct_ops()
    assert len(warm) == 12 * 16
    assert {k for k, _ in warm} == \
        {f"{s}.{c}" for s in SHAPES for c in COMMANDS} >= set(keys)


def test_a_collocations_phrase_matches_fewer_than_its_intersection(corpus):
    """What the collocations are for: a phrase of the stream often matches
    more than its target, and often fewer than the same words'
    intersection — even among 2,000 articles, where a rare collocation
    stands once (the cell's own shares are in the configuration's
    `at_this_size`)."""
    cfg, ds, game = corpus
    src = Source(_load("queries/game_queries.json"),
                 _load("traffic/game_c1.json"), ds, 11)
    more, fewer, n = 0, 0, 0
    while n < 150:
        src.next_op(0)
        terms, shape, _ = src.sent[0][-1]
        if shape != "phrase":
            continue
        n += 1
        p = int(game.match(terms, "phrase").sum())
        i = int(game.match(terms, "intersection").sum())
        assert 1 <= p <= i
        more += p > 1
        fewer += p < i
    assert more > 50 and fewer > 40


# -- the twelve keys against the reference ------------------------------------


@pytest.mark.parametrize("cmd", COMMANDS)
@pytest.mark.parametrize("shape", SHAPES)
def test_every_key_answers_as_the_reference(corpus, served, shape, cmd):
    cfg, ds, game = corpus
    es, _regime = served
    src = Source(_load("queries/game_queries.json"),
                 _load("traffic/game_c1.json"), ds, 5)
    ops = [(json.loads(body), q) for (key, (_p, body)), q in
           zip(src.distinct_ops(), src.warm) if key == f"{shape}.{cmd}"]
    assert len(ops) == 16
    worst = 0.0
    for body, query in ops:
        resp = es.search("wiki", body)
        assert ("total" in resp["hits"]) == (cmd != "TOP_10")
        answer = reduce_search(json.loads(json.dumps(resp)))
        assert game_numpy.shape_faults(answer, cmd, ds["n_docs"], 10) == \
            (0, 0), (query, answer)
        wrong, bad_total, err = game_numpy.compare(answer, query, game, 10)
        assert (wrong, bad_total) == (0, 0), (query, answer)
        worst = max(worst, err)
        # the controls, each by its own number
        if cmd != "COUNT":
            assert game_numpy.compare(answer, query, game, 10,
                                      "bf16")[2] > 1e-5 or \
                not answer["hits"]
    assert worst <= 1e-5


def test_a_union_adds_the_rows_its_regime_holds(corpus, served,
                                                monkeypatch):
    """The plane kernel of the `resident` regime adds its store's dense
    terms' rows whole (`SearchPostingsResident` moves, and stays within
    `SearchPostingsDispatched`); the plane kernel alone adds none; the
    dense steps add every term's row."""
    _cfg, ds, _game = corpus
    es, regime = served
    # no candidate list is short enough for the host rung
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    at = int(ds["bounds"][7])
    words = " ".join(ds["words"][t] for t in ds["toks"][at:at + 6])
    r0 = metrics.SEARCH_POSTINGS_RESIDENT.value
    d0 = metrics.SEARCH_POSTINGS_DISPATCHED.value
    resp = es.search("wiki", {"query": {"match": {"body": words}},
                              "size": 10, "track_total_hits": False})
    assert resp["hits"]["hits"]
    held = metrics.SEARCH_POSTINGS_RESIDENT.value - r0
    sent = metrics.SEARCH_POSTINGS_DISPATCHED.value - d0
    assert sent > 0
    assert held == {"dense": sent, "plane": 0}.get(regime, held)
    assert regime != "resident" or 0 < held < sent


def test_the_adjacency_control_fails_the_totals(corpus):
    cfg, ds, game = corpus
    src = Source(_load("queries/game_queries.json"),
                 _load("traffic/game_c1.json"), ds, 5)
    src.distinct_ops()
    bad = sum(game_numpy.compare(None, q, game, 10, "no_adjacency")[1]
              for q in src.warm if q[1] == "phrase")
    assert bad > 0
    assert all(game_numpy.compare(None, q, game, 10, "no_adjacency")[1:] ==
               (0, 0.0) for q in src.warm if q[1] != "phrase")


# -- the array phrase matcher against the per-document form --------------------


def _phrase_oracle(seg: SegmentSearcher, terms: list) -> np.ndarray:
    """`_eval_phrase`'s exact branch as it was before the array join: per
    candidate document, Python sets of positions."""
    tids = [seg.index.term_id(t) for t in terms]
    if min(tids) < 0:
        return np.empty(0, dtype=np.int32)
    cand = seg._union_postings([tids[0]])
    for t in tids[1:]:
        cand = np.intersect1d(cand, seg._union_postings([t]),
                              assume_unique=True)
    pos = [{int(d): set(int(p) for p in ps) for d, ps in
            seg.index.positions_of(t, cand).items()} for t in tids]
    out = [int(d) for d in cand
           if any(all((p + k) in pos[k][int(d)] for k in range(1, len(tids)))
                  for p in pos[0][int(d)])]
    return np.asarray(out, dtype=np.int32)


def _texts(n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    vocab = [f"w{i}" for i in range(12)]
    p = 1.0 / np.arange(1, 13)
    return [" ".join(rng.choice(vocab, size=int(rng.integers(1, 40)),
                                p=p / p.sum())) for _ in range(n)]


PHRASES = {
    "two_words": ["w0", "w1"],
    "a_term_twice": ["w0", "w0"],
    "twice_apart": ["w1", "w0", "w1"],
    "four_words": ["w2", "w0", "w1", "w0"],
    "rare_then_common": ["w11", "w0"],
    "a_term_absent": ["w0", "nosuchword"],
    "all_absent": ["nosuchword", "nosuchother"],
    "a_run_of_one_term": ["w0", "w0", "w0", "w0"],
}


@pytest.mark.parametrize("name", list(PHRASES))
def test_the_array_join_is_the_per_document_matcher(name):
    an = get_analyzer("simple")
    terms = PHRASES[name]
    ms = MultiSearcher(an)
    base = 0
    want = []
    for i, n in enumerate((700, 1, 130)):           # a phrase across segments
        texts = _texts(n, 40 + i)
        seg = SegmentSearcher(build_field_index(texts, an), an, n)
        ms.add_segment(seg, base)
        got = seg.eval_filter(QPhrase(terms))
        oracle = _phrase_oracle(seg, terms)
        assert got.dtype == np.int32 and np.array_equal(got, oracle)
        # and what the text itself says
        text_says = [d for d, t in enumerate(texts)
                     if f" {' '.join(terms)} " in f" {t} "]
        assert got.tolist() == text_says
        want.extend(base + d for d in text_says)
        base += n
    assert ms.eval_filter(QPhrase(terms)).tolist() == want
    assert ms.count_filter(QPhrase(terms)) == len(want)
    if name == "two_words":
        assert len(want) > 100


def test_no_served_plain_phrase_reads_positions_per_document(monkeypatch):
    """`positions_of` keeps its signature for slop and synonym callers; a
    phrase of plain terms with slop 0 never calls it, a sloppy one does."""
    from serenedb_tpu.search.segment import FieldIndex
    an = get_analyzer("simple")
    texts = _texts(300, 3)
    seg = SegmentSearcher(build_field_index(texts, an), an, len(texts))
    calls = []
    real = FieldIndex.positions_of
    monkeypatch.setattr(FieldIndex, "positions_of",
                        lambda self, tid, docs: calls.append(tid) or
                        real(self, tid, docs))
    plain = seg.eval_filter(QPhrase(["w0", "w1"]))
    assert len(plain) and not calls
    sloppy = seg.eval_filter(QPhrase(["w0", "w1"], slop=2))
    assert calls and set(plain) <= set(sloppy)


def test_a_phrase_after_a_delete_and_across_an_append():
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE d (id INT, body TEXT)")
    texts = _texts(400, 9)
    c.execute("INSERT INTO d VALUES " + ", ".join(
        f"({i}, '{t}')" for i, t in enumerate(texts[:300])))
    c.execute("CREATE INDEX ON d USING inverted (body) "
              "WITH (tokenizer = 'simple')")
    c.execute("SET serene_result_cache = off")

    def holds(t):
        return " w0 w1 " in f" {t} "

    def ids(sql):
        return sorted(r[0] for r in c.execute(sql).rows())

    q = "SELECT id FROM d WHERE body ## 'w0 w1'"
    assert ids(q) == [i for i in range(300) if holds(texts[i])]
    c.execute("INSERT INTO d VALUES " + ", ".join(
        f"({i}, '{texts[i]}')" for i in range(300, 400)))     # a segment
    c.execute("DELETE FROM d WHERE id % 3 = 0")
    live = [i for i in range(400) if i % 3 and holds(texts[i])]
    assert ids(q) == live
    assert c.execute("SELECT count(*) FROM d WHERE body ## 'w0 w1'"
                     ).scalar() == len(live)
    top = c.execute("SELECT id, bm25(body) s FROM d WHERE body ## 'w0 w1' "
                    "ORDER BY s DESC LIMIT 10").rows()
    assert len(top) == 10 and {r[0] for r in top} <= set(live)
    assert [r[1] for r in top] == sorted((r[1] for r in top), reverse=True)


# -- a match set too large for the host rung goes INTO the dispatch ------------


def _ledger():
    return {p["family"]: p["compiles"]
            for p in obs_device.PROGRAMS.snapshot()}


@pytest.mark.parametrize("regime", ["dense", "plane"])
def test_a_large_phrase_is_masked_on_the_device_and_never_rescored(
        regime, monkeypatch):
    if regime == "plane":
        monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    rng = np.random.default_rng(17)
    vocab = [f"w{i}" for i in range(200)]
    texts = []
    for i in range(7000):
        toks = list(rng.choice(vocab, int(rng.integers(3, 30))))
        if i % 4:                       # 5,250 articles hold the phrase
            at = int(rng.integers(0, len(toks) + 1))
            toks[at:at] = ["alpha", "beta"]
        if i % 5 == 0:                  # both words, not adjacent
            toks = ["beta"] + toks + ["alpha"]
        texts.append(" ".join(toks))
    db = Database()
    c = db.connect()
    c.execute('CREATE TABLE wiki ("_id" VARCHAR, "_source" VARCHAR, '
              "body VARCHAR)")
    c.execute("INSERT INTO wiki VALUES " + ", ".join(
        f"('{i}', '{{}}', '{t}')" for i, t in enumerate(texts)))
    c.execute("CREATE INDEX ON wiki USING inverted (body) "
              "WITH (tokenizer = 'simple')")                # prebuild
    es = EsApi(db)
    want = [i for i, t in enumerate(texts) if " alpha beta " in f" {t} "]
    assert len(want) > SegmentSearcher.MAXSCORE_CAND_CAP
    compiles = _ledger()
    gauges = (metrics.SEARCH_PHRASE_RESCORED,
              metrics.SEARCH_QUERIES_SCORED_DEVICE,
              metrics.SEARCH_QUERIES_SCORED_HOST,
              metrics.SEARCH_COUNT_MATERIALIZED)
    before = [g.value for g in gauges]
    resp = es.search("wiki", {"query": {"match_phrase": {
        "body": "alpha beta"}}, "size": 10, "track_total_hits": True})
    assert [g.value - b for g, b in zip(gauges, before)] == [0, 1, 0, 0]
    assert _ledger() == compiles                  # no program was built
    assert resp["hits"]["total"] == {"value": len(want), "relation": "eq"}
    got = [(int(h["_id"]), h["_score"]) for h in resp["hits"]["hits"]]
    # the ten best OF THE MATCH SET, as the host scores them
    from serenedb_tpu.search.index import find_index
    t = db.schemas["main"].tables["wiki"]
    seg = find_index(t, "body").searcher("body").segments[0][0]
    sc, dd = seg._cpu_score(np.asarray(want, dtype=np.int32),
                            seg.scoring_terms(QPhrase(["alpha", "beta"])),
                            10)
    assert [d for d, _ in got] == dd.tolist()
    assert np.allclose([s for _, s in got], sc, rtol=1e-6)
    # beside a conjunction and a union in one batch: each as alone
    ms = find_index(t, "body").searcher("body")
    from serenedb_tpu.search.query import QAnd, QOr, QTerm
    nodes = [QPhrase(["alpha", "beta"]),
             QAnd([QTerm("alpha"), QTerm("w3")]),
             QOr([QTerm("w1"), QTerm("w2")]), QPhrase(["w1", "w2"])]
    together = ms.topk_batch(nodes, 10)
    for (s1, d1), node in zip(together, nodes):
        s2, d2 = ms.topk(node, 10)
        assert np.array_equal(d1, d2) and np.array_equal(s1, s2)
    assert _ledger() == compiles


# -- _search runs only what the body asks for ----------------------------------


@pytest.fixture(scope="module")
def small_es():
    db = Database()
    c = db.connect()
    c.execute('CREATE TABLE wiki ("_id" VARCHAR, "_source" VARCHAR, '
              "body VARCHAR)")
    texts = _texts(500, 21)
    c.execute("INSERT INTO wiki VALUES " + ", ".join(
        f"('{i}', '{{\"id\": {i}}}', '{t}')" for i, t in enumerate(texts)))
    c.execute("CREATE INDEX ON wiki USING inverted (body) "
              "WITH (tokenizer = 'simple')")
    return EsApi(db), texts


QUERIES = {
    "term": {"match": {"body": "w3"}},
    "union": {"match": {"body": "w3 w7"}},
    "intersection": {"match": {"body": {"query": "w3 w7",
                                        "operator": "and"}}},
    "phrase": {"match_phrase": {"body": "w0 w1"}},
}


def _total(texts, shape):
    words = {"term": ["w3"], "phrase": ["w0", "w1"]}.get(shape, ["w3", "w7"])
    if shape == "phrase":
        return sum(" w0 w1 " in f" {t} " for t in texts)
    held = [[w in t.split() for w in words] for t in texts]
    return sum(all(h) if shape == "intersection" else any(h) for h in held)


@pytest.mark.parametrize("track", [True, False, 100, None])
@pytest.mark.parametrize("size", [0, 10])
@pytest.mark.parametrize("shape", list(QUERIES))
def test_the_answer_has_the_shape_the_body_asks_for(small_es, shape, size,
                                                    track):
    es, texts = small_es
    body = {"query": QUERIES[shape], "size": size}
    if track is not None:
        body["track_total_hits"] = track
    asked = (metrics.SEARCH_REQUESTS_COUNT_ONLY,
             metrics.SEARCH_REQUESTS_HITS_ONLY,
             metrics.SEARCH_REQUESTS_HITS_AND_COUNT)
    shapes = {"term": metrics.SEARCH_QUERIES_TERM,
              "union": metrics.SEARCH_QUERIES_UNION,
              "intersection": metrics.SEARCH_QUERIES_CONJUNCTION,
              "phrase": metrics.SEARCH_QUERIES_PHRASE}
    work = (metrics.SEARCH_BATCH_QUERIES, metrics.SEARCH_COUNT_BITSET,
            metrics.SEARCH_COUNT_INTERSECTED,
            metrics.SEARCH_COUNT_MATERIALIZED)
    before = [g.value for g in asked + tuple(shapes.values()) + work]
    hits = es.search("wiki", body)["hits"]
    moved = [g.value - b for g, b in
             zip(asked + tuple(shapes.values()) + work, before)]
    want_total = track is not False
    assert moved[:3] == [int(size == 0 and want_total),
                         int(size > 0 and not want_total),
                         int(size > 0 and want_total)]
    assert moved[3:7] == [int(s == shape) for s in shapes]
    # a request without hits opens no batcher slot; one without a total
    # counts nothing; no count builds a doc set
    assert moved[7] == int(size > 0)
    assert sum(moved[8:10]) == int(want_total) and moved[10] == 0
    if want_total:
        assert hits["total"] == {"value": _total(texts, shape),
                                 "relation": "eq"}
    else:
        assert "total" not in hits
    assert len(hits["hits"]) == min(size, _total(texts, shape))
    assert (hits["max_score"] is None) == (size == 0)


def test_a_page_and_its_total_join_the_phrase_once(small_es, monkeypatch):
    es, texts = small_es
    calls = []
    real = SegmentSearcher._phrase_join
    monkeypatch.setattr(SegmentSearcher, "_phrase_join",
                        lambda self, terms: calls.append(terms) or
                        real(self, terms))
    body = {"query": QUERIES["phrase"], "size": 10}
    both = es.search("wiki", dict(body, track_total_hits=True))
    assert len(calls) == 1                        # TOP_10_COUNT: once
    page = es.search("wiki", dict(body, track_total_hits=False))
    total = es.search("wiki", dict(body, size=0))
    assert len(calls) == 3          # no memo outlives its request
    assert both["hits"]["hits"] == page["hits"]["hits"]
    assert both["hits"]["total"] == total["hits"]["total"]


def test_a_phrase_search_is_one_request_with_its_join_on_the_timeline(
        small_es):
    from serenedb_tpu.obs.trace import FLIGHT
    from serenedb_tpu.server.http_server import Router
    es, _texts_ = small_es
    router = Router(es)
    body = json.dumps({"query": QUERIES["phrase"], "size": 10,
                       "track_total_hits": True}).encode()
    n0 = metrics.STAGE_HISTS["search_phrase"].count
    status, data, _ = router.handle("POST", "/wiki/_search", body)
    assert status == 200 and json.loads(data)["hits"]["hits"]
    entry = FLIGHT.last()
    assert entry["query"].startswith("POST /wiki/_search")
    assert sum(entry["stages"].values()) == entry["duration_ns"]
    assert {"search_plan", "search_phrase", "host_scan"} <= \
        set(entry["stages"])
    assert metrics.STAGE_HISTS["search_phrase"].count - n0 == 1
    # a scroll keeps its total whatever the body says
    first = es.search_scroll_start(
        "wiki", {"query": QUERIES["phrase"], "size": 5,
                 "track_total_hits": False}, "1m")
    assert first["hits"]["total"]["value"] >= len(first["hits"]["hits"]) == 5


@pytest.mark.parametrize("longest", [200_000, 60])
def test_a_column_of_articles_sorts_its_dictionary_without_a_unicode_cast(
        longest):
    """What COPY of this corpus forced (exec/tables.py: _arrow_to_column):
    a string column's dictionary is sorted by arrow, in numpy's and
    Python's code-point order, not through a fixed-width unicode copy (4 B
    x the LONGEST value for every value: 62 GB for the cell's 300,000
    articles, whose longest has 50,000 characters); a column of short
    values sorts the same way."""
    import tracemalloc

    import pyarrow as pa

    from serenedb_tpu.exec.tables import _arrow_to_column
    rng = np.random.default_rng(5)
    values = ["".join(rng.choice(list("abcxyz é中"), int(n)))
              for n in rng.integers(1, 60, 3000)]
    values[7] = "z" * longest                   # one article of the tail
    values[11] = values[12] = "é中"            # a repeated value
    tracemalloc.start()
    col = _arrow_to_column(pa.chunked_array([pa.array(values,
                                                      pa.large_string())]))
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert col.dictionary.tolist() == sorted(set(values))
    assert col.dictionary[col.data].tolist() == values
    assert peak < 50e6        # the cast would take 3,000 x 200,000 x 4 B
