"""Batched search serving (search/batcher.py): parity matrix,
coalescing mechanics, error isolation, metrics, and the cache contract.

The core contract under test: per-query top-k results are BIT-IDENTICAL
(scores, doc ids, tie order) between `serene_search_batch = on` (queries
coalesce into shared scoring dispatches) and `= off` (the serial-dispatch
parity oracle), at any worker count, with the fragment cache on or off —
which is also exactly why serene_search_batch stays out of the result
cache's RESULT_AFFECTING_SETTINGS digest.
"""

import threading
import time

import numpy as np
import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.search.analysis import get_analyzer
from serenedb_tpu.search.batcher import BATCHER, SearchBatcher, batched_topk
from serenedb_tpu.search.query import parse_query
from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
from serenedb_tpu.search.segment import build_field_index
from serenedb_tpu.utils import metrics

WORDS = ("apple banana cherry quick brown fox jumps over lazy dog search "
         "engine database index query term").split()


def _make_db(n=600, seed=7):
    rng = np.random.default_rng(seed)
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE docs (id INT, body TEXT)")
    vals = []
    for i in range(n):
        if i % 97 == 0:
            vals.append(f"({i}, NULL)")          # NULL text rows
        elif i % 13 == 0:
            # tie-heavy: identical docs score identically — tie order
            # must be the deterministic doc-id order in both modes
            vals.append(f"({i}, 'apple banana apple')")
        else:
            body = " ".join(rng.choice(WORDS, rng.integers(3, 24)))
            vals.append(f"({i}, '{body}')")
    c.execute("INSERT INTO docs VALUES " + ", ".join(vals))
    c.execute("CREATE INDEX ON docs USING inverted (body)")
    return db


@pytest.fixture(scope="module")
def db():
    return _make_db()


#: the parity query set: single-term, 2-term conjunction, phrase,
#: filtered (residual keeps it off the top-k pushdown → stream+score
#: path), tie-heavy, empty-result, k > hits, and a tfidf scorer
QUERIES = [
    ("SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple' "
     "ORDER BY s DESC LIMIT 10"),
    ("SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple & banana' "
     "ORDER BY s DESC LIMIT 10"),
    ("SELECT id, bm25(body) AS s FROM docs WHERE body ## 'quick brown' "
     "ORDER BY s DESC LIMIT 10"),
    ("SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple | dog' "
     "AND id < 300 ORDER BY s DESC, id LIMIT 10"),
    ("SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'banana' "
     "ORDER BY s DESC LIMIT 10"),
    ("SELECT id FROM docs WHERE body @@ 'zzzznothing' "
     "ORDER BY bm25(body) DESC LIMIT 5"),
    ("SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'quick & fox' "
     "ORDER BY s DESC LIMIT 5000"),
    ("SELECT id, tfidf(body) AS s FROM docs WHERE body @@ 'cherry | dog' "
     "ORDER BY s DESC LIMIT 10"),
]


def _run_queries(db, queries, batch, workers, cache, threads=4):
    """Each query executed `threads` times concurrently on separate
    sessions; returns {query: [rows per thread]}."""
    out = {}
    errs = []

    def run(q, slot, res):
        try:
            conn = db.connect()
            conn.execute(f"SET serene_search_batch = {batch}")
            conn.execute(f"SET serene_workers = {workers}")
            conn.execute(f"SET serene_result_cache = {cache}")
            bar.wait(timeout=30)
            res[slot] = conn.execute(q).rows()
        except Exception as e:                     # pragma: no cover
            errs.append(e)

    for q in queries:
        res = [None] * threads
        bar = threading.Barrier(threads)
        ts = [threading.Thread(target=run, args=(q, i, res))
              for i in range(threads)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
        assert not errs, errs
        out[q] = res
    return out


def test_parity_matrix(db):
    """batched on/off × workers 1/4 × fragment cache on/off: every
    combination returns the serial oracle's exact rows (scores included —
    engine rows surface the f32 bits as python floats)."""
    # oracle context: defaults except batching off
    oc = db.connect()
    oc.execute("SET serene_search_batch = off")
    oc.execute("SET serene_result_cache = off")
    oc.execute("SET serene_workers = 1")
    oracle = {q: oc.execute(q).rows() for q in QUERIES}
    for batch in ("on", "off"):
        for workers in (1, 4):
            for cache in ("on", "off"):
                got = _run_queries(db, QUERIES, batch, workers, cache)
                for q in QUERIES:
                    for rows in got[q]:
                        assert rows == oracle[q], \
                            (batch, workers, cache, q, rows, oracle[q])


def test_query_batched_with_itself(db):
    """The same query coalescing with itself (8 concurrent submissions)
    returns identical rows on every thread."""
    q = QUERIES[0]
    oc = db.connect()
    oc.execute("SET serene_search_batch = off")
    oc.execute("SET serene_result_cache = off")
    ref = oc.execute(q).rows()
    got = _run_queries(db, [q], "on", 4, "off", threads=8)
    assert all(rows == ref for rows in got[q])


def test_plane_path_parity_packed_regime(db, monkeypatch):
    """Force the packed-plane regime (no dense matmul) so the plane
    kernel scores this corpus, then assert searcher-level bit parity:
    batched vs solo dispatch, including duplicate nodes, ties, and
    k > hits."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    an = get_analyzer("text")
    rng = np.random.default_rng(11)
    docs = [" ".join(rng.choice(WORDS, rng.integers(3, 24)))
            for _ in range(700)]
    docs[::13] = ["apple banana apple"] * len(docs[::13])   # ties
    fi = build_field_index(docs, an)
    ms = MultiSearcher(an)
    ms.add_segment(SegmentSearcher(fi, an, len(docs)), 0)
    qs = ["apple", "apple | dog", "apple & banana", '"quick brown"',
          "zzznothing", "banana | fox | dog", "apple"]
    nodes = [parse_query(q, an) for q in qs]
    for k in (3, 10, 5000):
        solo = [ms.topk_batch([n], k)[0] for n in nodes]
        batched = ms.topk_batch(nodes, k)
        for i in range(len(nodes)):
            assert np.array_equal(batched[i][0].view(np.uint32),
                                  solo[i][0].view(np.uint32)), (k, qs[i])
            assert np.array_equal(batched[i][1], solo[i][1]), (k, qs[i])


def test_multi_segment_plane_parity(monkeypatch):
    """Global idf/avgdl spanning segments: batched per-segment plane
    results merge to the solo bits."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    an = get_analyzer("text")
    ms = MultiSearcher(an)
    base = 0
    for si in range(3):
        rng = np.random.default_rng(20 + si)
        docs = [" ".join(rng.choice(WORDS, rng.integers(3, 24)))
                for _ in range(300 + 40 * si)]
        fi = build_field_index(docs, an)
        ms.add_segment(SegmentSearcher(fi, an, len(docs)), base)
        base += len(docs)
    nodes = [parse_query(q, an)
             for q in ("apple", "apple | dog", "cherry | term")]
    solo = [ms.topk_batch([n], 10)[0] for n in nodes]
    batched = ms.topk_batch(nodes, 10)
    for i in range(len(nodes)):
        assert np.array_equal(batched[i][0].view(np.uint32),
                              solo[i][0].view(np.uint32))
        assert np.array_equal(batched[i][1], solo[i][1])


# -- batcher mechanics (stub searcher) ------------------------------------


class _StubSearcher:
    def __init__(self, delay=0.0, poison=None):
        self.delay = delay
        self.poison = poison
        self.calls: list[list] = []
        self._lock = threading.Lock()

    def topk_batch(self, nodes, k, scorer="bm25", mesh_n=0):
        with self._lock:
            self.calls.append(list(nodes))
        if self.delay:
            time.sleep(self.delay)
        if self.poison is not None and any(n is self.poison for n in nodes):
            raise ValueError("poisoned query")
        return [(np.asarray([float(len(nodes))], dtype=np.float32),
                 np.asarray([hash(n) % 97], dtype=np.int64))
                for n in nodes]

    def topk(self, node, k, scorer="bm25", mesh_n=0):
        return self.topk_batch([node], k, scorer, mesh_n)[0]

    def probe_topk(self, node, k, scorer="bm25", mesh_n=0):
        return None


def test_batcher_coalesces_under_load():
    """While one dispatch is in flight, arrivals queue and fold into the
    next dispatch — group-commit batching."""
    b = SearchBatcher()
    stub = _StubSearcher(delay=0.15)
    results = {}

    def submit(name):
        results[name] = b.submit(stub, name, 10, "bm25", 0, 0.5, 128)

    t1 = threading.Thread(target=submit, args=("q0",))
    t1.start()
    time.sleep(0.05)          # q0 is mid-dispatch now
    rest = [threading.Thread(target=submit, args=(f"q{i}",))
            for i in range(1, 6)]
    [t.start() for t in rest]
    t1.join(timeout=10)
    [t.join(timeout=10) for t in rest]
    assert len(results) == 6
    sizes = sorted(len(c) for c in stub.calls)
    assert sizes[0] == 1 and sizes[-1] >= 2, sizes     # coalescing happened
    for name, (out, stats) in results.items():
        assert stats["queries"] == float(out[0][0])    # batch size echoed


def test_batcher_lone_query_never_waits():
    """A query alone in its group dispatches immediately — far faster
    than the configured window."""
    b = SearchBatcher()
    stub = _StubSearcher()
    t0 = time.perf_counter()
    out, stats = b.submit(stub, "solo", 10, "bm25", 0, 5.0, 128)
    assert time.perf_counter() - t0 < 1.0
    assert stats["queries"] == 1


def test_batcher_batch_max_splits():
    b = SearchBatcher()
    stub = _StubSearcher(delay=0.1)
    done = []

    def submit(name):
        done.append(b.submit(stub, name, 10, "bm25", 0, 0.4, 2))

    t1 = threading.Thread(target=submit, args=("a",))
    t1.start()
    time.sleep(0.03)
    rest = [threading.Thread(target=submit, args=(n,))
            for n in ("b", "c", "d", "e")]
    [t.start() for t in rest]
    t1.join(timeout=10)
    [t.join(timeout=10) for t in rest]
    assert len(done) == 5
    assert max(len(c) for c in stub.calls) <= 2
    # every query scored exactly once — a claimer whose queue overflowed
    # batch_max must take its own entry along, never leave it orphaned
    # for a redundant later dispatch
    assert sorted(n for c in stub.calls for n in c) == \
        ["a", "b", "c", "d", "e"]
    # and no idle group stays behind pinning the searcher
    assert not b._groups


def test_batcher_error_isolation_serial_retry():
    """A dispatch poisoned by one query retries every member serially:
    siblings succeed, only the poisoned caller raises."""
    b = SearchBatcher()
    stub = _StubSearcher(delay=0.15, poison="BAD")
    outs, errs = {}, {}

    def submit(name):
        try:
            outs[name] = b.submit(stub, name, 10, "bm25", 0, 0.5, 128)
        except ValueError as e:
            errs[name] = e

    t1 = threading.Thread(target=submit, args=("g1",))
    t1.start()
    time.sleep(0.05)
    others = [threading.Thread(target=submit, args=(n,))
              for n in ("BAD", "g2", "g3")]
    [t.start() for t in others]
    t1.join(timeout=10)
    [t.join(timeout=10) for t in others]
    assert set(outs) == {"g1", "g2", "g3"}
    assert set(errs) == {"BAD"}
    # the poisoned coalesced dispatch really happened before the retries
    assert any(len(c) > 1 and "BAD" in c for c in stub.calls)


def _plane_segment(n=700, seed=11):
    an = get_analyzer("text")
    rng = np.random.default_rng(seed)
    docs = [" ".join(rng.choice(WORDS, rng.integers(3, 24)))
            for _ in range(n)]
    return SegmentSearcher(build_field_index(docs, an), an, len(docs)), an


def _bits_equal(a, b):
    return (np.array_equal(a[0].view(np.uint32), b[0].view(np.uint32))
            and np.array_equal(a[1], b[1]))


class _PoisonWrap:
    """Real scoring, except batches containing the poison node raise —
    the batcher must serial-retry every member on its own thread."""

    def __init__(self, seg, poison):
        self.seg, self.poison = seg, poison

    def topk_batch(self, nodes, k, scorer="bm25", mesh_n=0):
        if any(n is self.poison for n in nodes):
            raise ValueError("poisoned query")
        return self.seg.topk_batch(nodes, k, scorer, mesh_n=mesh_n)

    def topk(self, node, k, scorer="bm25", mesh_n=0):
        return self.topk_batch([node], k, scorer, mesh_n)[0]

    def probe_topk(self, node, k, scorer="bm25", mesh_n=0):
        return None


def test_batcher_poison_isolated_under_device_tier(monkeypatch):
    """A poisoned query coalesced with siblings the plane kernel scores
    fails ONLY its own caller; every sibling's serial retry returns the
    bits of a dispatch of its own."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    seg, an = _plane_segment()
    good = [parse_query(q, an)
            for q in ("apple | dog", "banana | fox | dog")]
    poison = parse_query("cherry | term", an)
    ref = [seg.topk_batch([n], 5000)[0] for n in good]
    wrap = _PoisonWrap(seg, poison)
    b = SearchBatcher()
    results, errors = {}, {}
    bar = threading.Barrier(3)

    def run(node, slot):
        bar.wait(timeout=30)
        try:
            results[slot] = b.submit(wrap, node, 5000, "bm25", 0, 0.5, 128)
        except ValueError as e:
            errors[slot] = e
    ts = [threading.Thread(target=run, args=(n, i))
          for i, n in enumerate(good + [poison])]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    assert set(errors) == {2}, "poison must fail exactly its own caller"
    for i in range(2):
        out, _stats = results[i]
        assert _bits_equal(out, ref[i]), i


@pytest.mark.parametrize("query,k,tier", [
    ("apple | dog", 5000, "device"),             # past MaxScore: the plane
    ("banana | fox | engine", 10, "host"),       # MaxScore candidates
    ("zzznothing", 10, "host")])                 # no indexed term
def test_the_batcher_scores_on_the_tier_topk_batch_alone_takes(
        monkeypatch, query, k, tier):
    """Where a question is scored is chosen from the store and the
    question: through `batched_topk` it moves SearchQueriesScoredDevice /
    ...Host exactly as through `topk_batch` alone, on any backend."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    from serenedb_tpu.utils.config import REGISTRY as SETTINGS
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    prior = (SETTINGS.get_global("serene_search_batch"),
             SETTINGS.get_global("serene_result_cache"))
    SETTINGS.set_global("serene_search_batch", True)
    SETTINGS.set_global("serene_result_cache", False)
    try:
        seg, an = _plane_segment()
        ms = MultiSearcher(an)
        ms.add_segment(seg, 0)
        node = parse_query(query, an)

        def moved(call):
            d0 = metrics.SEARCH_QUERIES_SCORED_DEVICE.value
            h0 = metrics.SEARCH_QUERIES_SCORED_HOST.value
            out = call()
            return (out, metrics.SEARCH_QUERIES_SCORED_DEVICE.value - d0,
                    metrics.SEARCH_QUERIES_SCORED_HOST.value - h0)

        alone, d_a, h_a = moved(lambda: ms.topk_batch([node], k)[0])
        (batched, stats), d_b, h_b = moved(
            lambda: batched_topk(ms, node, k))
    finally:
        SETTINGS.set_global("serene_search_batch", prior[0])
        SETTINGS.set_global("serene_result_cache", prior[1])
    assert stats is not None              # it went through the batcher
    assert (d_b, h_b) == (d_a, h_a) == \
        ((1, 0) if tier == "device" else (0, 1))
    assert _bits_equal(batched, alone)


def test_batched_topk_cache_hit_skips_batch(db):
    """A fragment-cache hit returns immediately (stats None) and never
    occupies a batch slot."""
    from serenedb_tpu.engine import CURRENT_CONNECTION
    from serenedb_tpu.search.index import find_index
    conn = db.connect()
    # explicit: this test exercises ON-mode mechanics even under the
    # verify_tier1.sh SERENE_SEARCH_BATCH=off global pass
    conn.execute("SET serene_search_batch = on")
    t = db.resolve_table(["docs"])
    idx = find_index(t, "body")
    searcher = idx.searcher("body")
    an = get_analyzer("text")
    node = parse_query("apple | term", an)
    tok = CURRENT_CONNECTION.set(conn)
    try:
        out1, stats1 = batched_topk(searcher, node, 10, "bm25", 0,
                                    conn.settings)
        assert stats1 is not None          # miss: went through the batcher
        d0 = metrics.SEARCH_BATCH_QUERIES.value
        out2, stats2 = batched_topk(searcher, node, 10, "bm25", 0,
                                    conn.settings)
        assert stats2 is None              # probe hit: no batch entry
        assert metrics.SEARCH_BATCH_QUERIES.value == d0
        assert np.array_equal(out1[0].view(np.uint32),
                              out2[0].view(np.uint32))
        assert np.array_equal(out1[1], out2[1])
    finally:
        CURRENT_CONNECTION.reset(tok)


# -- satellites -----------------------------------------------------------


def test_msearch_error_isolation(db):
    """A malformed body sandwiched between valid items reports inline on
    that item only — siblings in the same coalesced dispatch succeed."""
    from serenedb_tpu.server.es_api import EsApi
    es = EsApi(db)
    for i in range(30):
        es.index_doc("msi", {"body": WORDS[i % len(WORDS)] + " apple"})
    es.refresh("msi")
    body = "\n".join([
        '{"index": "msi"}',
        '{"query": {"match": {"body": "apple"}}}',
        '{"index": "msi"}',
        '{"query": {"bogus_kind": {}}}',                    # bad query type
        '{"index": "msi"}',
        'not valid json {{{',                               # bad JSON
        '{"index": "msi"}',
        '{"query": {"match": {"body": "banana"}}}',
    ]) + "\n"
    res = es.msearch(body)
    r = res["responses"]
    assert len(r) == 4
    assert r[0]["status"] == 200 and r[0]["hits"]["total"]["value"] > 0
    assert r[1]["status"] == 400 and "error" in r[1]
    assert r[2]["status"] == 400 and "error" in r[2]
    assert r[3]["status"] == 200
    # and the batch never poisoned the siblings' result content
    solo = es.search("msi", {"query": {"match": {"body": "apple"}}})
    assert solo["hits"]["hits"] == r[0]["hits"]["hits"]


def test_gauges_and_exports(db):
    """SearchBatch{Dispatches,Queries,Coalesced} and the SearchBatchWindow
    histogram (its sum is the queued time: no gauge repeats it) exist,
    move under load, and surface through /metrics and the /_stats metric
    map."""
    base = {g: metrics.REGISTRY.snapshot()[g]
            for g in ("SearchBatchDispatches", "SearchBatchQueries",
                      "SearchBatchCoalesced")}
    counts0, wait0 = metrics.SEARCH_BATCH_WINDOW_HIST.snapshot()
    _run_queries(db, [QUERIES[0], QUERIES[4]], "on", 4, "off", threads=6)
    snap = metrics.REGISTRY.snapshot()
    assert snap["SearchBatchDispatches"] > base["SearchBatchDispatches"]
    assert snap["SearchBatchQueries"] > base["SearchBatchQueries"]
    counts1, wait1 = metrics.SEARCH_BATCH_WINDOW_HIST.snapshot()
    assert sum(counts1) - sum(counts0) == \
        snap["SearchBatchQueries"] - base["SearchBatchQueries"]
    assert wait1 >= wait0
    from serenedb_tpu.obs.export import prometheus_text, stats_json
    text = prometheus_text()
    for prom in ("serenedb_search_batch_dispatches",
                 "serenedb_search_batch_queries",
                 "serenedb_search_batch_window_seconds_sum",
                 "serenedb_search_batch_coalesced"):
        assert prom in text
    assert "serenedb_search_batch_window_wait_ns" not in text
    assert "serenedb_query_time_ns" not in text
    assert "SearchBatchDispatches" in stats_json()["metrics"]


def test_result_cache_settings_exclusion():
    """serene_search_batch must NOT key the result cache: batching is
    bit-identical by contract (the parity matrix above is the proof), so
    keying on it would split identical entries."""
    from serenedb_tpu.cache.result import RESULT_AFFECTING_SETTINGS
    assert "serene_search_batch" not in RESULT_AFFECTING_SETTINGS
    assert "serene_search_batch_window_ms" not in RESULT_AFFECTING_SETTINGS
    assert "serene_search_batch_max" not in RESULT_AFFECTING_SETTINGS


def test_explain_analyze_batch_line(db):
    conn = db.connect()
    conn.execute("SET serene_search_batch = on")
    conn.execute("SET serene_result_cache = off")
    rows = conn.execute("EXPLAIN ANALYZE " + QUERIES[0]).rows()
    lines = [r[0] for r in rows]
    assert any("Batch: queries=" in ln and "shared_scoring=" in ln
               for ln in lines), lines


@pytest.mark.slow
def test_qps_smoke():
    """Aggregate throughput smoke: 16 concurrent distinct 2-term top-10
    searches, batched vs serial — batched must not lose. Kept loose:
    a speed is the benchmark's to read (`msmarco.search_c32`)."""
    db = _make_db(n=4000, seed=3)

    def drive(batch):
        qs = [f"SELECT id, bm25(body) AS s FROM docs WHERE body @@ "
              f"'{WORDS[i % 10]} | {WORDS[(i + 5) % 13]}' "
              f"ORDER BY s DESC LIMIT 10" for i in range(16)]
        bar = threading.Barrier(16)

        def run(i):
            conn = db.connect()
            conn.execute(f"SET serene_search_batch = {batch}")
            conn.execute("SET serene_result_cache = off")
            bar.wait(timeout=30)
            for _ in range(3):
                conn.execute(qs[i])
        ts = [threading.Thread(target=run, args=(i,)) for i in range(16)]
        t0 = time.perf_counter()
        [t.start() for t in ts]
        [t.join(timeout=120) for t in ts]
        return time.perf_counter() - t0

    drive("on")                    # warm compiles
    t_on = drive("on")
    t_off = drive("off")
    assert t_on < t_off * 1.5, (t_on, t_off)


# -- the closed program set behind the batcher (ISSUE 26) -------------------


@pytest.mark.parametrize("n_queries", [1, 8, 9, 33, 80])
def test_batch_of_any_size_keeps_serial_bits_on_the_plane_kernel(
        monkeypatch, n_queries):
    """A coalesced batch is fitted to a rung of the store's program ladder
    (1 / 8 / 32 queries) and split past the largest: per query the bits
    are those of a dispatch of its own."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    an = get_analyzer("text")
    rng = np.random.default_rng(5)
    docs = [" ".join(rng.choice(WORDS, rng.integers(3, 24)))
            for _ in range(900)]
    docs[::13] = ["apple banana apple"] * len(docs[::13])   # ties
    fi = build_field_index(docs, an)
    ms = MultiSearcher(an)
    ms.add_segment(SegmentSearcher(fi, an, len(docs)), 0)
    pool = ["apple", "apple | dog", "apple & banana", '"quick brown"',
            "zzznothing", "banana | fox | dog", "quick & fox & jumps",
            "search | engine | database | index | query | term"]
    nodes = [parse_query(pool[i % len(pool)], an) for i in range(n_queries)]
    solo = [ms.topk_batch([n], 10)[0] for n in nodes]
    batched = ms.topk_batch(nodes, 10)
    for i in range(n_queries):
        assert np.array_equal(batched[i][0].view(np.uint32),
                              solo[i][0].view(np.uint32)), i
        assert np.array_equal(batched[i][1], solo[i][1]), i


def test_batcher_counts_each_query_once_by_its_tier(db):
    """Through the batcher, SearchQueriesScoredDevice + ...Host moves by
    the queries answered (fragment cache off: no query is neither)."""
    conn = db.connect()
    conn.execute("SET serene_result_cache = off")
    before = (metrics.SEARCH_QUERIES_SCORED_DEVICE.value +
              metrics.SEARCH_QUERIES_SCORED_HOST.value)
    for q in QUERIES[:2] + QUERIES[4:6]:
        conn.execute(q).rows()
    after = (metrics.SEARCH_QUERIES_SCORED_DEVICE.value +
             metrics.SEARCH_QUERIES_SCORED_HOST.value)
    assert after - before == 4
