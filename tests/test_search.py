"""Search core tests: segment build, filter parity vs the brute-force
semantics contract, BM25 top-k correctness, SQL pushdown."""

import numpy as np
import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.search.analysis import get_analyzer
from serenedb_tpu.search.query import (eval_query_on_text, match_phrase_brute,
                                       parse_query)
from serenedb_tpu.search.searcher import SegmentSearcher
from serenedb_tpu.search.segment import build_field_index

WORDS = ("apple banana cherry quick brown fox jumps over lazy dog search "
         "engine database index query term").split()


def make_corpus(n=300, seed=3):
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n):
        ln = rng.integers(3, 30)
        docs.append(" ".join(rng.choice(WORDS, ln)))
    return docs


@pytest.fixture(scope="module")
def corpus():
    return make_corpus()


@pytest.fixture(scope="module")
def searcher(corpus):
    an = get_analyzer("text")
    fi = build_field_index(corpus, an)
    return SegmentSearcher(fi, an, len(corpus))


QUERIES = [
    "apple",
    "apple & banana",
    "apple | cherry",
    "quick & !lazy",
    '"quick brown"',
    '"quick brown fox"',
    "qui*",
    "(apple | banana) & cherry",
    "!apple",
    "nonexistentterm",
    "apple & nonexistentterm",
]


@pytest.mark.parametrize("q", QUERIES)
def test_filter_parity_with_brute_force(searcher, corpus, q):
    an = get_analyzer("text")
    node = parse_query(q, an)
    expected = {i for i, text in enumerate(corpus)
                if eval_query_on_text(node, an, text)}
    got = set(searcher.eval_filter(node).tolist())
    assert got == expected, q


@pytest.mark.parametrize("q", ["apple", "apple | cherry", "apple & banana",
                               '"quick brown"', "qui*", "quick & !lazy"])
def test_topk_matches_cpu_reference(searcher, q):
    an = get_analyzer("text")
    node = parse_query(q, an)
    k = 10
    scores, docs = searcher.topk(node, k)
    # every returned doc must match the filter semantics
    match = set(searcher.eval_filter(node).tolist())
    assert all(int(d) in match for d in docs), q
    # scores descending
    assert all(scores[i] >= scores[i + 1] - 1e-5
               for i in range(len(scores) - 1)), q
    # exact score check vs the CPU reference over the match set
    tids = searcher.scoring_terms(node)
    if match and tids:
        ref_scores, ref_docs = searcher._cpu_score(
            np.asarray(sorted(match), dtype=np.int32), tids, k)
        np.testing.assert_allclose(scores, ref_scores[:len(scores)],
                                   rtol=2e-3, atol=1e-3)


def test_bm25_manual_formula(searcher):
    """Single-term score equals the hand-computed BM25 on one doc."""
    an = get_analyzer("text")
    node = parse_query("apple", an)
    scores, docs = searcher.topk(node, 1)
    d = int(docs[0])
    fi = searcher.index
    tid = fi.term_id(an.terms("apple")[0])   # analyzed (stemmed) form
    pd, pt = fi.postings(tid)
    tf = float(pt[np.searchsorted(pd, d)])
    df = float(fi.doc_freq[tid])
    n = searcher.num_docs
    idf = np.log(1 + (n - df + 0.5) / (df + 0.5))
    dl = float(fi.norms[d])
    expected = idf * (1.2 + 1) * tf / (tf + 1.2 * (1 - 0.75 + 0.75 * dl / fi.avgdl))
    assert scores[0] == pytest.approx(expected, rel=1e-3)


def test_phrase_positions(searcher, corpus):
    an = get_analyzer("text")
    node = parse_query('"brown fox"', an)
    got = set(searcher.eval_filter(node).tolist())
    expected = set(np.flatnonzero(
        match_phrase_brute(np.asarray(corpus, dtype=object),
                           np.asarray(["brown fox"] * len(corpus),
                                      dtype=object))).tolist())
    assert got == expected


# -- SQL integration -------------------------------------------------------

@pytest.fixture
def sql_conn(corpus):
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE docs (id INT, body TEXT)")
    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.exec.tables import MemTable
    batch = Batch.from_pydict({
        "id": list(range(len(corpus))),
        "body": list(corpus),
    })
    db.schemas["main"].tables["docs"] = MemTable("docs", batch)
    return c


def test_sql_index_pushdown_parity(sql_conn):
    q = "SELECT count(*) FROM docs WHERE body @@ 'apple & banana'"
    brute = sql_conn.execute(q).scalar()
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    ex = sql_conn.execute("EXPLAIN " + q).rows()
    assert any("SearchScan" in r[0] for r in ex)
    assert sql_conn.execute(q).scalar() == brute


def test_sql_phrase_pushdown_parity(sql_conn):
    q = "SELECT count(*) FROM docs WHERE body ## 'quick brown'"
    brute = sql_conn.execute(q).scalar()
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    assert sql_conn.execute(q).scalar() == brute


def test_sql_topk_scored(sql_conn):
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    r = sql_conn.execute(
        "SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple' "
        "ORDER BY s DESC LIMIT 5")
    ex = sql_conn.execute(
        "EXPLAIN SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple' "
        "ORDER BY s DESC LIMIT 5").rows()
    assert any("TopK" in row[0] for row in ex)
    rows = r.rows()
    assert 0 < len(rows) <= 5
    scores = [row[1] for row in rows]
    assert scores == sorted(scores, reverse=True)
    assert all(s > 0 for s in scores)


def test_sql_index_stale_after_insert_read_repairs(sql_conn):
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    sql_conn.execute("INSERT INTO docs VALUES (9999, 'zzzuniqueterm here')")
    # a stale index (data_version mismatch) is refreshed in place and
    # USED — falling back to a brute scan would silently analyze with the
    # default analyzer instead of the column's tokenizer
    assert sql_conn.execute(
        "SELECT count(*) FROM docs WHERE body @@ 'zzzuniqueterm'"
    ).scalar() == 1
    ex = sql_conn.execute(
        "EXPLAIN SELECT count(*) FROM docs WHERE body @@ 'zzzuniqueterm'"
    ).rows()
    assert any("SearchScan" in r[0] for r in ex)


def test_sql_mixed_predicate_residual(sql_conn):
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    q = ("SELECT count(*) FROM docs WHERE body @@ 'apple' AND id < 100")
    with_index = sql_conn.execute(q).scalar()
    # oracle: no index (different table name, same data via subquery trick)
    brute = sql_conn.execute(
        "SELECT count(*) FROM (SELECT * FROM docs) d "
        "WHERE body @@ 'apple' AND id < 100").scalar()
    assert with_index == brute


def test_tfidf_scorer_differs_from_bm25(sql_conn):
    sql_conn.execute("CREATE INDEX ON docs USING inverted (body)")
    bm = sql_conn.execute(
        "SELECT id, bm25(body) AS s FROM docs WHERE body @@ 'apple' "
        "ORDER BY s DESC LIMIT 500").rows()
    tf = sql_conn.execute(
        "SELECT id, tfidf(body) AS s FROM docs WHERE body @@ 'apple' "
        "ORDER BY s DESC LIMIT 500").rows()
    assert len(bm) == len(tf)
    # same match set (full), different score values (different formulas)
    assert {r[0] for r in bm} == {r[0] for r in tf}
    bm_scores = dict(bm)
    assert any(abs(bm_scores[i] - s) > 1e-6 for i, s in tf)
    # tfidf = idf * sqrt(tf) — verify one score by hand
    import numpy as np
    from serenedb_tpu.search.index import find_index
    t = sql_conn.db.schemas["main"].tables["docs"]
    idx = find_index(t, "body")
    ms = idx.searcher("body")
    searcher = ms.segments[0][0]   # single-segment index
    fi = searcher.index
    tid = fi.term_id("apple")
    if tid >= 0 and tf:
        d = int(tf[0][0])
        # find the row index of doc with id==d
        ids = t.full_batch(["id"]).column("id").to_pylist()
        row = ids.index(d)
        pd, pt = fi.postings(tid)
        tfreq = float(pt[np.searchsorted(pd, row)])
        idf = 1.0 + np.log(searcher.num_docs / (fi.doc_freq[tid] + 1.0))
        assert tf[0][1] == pytest.approx(idf * np.sqrt(tfreq), rel=1e-3)


def test_fuzzy_expansion_uncapped_matches_brute(sql_conn):
    # >128 near-terms: indexed fuzzy must equal brute force (no silent cap)
    c = sql_conn
    c.execute("CREATE TABLE many (body TEXT)")
    rows = ", ".join(f"('aaaa{chr(97 + i % 26)}{j}')"
                     for i in range(26) for j in range(6))
    c.execute(f"INSERT INTO many VALUES {rows}")
    q = "SELECT count(*) FROM many WHERE body @@ 'aaaax1~2'"
    brute = c.execute(q).scalar()
    c.execute("CREATE INDEX ON many USING inverted (body)")
    assert c.execute(q).scalar() == brute
    neg = "SELECT count(*) FROM many WHERE body @@ '!aaaax1~2'"
    assert c.execute(neg).scalar() == 156 - brute


def test_fuzzy_highlight(sql_conn):
    c = sql_conn
    r = c.execute("SELECT ts_headline('databose quirks', 'database~1')"
                  ).scalar()
    assert r == "<b>databose</b> quirks"


# -- block-max WAND pruning (reference: wand_writer.hpp / block_disjunction) --

def _wand_fixture(n_docs=6000, seed=11):
    """A corpus with realistic block-max variance: a clustered 'hot' doc-id
    region (short docs with high tf of a few terms) and a long cold tail
    (long docs, background tf only). Blocks covering the cold region get
    provably-low upper bounds — the structure WAND exploits."""
    rng = np.random.default_rng(seed)
    vocab = [f"t{i}" for i in range(40)]
    docs = []
    for d in range(n_docs):
        if d < 600:  # hot cluster: short docs, two boosted terms
            words = list(rng.choice(vocab, int(rng.integers(20, 60))))
            words += [vocab[d % 7]] * 30 + [vocab[(d + 1) % 7]] * 30
        else:        # cold tail: long docs, background term frequencies
            words = list(rng.choice(vocab, int(rng.integers(150, 300))))
        docs.append(" ".join(words))
    an = get_analyzer("simple")
    fi = build_field_index(docs, an)
    return SegmentSearcher(fi, an, n_docs), docs, an


def test_wand_pruning_parity_and_reduction():
    """Pruned top-k must equal the unpruned top-k exactly, and the pruning
    must actually drop block rows on a skewed corpus."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    searcher, docs, an = _wand_fixture()
    store = searcher._device_store()
    fi = searcher.index
    qs = ["t0 | t1", "t2 | t3 | t4", "t5", "t0 | t6 | t1"]
    nodes = [parse_query(q, an) for q in qs]
    k = 10

    # unpruned assembly (wand off) vs pruned assembly row counts
    shapes = [searcher._query_shape(n) for n in nodes]
    queries = [(np.asarray(t, dtype=np.int64), r)
               for t, r, _, _ in shapes]
    qb_off = bm25_ops.assemble_query_batch(store, searcher.num_docs,
                                           queries, fi.doc_freq)
    plans = [bm25_ops.wand_plan(
        store, t, bm25_ops.idf_lucene(searcher.num_docs, fi.doc_freq[t]),
        k, fi.avgdl, 1.2, 0.75, "bm25") for t, r, _, _ in shapes]
    qb_on = bm25_ops.assemble_query_batch(
        store, searcher.num_docs, queries, fi.doc_freq, plans=plans)
    def live_rows(qb):
        return (int((qb.row_idx != store.n_packed).sum()) +
                int((qb.raw_idx != store.n_raw).sum()))
    rows_off = live_rows(qb_off)
    rows_on = live_rows(qb_on)
    assert rows_on < rows_off, (rows_on, rows_off)

    # end-to-end parity: device top-k with pruning equals CPU reference
    out = searcher.topk_batch(nodes, k)
    for node, (scores, dd) in zip(nodes, out):
        match = searcher.eval_filter(node)
        tids = searcher.scoring_terms(node)
        ref_s, ref_d = searcher._cpu_score(match, tids, k)
        np.testing.assert_allclose(scores, ref_s[:len(scores)],
                                   rtol=2e-3, atol=1e-3)
        # doc sets must agree wherever scores are not tied at the cut
        assert set(dd.tolist()) == set(ref_d[:len(dd)].tolist()) or \
            abs(float(ref_s[len(dd) - 1]) - float(ref_s[min(len(dd), len(ref_s) - 1)])) < 1e-4


def test_wand_prune_never_drops_topk_docs():
    """Direct unit check of wand_prune: every true top-k doc's rows survive."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    searcher, docs, an = _wand_fixture(n_docs=4000, seed=5)
    store = searcher._device_store()
    fi = searcher.index
    tids = [fi.term_id("t0"), fi.term_id("t1"), fi.term_id("t2")]
    assert all(t >= 0 for t in tids)
    k = 7
    idf = bm25_ops.idf_lucene(searcher.num_docs, fi.doc_freq[np.asarray(tids)])
    plan = bm25_ops.wand_plan(store, tids, idf, k, fi.avgdl, 1.2, 0.75,
                              "bm25")
    if plan is None:
        return  # nothing prunable on this corpus — parity covered above
    kept = plan.kept
    ref_s, ref_d = searcher._cpu_score(
        np.arange(searcher.num_docs, dtype=np.int32), tids, k)
    for d in ref_d:
        d = int(d)
        for tid in tids:
            if not store.heavy[tid]:
                continue
            s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
            pd = store.flat_docs[s:e]
            i = int(np.searchsorted(pd, d))
            if i >= len(pd) or pd[i] != d:
                continue  # term doesn't hit this doc
            row = int(store.block_offsets[tid]) + i // 128
            assert row in set(kept[tid].tolist()), (d, tid)


def test_query_batch_chunking_parity():
    """The accumulator-cap query chunking must not change results: force a
    tiny cap so a batch splits, compare against the unsplit batch."""
    searcher, docs, an = _wand_fixture(n_docs=3000, seed=7)
    qs = ["t0 | t1", "t2", "t3 & t4", "t5 | t6 | t0", "t1", "t2 | t5"]
    nodes = [parse_query(q, an) for q in qs]
    base = searcher.topk_batch(nodes, 10)
    old = SegmentSearcher.ACC_ENTRY_CAP
    try:
        SegmentSearcher.ACC_ENTRY_CAP = searcher._device_store().ndocs_pad * 2
        chunked = searcher.topk_batch(nodes, 10)
    finally:
        SegmentSearcher.ACC_ENTRY_CAP = old
    for (s1, d1), (s2, d2) in zip(base, chunked):
        np.testing.assert_allclose(s1, s2, rtol=1e-6)
        assert d1.tolist() == d2.tolist()


def test_dense_path_parity_vs_scatter_and_cpu(monkeypatch):
    """The dense matmul path (small-corpus regime) must return exactly the
    scatter path's results, which must match the exhaustive CPU scorer."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    searcher, docs, an = _wand_fixture(n_docs=2500, seed=11)
    qs = ["t0 | t1", "t2", "t3 & t4", "t5 | t6 | t0", "t1 ## t2", "t9"]
    nodes = [parse_query(q, an) for q in qs]
    assert bm25_ops.dense_fits(searcher._device_store().ndocs_pad,
                               len(searcher.index.doc_freq))
    dense_out = searcher.topk_batch(nodes, 10)
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    scatter_out = searcher.topk_batch(nodes, 10)
    for node, (s1, d1), (s2, d2) in zip(nodes, dense_out, scatter_out):
        match = searcher.eval_filter(node)
        tids = searcher.scoring_terms(node)
        ref_s, ref_d = searcher._cpu_score(match, tids, 10)
        keep = ref_s > 0
        ref_s, ref_d = ref_s[keep][:10], ref_d[keep][:10]
        np.testing.assert_allclose(s1, ref_s, rtol=2e-3, atol=1e-3)
        np.testing.assert_allclose(s2, ref_s, rtol=2e-3, atol=1e-3)
        for j, (a, b) in enumerate(zip(d1.tolist(), ref_d.tolist())):
            if a != b:
                assert abs(float(s1[j]) - float(ref_s[j])) < 1e-3
        for j, (a, b) in enumerate(zip(d2.tolist(), ref_d.tolist())):
            if a != b:
                assert abs(float(s2[j]) - float(ref_s[j])) < 1e-3


def test_dense_path_tfidf_parity():
    searcher, docs, an = _wand_fixture(n_docs=1500, seed=13)
    nodes = [parse_query(q, an) for q in ["t0 | t3", "t7", "t1 & t2"]]
    out = searcher.topk_batch(nodes, 8, scorer="tfidf")
    for node, (s1, d1) in zip(nodes, out):
        match = searcher.eval_filter(node)
        tids = searcher.scoring_terms(node)
        ref_s, ref_d = searcher._cpu_score(match, tids, 8, scorer="tfidf")
        keep = ref_s > 0
        ref_s = ref_s[keep][:8]
        np.testing.assert_allclose(s1, ref_s, rtol=2e-3, atol=1e-3)


def test_cpu_wand_topk_matches_exhaustive():
    """cpu_topk_wand (block-max WAND + MaxScore host scorer — the honest
    bench baseline) must equal exhaustive scoring exactly."""
    searcher, docs, an = _wand_fixture(n_docs=4000, seed=17)
    qs = ["t0 | t1", "t2 | t3 | t4", "t5", "t0 | t6 | t1", "t1 & t3"]
    for q in qs:
        node = parse_query(q, an)
        tids, req, mask, empty = searcher._query_shape(node)
        assert not (mask or empty)
        ws, wd = searcher.cpu_topk_wand(tids, 10, require_all=req)
        match = searcher.eval_filter(node)
        es, ed = searcher._cpu_score(match, tids, 10)
        keep = es > 0
        es, ed = es[keep][:10], ed[keep][:10]
        np.testing.assert_allclose(ws, es, rtol=1e-6)
        for j, (a, b) in enumerate(zip(wd.tolist(), ed.tolist())):
            if a != b:
                assert abs(float(ws[j]) - float(es[j])) < 1e-6


def test_packed_store_exception_rows_and_compression():
    """Posting rows with doc gaps ≥ 2^16 or tf ≥ 2^8 must fall back to the
    raw exception plane with exact scores, and the packed layout must
    actually shrink the HBM tile footprint."""
    from serenedb_tpu.ops import bm25 as bm25_ops
    rng = np.random.default_rng(3)
    n_docs = 300_000
    # term 0: sparse spread over the full doc space → huge gaps (raw rows);
    # term 1: dense cluster with one giant tf (raw via tf overflow);
    # term 2: a normal dense term (packed rows)
    # deterministic gap > 2^16 between the first two postings → the row
    # must take the raw exception plane
    d0 = np.concatenate([[0], 70_000 + np.arange(63) * 3000]) \
        .astype(np.int32)
    d1 = np.arange(100, 356, dtype=np.int32)
    d2 = np.sort(rng.choice(5000, 2000, replace=False)).astype(np.int32)
    post_docs = np.concatenate([d0, d1, d2])
    t1 = np.ones(len(d1), dtype=np.int32)
    t1[7] = 5000   # tf overflow
    post_tfs = np.concatenate([
        rng.integers(1, 5, len(d0)).astype(np.int32), t1,
        rng.integers(1, 5, len(d2)).astype(np.int32)])
    offsets = np.asarray([0, len(d0), len(d0) + len(d1),
                          len(post_docs)], dtype=np.int64)
    doc_freq = np.asarray([len(d0), len(d1), len(d2)], dtype=np.int32)
    norms = rng.integers(5, 60, n_docs).astype(np.int32)
    store = bm25_ops.build_block_store(offsets, post_docs, post_tfs,
                                      doc_freq, norms, n_docs)
    assert store.n_raw > 1, "expected raw exception rows"
    assert store.n_packed > 0, "expected packed rows"
    # the gap-overflow row (term 0) and the tf-overflow row (term 1, first
    # block holds tf=5000) must be in the raw plane
    assert store.row_plane[int(store.block_offsets[0])] == 1
    assert store.row_plane[int(store.block_offsets[1])] == 1
    # term 2 is dense and small-valued → packed
    assert store.row_plane[int(store.block_offsets[2])] == 0
    assert store.hbm_bytes < store.hbm_bytes_raw_equiv * 0.6, \
        (store.hbm_bytes, store.hbm_bytes_raw_equiv)

    from serenedb_tpu.search.segment import FieldIndex, _add_block_max
    fi = FieldIndex(
        terms=np.asarray(["aa", "bb", "cc"], dtype=object),
        doc_freq=doc_freq, offsets=offsets, post_docs=post_docs,
        post_tfs=post_tfs,
        pos_offsets=np.zeros(len(post_docs) + 1, dtype=np.int64),
        positions=np.empty(0, dtype=np.int32), norms=norms,
        block_max_tf=np.empty(0, dtype=np.int32),
        block_offsets=np.zeros(4, dtype=np.int64),
        total_tokens=int(post_tfs.sum()))
    _add_block_max(fi)
    s = SegmentSearcher(fi, get_analyzer("simple"), n_docs)
    s._dev = store
    for q, req in [(parse_query("aa", s.analyzer), 0),
                   (parse_query("bb", s.analyzer), 0),
                   (parse_query("aa | cc", s.analyzer), 0),
                   (parse_query("bb & cc", s.analyzer), 2)]:
        tids = s.scoring_terms(q)
        dev_s, dev_d = s.topk_batch([q], 10)[0]
        match = s.eval_filter(q)
        ref_s, ref_d = s._cpu_score(match, tids, 10)
        keep = ref_s > 0
        ref_s, ref_d = ref_s[keep][:10], ref_d[keep][:10]
        np.testing.assert_allclose(dev_s, ref_s, rtol=2e-3, atol=1e-3)
        for j, (a, b) in enumerate(zip(dev_d.tolist(), ref_d.tolist())):
            if a != b:
                assert abs(float(dev_s[j]) - float(ref_s[j])) < 1e-3


@pytest.fixture(scope="module")
def long_doc_segment():
    """600 short documents and one of 70,000 tokens that holds every
    word 35 times: its tfs and gaps fit the packed widths, its LENGTH
    does not."""
    rng = np.random.default_rng(8)
    vocab = 2000
    p = 1.0 / np.arange(1, vocab + 1) ** 0.9
    p /= p.sum()
    docs = [" ".join(f"w{t}" for t in rng.choice(vocab, int(n), p=p))
            for n in rng.integers(20, 200, 600)]
    docs.insert(300, " ".join(f"w{i % vocab}" for i in range(70_000)))
    an = get_analyzer("simple")
    return SegmentSearcher(build_field_index(docs, an), an, len(docs))


def test_a_row_with_a_length_past_uint16_takes_the_raw_plane(
        long_doc_segment):
    from serenedb_tpu.ops import bm25 as bm25_ops
    s = long_doc_segment
    assert int(s.index.norms[300]) == 70_000
    store = s._device_store()
    assert int(np.asarray(store.raw_dls).max()) == 70_000
    assert int(np.asarray(store.block_dls).max()) < 1 << 16
    assert store.n_packed > 0
    heavy = np.flatnonzero(store.heavy)
    assert len(heavy) > 100
    for tid in heavy:
        b0, b1 = store.block_offsets[tid], store.block_offsets[tid + 1]
        pd = s.index.postings(int(tid))[0]
        at = b0 + int(np.searchsorted(pd, 300)) // bm25_ops.BLOCK
        planes = store.row_plane[b0:b1]
        # the row that holds document 300 is raw for its length alone
        # (its tf is 35, its gaps are small); the term's other rows pack
        assert planes[at - b0] == 1
        assert store.block_bmax_tf[at] < 1 << 8
        assert not np.delete(planes, at - b0).any()
        slot = store.row_slot[at]
        lane = np.flatnonzero(np.asarray(store.raw_docs[slot]) == 300)
        assert np.asarray(store.raw_dls[slot])[lane].tolist() == [70_000]


@pytest.mark.parametrize("scorer", ["bm25", "tfidf", "lm_dirichlet",
                                    "jelinek_mercer", "dfi"])
def test_topk_over_raw_length_rows_is_the_reference(
        long_doc_segment, scorer, monkeypatch):
    from serenedb_tpu.ops import bm25 as bm25_ops
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    s = long_doc_segment
    for text in ["w0 & w3", "w1 & w2 & w40", "w5 & w700"]:
        q = parse_query(text, s.analyzer)
        tids = s.scoring_terms(q)
        tiers = [None]
        dev_s, dev_d = s.topk_batch([q], 10, scorer, tiers=tiers)[0]
        assert tiers == ["device"]
        match = s.eval_filter(q)
        assert 300 in match.tolist()
        ref_s, ref_d = s._cpu_score(match, tids, 10, scorer)
        keep = ref_s > 0
        ref_s, ref_d = ref_s[keep][:10], ref_d[keep][:10]
        np.testing.assert_allclose(dev_s, ref_s, rtol=2e-5)
        for j, (a, b) in enumerate(zip(dev_d.tolist(), ref_d.tolist())):
            assert a == b or np.isclose(dev_s[j], ref_s[j], rtol=2e-5)
        # the long document is scored by its own length: the same term
        # counts in a document of the median length would rank first
        if scorer == "bm25":
            assert 300 not in dev_d.tolist()
