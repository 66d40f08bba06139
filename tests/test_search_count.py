"""`count_filter`: the exact total of a search without its doc set.

A node whose doc set is a union of posting lists is counted through doc
bitsets (dense terms keep one; sparse terms add the ids not yet set);
every other shape takes `len(eval_filter)`. Either way the number is
`_matching_docs`' length: same docs, same NULL rule.
"""

import sys
import threading

import numpy as np
import pytest

from serenedb_tpu.search.analysis import get_analyzer
from serenedb_tpu.search.query import (QAnd, QFuzzy, QNot, QNothing, QOr,
                                       QPhrase, QPrefix, QRegex, QTerm)
from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
from serenedb_tpu.search.segment import build_field_index
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS

AN = get_analyzer("text")
VOCAB = [f"w{i}" for i in range(120)]


def _texts(n: int, seed: int, nulls: bool = False) -> list:
    """Zipf words: w0 in most rows (a dense term at any size), w100 in
    hardly any (sparse wherever a bitset row outweighs a few ids)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, len(VOCAB) + 1)
    p /= p.sum()
    out = [" ".join(rng.choice(VOCAB, size=int(rng.integers(1, 10)), p=p))
           for _ in range(n)]
    if nulls:
        for i in range(0, n, 7):
            out[i] = None
    return out


def _store(sizes: list, nulls: bool = False):
    """(MultiSearcher over segments of these sizes, the column's validity
    by global row or None)."""
    ms = MultiSearcher(AN)
    base = 0
    valid = []
    for i, n in enumerate(sizes):
        texts = _texts(n, 100 + i, nulls)
        ms.add_segment(SegmentSearcher(build_field_index(texts, AN), AN, n),
                       base)
        valid.extend(t is not None for t in texts)
        base += n
    return ms, (np.asarray(valid, dtype=bool) if nulls else None)


STORES = {
    "one_segment": lambda: _store([640]),
    "three_segments": lambda: _store([300, 130, 77]),
    "not_a_multiple_of_64": lambda: _store([1037]),
    "under_64_docs": lambda: _store([37]),
    "an_empty_segment": lambda: _store([200, 0, 100]),
    "null_rows": lambda: _store([500, 93], nulls=True),
}

#: name → (node, the counter its count ticks: True the bitsets' (a union),
#: None the intersections' (a conjunction of terms, a plain phrase), False
#: the materialized doc set's)
NODES = {
    "one_term": (QTerm("w0"), True),
    "or_dense_and_sparse": (QOr([QTerm("w0"), QTerm("w1"), QTerm("w90"),
                                 QTerm("w100")]), True),
    "all_sparse": (QOr([QTerm("w80"), QTerm("w95"), QTerm("w100")]), True),
    "all_dense": (QOr([QTerm("w0"), QTerm("w1"), QTerm("w2")]), True),
    "a_term_twice": (QOr([QTerm("w1"), QTerm("w70"), QTerm("w1"),
                          QTerm("w70")]), True),
    "an_absent_term": (QOr([QTerm("w2"), QTerm("nosuchword")]), True),
    "only_absent": (QTerm("nosuchword"), True),
    "nothing": (QNothing(), True),
    "nested_or": (QOr([QTerm("w3"), QOr([QTerm("w60"),
                                         QOr([QTerm("w0"), QTerm("w99")])])]),
                  True),
    "prefix": (QPrefix("w1"), True),
    "or_of_prefix_and_term": (QOr([QPrefix("w9"), QTerm("w4")]), True),
    "fuzzy": (QFuzzy("w11", 1), True),
    "regex": (QRegex("w[0-3]"), True),
    "and": (QAnd([QTerm("w0"), QTerm("w1")]), None),
    "and_dense_and_sparse": (QAnd([QTerm("w0"), QTerm("w90"), QTerm("w1"),
                                   QTerm("w100")]), None),
    "and_all_sparse": (QAnd([QTerm("w80"), QTerm("w95")]), None),
    "and_a_term_twice": (QAnd([QTerm("w1"), QTerm("w70"), QTerm("w1")]),
                         None),
    "and_an_absent_term": (QAnd([QTerm("w2"), QTerm("nosuchword")]), None),
    "and_with_not": (QAnd([QTerm("w0"), QNot(QTerm("w1"))]), False),
    "not": (QNot(QTerm("w0")), False),
    "phrase": (QPhrase(["w0", "w1"]), None),
    "phrase_of_three": (QPhrase(["w1", "w0", "w2"]), None),
    "phrase_a_term_twice": (QPhrase(["w0", "w0"]), None),
    "phrase_an_absent_term": (QPhrase(["w0", "nosuchword"]), None),
    "sloppy_phrase": (QPhrase(["w0", "w1"], slop=2), False),
    "or_over_and": (QOr([QTerm("w5"), QAnd([QTerm("w0"), QTerm("w2")])]),
                    False),
}


def _expected(ms: MultiSearcher, node, validity) -> int:
    """What `SearchScanNode._matching_docs` would give the length of."""
    docs = ms.eval_filter(node)
    if validity is not None:
        docs = docs[validity[docs]]
    return len(docs)


@pytest.fixture(scope="module")
def stores():
    return {name: make() for name, make in STORES.items()}


@pytest.fixture(autouse=True)
def result_cache_off():
    """Off, as the search cells run: no doc set is kept from one count
    to the next, so which side ticks is the node's shape alone."""
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    yield
    SETTINGS.set_global("serene_result_cache", prior)


@pytest.mark.parametrize("shape", list(NODES))
@pytest.mark.parametrize("store", list(STORES))
def test_count_filter_is_the_length_of_eval_filter(stores, store, shape):
    ms, validity = stores[store]
    node, by_bitset = NODES[shape]
    sides = (metrics.SEARCH_COUNT_BITSET, metrics.SEARCH_COUNT_INTERSECTED,
             metrics.SEARCH_COUNT_MATERIALIZED)
    before = [g.value for g in sides]
    got = ms.count_filter(node, validity)
    assert got == _expected(ms, node, validity)
    # one tick per segment asked, on the side the node's shape decides
    n = len(ms.segments)
    assert [g.value - b for g, b in zip(sides, before)] == \
        [n if by_bitset is side else 0 for side in (True, None, False)]


def test_null_rows_change_a_negations_count(stores):
    """The NULL rule bites where it can: NOT w0 holds every NULL row in
    `eval_filter`, none in the count."""
    ms, validity = stores["null_rows"]
    node = QNot(QTerm("w0"))
    assert ms.count_filter(node, validity) < len(ms.eval_filter(node))
    assert ms.count_filter(node, None) == len(ms.eval_filter(node))


def test_a_doc_set_the_fragment_cache_holds_is_the_count():
    """With the result cache on, a Stream scan's `("filter", sig)`
    fragment answers the count that follows it: the cache's hit, as
    before this path existed."""
    ms, _ = _store([300, 130])
    node = QOr([QTerm("w1"), QTerm("w90")])
    SETTINGS.set_global("serene_result_cache", True)
    b0 = metrics.SEARCH_COUNT_BITSET.value
    assert ms.count_filter(node) == len(ms.eval_filter(node))   # it stores
    assert metrics.SEARCH_COUNT_BITSET.value - b0 == 2
    h0 = metrics.FRAGMENT_CACHE_HITS.value
    m0 = metrics.SEARCH_COUNT_MATERIALIZED.value
    assert ms.count_filter(node) == len(ms.eval_filter(node))
    assert metrics.SEARCH_COUNT_MATERIALIZED.value - m0 == 2
    assert metrics.SEARCH_COUNT_BITSET.value - b0 == 2
    assert metrics.FRAGMENT_CACHE_HITS.value - h0 >= 2


@pytest.mark.parametrize("store", list(STORES))
def test_only_dense_terms_hold_a_bitset_and_they_weigh_under_the_postings(
        stores, store):
    ms, validity = stores[store]
    for node, _ in NODES.values():      # whatever the parity test left
        ms.count_filter(node, validity)
    for seg, _ in ms.segments:
        df = seg.index.doc_freq
        assert all(int(df[t]) * 32 >= seg.num_docs for t in seg._doc_bits)
        assert all(len(r) == -(-seg.num_docs // 64) and r.dtype == np.uint64
                   for r in seg._doc_bits.values())
        assert seg.count_bitset_bytes <= seg.index.post_docs.nbytes


def test_prebuild_leaves_nothing_to_build_on_the_first_count():
    ms, _ = _store([700, 90])
    y0 = metrics.SEARCH_COUNT_BITSET_BYTES.value
    ms.prebuild()
    held = [dict(seg._doc_bits) for seg, _ in ms.segments]
    built = metrics.SEARCH_COUNT_BITSET_BYTES.value - y0
    assert built == sum(seg.count_bitset_bytes for seg, _ in ms.segments) > 0
    # every dense term has its row, no other term has one
    for seg, _ in ms.segments:
        df = seg.index.doc_freq.astype(np.int64)
        row_bytes = -(-seg.num_docs // 64) * 8
        assert sorted(seg._doc_bits) == \
            np.flatnonzero(df * 4 >= row_bytes).tolist()
        assert seg.count_bitset_bytes <= seg.index.post_docs.nbytes
    for node, _ in NODES.values():
        assert ms.count_filter(node) == len(ms.eval_filter(node))
    assert metrics.SEARCH_COUNT_BITSET_BYTES.value - y0 == built
    for (seg, _), before in zip(ms.segments, held):
        assert seg._doc_bits.keys() == before.keys()
        assert all(seg._doc_bits[t] is before[t] for t in before)


def test_threads_that_count_at_once_build_each_row_once():
    """A segment that never went through `prebuild` builds its dense
    rows on first use, from whichever thread asks first: every thread
    reads the same counts, and each row is kept (and its bytes counted)
    once."""
    ms, _ = _store([900])
    seg = ms.segments[0][0]
    nodes = [n for n, by_bitset in NODES.values() if by_bitset]
    want = [len(ms.eval_filter(n)) for n in nodes]
    y0 = metrics.SEARCH_COUNT_BITSET_BYTES.value
    got, start = [], threading.Barrier(16)

    def client():
        start.wait(timeout=30)
        got.append([ms.count_filter(n) for n in nodes])

    prior = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=client) for _ in range(16)]
        [t.start() for t in ts]
        [t.join(timeout=60) for t in ts]
    finally:
        sys.setswitchinterval(prior)
    assert not any(t.is_alive() for t in ts)
    assert got == [want] * 16
    assert metrics.SEARCH_COUNT_BITSET_BYTES.value - y0 == \
        seg.count_bitset_bytes > 0
