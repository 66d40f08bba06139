"""The closed set of BM25 scoring programs (ops/bm25.py, searcher.py).

After an index build has returned, no search of any term count, document
frequency, operator or batch composition builds a program: the programs
are enumerated from the store's padded document count and the batcher's
cap, built by `prebuild`, and a batch is fitted to a rung and cut into
fixed-capacity accumulate steps. Per-query results are bit-identical
however a batch was composed, cut or split, and equal to the host WAND
scorer. Each query is counted once, by the tier that scored it, and the
three search stages keep a request's stages adding up to its latency.
"""

import json
import threading

import numpy as np
import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.obs import trace as trace_mod
from serenedb_tpu.obs.trace import FLIGHT
from serenedb_tpu.ops import bm25 as bm25_ops
from serenedb_tpu.search.analysis import get_analyzer
from serenedb_tpu.search.batcher import batched_topk
from serenedb_tpu.search.query import QAnd, QOr, QTerm
from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
from serenedb_tpu.search.segment import build_field_index
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS

N_DOCS = 3000
VOCAB = 400
#: the `plane` fixture zeroes the budget for the module's lifetime
DENSE_BUDGET = bm25_ops.DENSE_HBM_BUDGET


def _corpus(seed=5):
    """Zipf pseudo-words: a few terms in most documents (many packed
    rows), a long tail under HEAVY_DF (light tails), and one document
    repeating a term 300 times (tf >= 256: a raw exception row)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, VOCAB + 1) ** 1.1
    p /= p.sum()
    docs = []
    for i in range(N_DOCS):
        n = int(rng.integers(4, 40))
        docs.append(" ".join(f"w{t}" for t in rng.choice(VOCAB, n, p=p)))
    docs[17] = " ".join(["w3"] * 300 + ["w5", "w9"])
    return docs


def _questions(seed, n):
    """(QNode, term count, is conjunction): 1-15 distinct terms, frequent
    terms likelier, one in five a conjunction."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, VOCAB + 1) ** 0.7
    p /= p.sum()
    out = []
    for _ in range(n):
        nt = int(rng.integers(1, 16))
        terms = [QTerm(f"w{t}") for t in
                 rng.choice(VOCAB, nt, replace=False, p=p)]
        conj = nt > 1 and rng.random() < 0.2
        out.append(terms[0] if nt == 1 else
                   (QAnd(terms) if conj else QOr(terms)))
    return out


@pytest.fixture(scope="module")
def plane():
    """A single-segment searcher whose store is on the plane kernel
    (dense budget 0 BEFORE the prebuild), with the fragment cache out of
    the way."""
    mp = pytest.MonkeyPatch()
    mp.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    an = get_analyzer("simple")
    docs = _corpus()
    seg = SegmentSearcher(build_field_index(docs, an), an, len(docs))
    ms = MultiSearcher(an)
    ms.add_segment(seg, 0)
    # what THIS prebuild compiled: the ledger is the process's, and a
    # file that ran before in this worker may have built programs of
    # the same keys (the top-k step's key holds no more of the store
    # than its padded document count)
    before = _ledger("compiles", "storms")
    built = ms.prebuild()
    yield ms, seg, (built, _ledger("compiles", "storms", since=before))
    SETTINGS.set_global("serene_result_cache", prior)
    mp.undo()


def _ledger(*fields, since=None):
    """The compile ledger's `fields`, summed per family — or, per family
    that moved, their growth since the reading `since`."""
    now = {p["family"]: sum(p[f] for f in fields)
           for p in obs_device.PROGRAMS.snapshot()}
    if since is None:
        return now
    return {f: n - since.get(f, 0) for f, n in now.items()
            if n != since.get(f, 0)}


#: the families a search can dispatch
SEARCH_FAMILIES = ("bm25_accumulate", "bm25_topk", "bm25_resident",
                   "dense_topk", "dense_build")


class _Builds:
    """Programs jax built while the block ran, counted from outside the
    program as the benchmark's child wrapper does, beside the ledger's
    own compile count of the search families. Both are the process's:
    what a thread that was alive before the block built (the maintenance
    loop of a database an earlier file of this worker left open) is not
    this block's, so jax's events are kept by thread."""

    _events: list = []
    _hooked = False

    def __enter__(self):
        import jax.monitoring as mon
        if not _Builds._hooked:
            mon.register_event_duration_secs_listener(
                lambda ev, dur, **kw: _Builds._events.append(
                    (ev, threading.current_thread())))
            _Builds._hooked = True
        self.n0 = len(_Builds._events)
        self.others = set(threading.enumerate()) - \
            {threading.current_thread()}
        self.c0 = _ledger("compiles")
        return self

    def __exit__(self, *exc):
        self.jax = sum(
            ev == "/jax/core/compile/backend_compile_duration"
            and th not in self.others
            for ev, th in _Builds._events[self.n0:])
        self.ledger = sum(
            n for f, n in _ledger("compiles", since=self.c0).items()
            if f in SEARCH_FAMILIES)
        return False


def _bits(res):
    return [(s.view(np.uint32).tolist(), d.tolist()) for s, d in res]


def test_prebuild_lists_and_builds_the_closed_set(plane):
    ms, seg, (built, compiled) = plane
    store = seg._device_store()
    rungs = seg._rungs(store)
    keys = bm25_ops.plane_program_keys(rungs)
    assert [r.nq for r in rungs] == [1, 8, 32]
    assert len(keys) == len(set(keys)) == 24 <= 24
    # the fixture's prebuild built what the process did not hold yet,
    # of these two families only: all 24 in a fresh process — and a
    # closed set built ahead of the queries is no recompile storm, so
    # the sum below holds compiles alone
    assert set(compiled) <= {"bm25_accumulate", "bm25_topk"}
    assert compiled.get("bm25_accumulate", 0) <= 12
    assert compiled.get("bm25_topk", 0) <= 12
    assert built == sum(compiled.values()) <= 24
    # and every program of the set is built now, whoever built it
    kk = min(bm25_ops.pad_k(1), store.ndocs_pad)
    for rung, with_hits, first in keys:
        prog = bm25_ops._topk_program(
            store.ndocs_pad, rung.nq, with_hits, kk,
            first == bm25_ops.MASKED) \
            if first in (None, bm25_ops.MASKED) else \
            bm25_ops._accumulate_program(store, rung, with_hits, first,
                                         "bm25")
        assert prog.called, (rung, with_hits, first)
    with _Builds() as b:
        assert ms.prebuild() == 0          # nothing left to build
    assert (b.jax, b.ledger) == (0, 0)


def test_500_mixed_queries_build_nothing_and_match_serial_and_wand(plane):
    ms, seg, _ = plane
    nodes = _questions(11, 500)
    rng = np.random.default_rng(3)
    cap = int(SETTINGS.get_global("serene_search_batch_max"))
    sizes, left = [], len(nodes)
    while left:
        n = min(left, int(rng.choice([1, 2, 5, 8, 9, 31, 32, 33, cap])))
        sizes.append(n)
        left -= n
    dev0 = metrics.SEARCH_QUERIES_SCORED_DEVICE.value
    host0 = metrics.SEARCH_QUERIES_SCORED_HOST.value
    post0 = metrics.SEARCH_POSTINGS_DISPATCHED.value
    batched, at = [], 0
    with _Builds() as b:
        for n in sizes:
            k = int(rng.integers(1, 11))
            part = nodes[at:at + n]
            got = ms.topk_batch(part, k)
            serial = [ms.topk_batch([q], k)[0] for q in part]
            assert _bits(got) == _bits(serial), (n, k)
            batched += [(q, k, r) for q, r in zip(part, got)]
            at += n
        # and through the batcher itself, coalescing as it happens to
        outs = [None] * 40
        bar = threading.Barrier(40)

        def submit(i):
            bar.wait(timeout=30)
            outs[i] = batched_topk(ms, nodes[i], 10)[0]
        ts = [threading.Thread(target=submit, args=(i,)) for i in range(40)]
        [t.start() for t in ts]
        [t.join(timeout=120) for t in ts]
        assert _bits(outs) == _bits([ms.topk_batch([q], 10)[0]
                                     for q in nodes[:40]])
    assert (b.jax, b.ledger) == (0, 0)
    # each query scored once by one tier: 500 batched + 500 serial, then
    # 40 through the batcher + 40 serial
    dev = metrics.SEARCH_QUERIES_SCORED_DEVICE.value - dev0
    host = metrics.SEARCH_QUERIES_SCORED_HOST.value - host0
    assert dev + host == 2 * 500 + 2 * 40
    assert dev > 0 and host > 0
    assert metrics.SEARCH_POSTINGS_DISPATCHED.value > post0
    # equal to the host WAND scorer: scores within f32 of its float64,
    # and a differing id only where the scores tie
    for q, k, (scores, docs) in batched[::7]:
        tids, req, _mask, empty = seg._query_shape(q)
        if empty or not tids:
            assert len(scores) == 0
            continue
        ws, wd = seg.cpu_topk_wand(tids, k, require_all=req)
        assert len(ws) == len(scores)
        np.testing.assert_allclose(scores, ws, rtol=2e-5)
        for a, c, sa in zip(docs.tolist(), wd.tolist(), ws.tolist()):
            assert a == c or np.isclose(
                sa, ws[wd.tolist().index(a)] if a in wd.tolist() else sa,
                rtol=2e-5)


def test_the_batcher_dispatches_only_the_closed_set(plane):
    """200 questions through the batcher, coalescing as it happens to:
    every program looked up is of the families `prebuild` built, on any
    backend, and none is built."""
    ms, _seg, _ = plane
    nodes = _questions(47, 200)
    before = _ledger("hits", "misses")
    outs = [None] * len(nodes)
    with _Builds() as b:
        for lo in range(0, len(nodes), 40):
            bar = threading.Barrier(40)

            def submit(i):
                bar.wait(timeout=30)
                outs[i] = batched_topk(ms, nodes[i], 10)[0]
            ts = [threading.Thread(target=submit, args=(i,))
                  for i in range(lo, lo + 40)]
            [t.start() for t in ts]
            [t.join(timeout=120) for t in ts]
    assert (b.jax, b.ledger) == (0, 0)
    # the scoring families that were looked up (other families are other
    # threads' business, if any ran)
    moved = {f for f in _ledger("hits", "misses", since=before)
             if f.startswith(("bm25_", "dense_"))}
    assert moved == {"bm25_accumulate", "bm25_topk"}
    assert "bm25_contrib" not in _ledger("compiles")
    assert _bits(outs[::9]) == _bits([ms.topk_batch([q], 10)[0]
                                      for q in nodes[::9]])


def test_no_recompile_storm_across_batch_sizes(plane):
    """Coalesced batches arrive at every size; each is fitted to a rung
    of the closed set, so the compile ledger stays quiet: nothing is
    built and no DeviceRecompileStorms fires."""
    _ms, seg, _ = plane
    nodes = _questions(53, 40)
    s0 = metrics.DEVICE_RECOMPILE_STORMS.value
    with _Builds() as b:
        for size in (1, 2, 3, 4, 5, 8, 9, 32, 33, 40):
            seg.topk_batch(nodes[:size], 10)
    assert (b.jax, b.ledger) == (0, 0)
    assert metrics.DEVICE_RECOMPILE_STORMS.value == s0


def test_a_split_batch_equals_the_same_queries_alone(plane):
    ms, seg, _ = plane
    nodes = _questions(23, 70)             # > the largest rung (32)
    with _Builds() as b:
        whole = seg.topk_batch(nodes, 10)
        alone = [seg.topk_batch([q], 10)[0] for q in nodes]
    assert _bits(whole) == _bits(alone)
    assert (b.jax, b.ledger) == (0, 0)


def test_steps_of_any_capacity_add_in_one_order(plane, monkeypatch):
    """Tiny capacities cut every section of a batch into many accumulate
    steps (packed rows, then raw rows from the last packed step on, then
    tails): the bits are those of the rungs' own capacities."""
    ms, seg, _ = plane
    nodes = _questions(31, 24) + [QOr([QTerm("w3"), QTerm("w390")]),
                                  QAnd([QTerm("w3"), QTerm("w5")])]
    base = seg.topk_batch(nodes, 10)
    monkeypatch.setattr(
        bm25_ops, "score_rungs",
        lambda nd, cap, acc: (bm25_ops.Rung(32, 3, 1, 5),))
    store = seg._device_store()
    qb = bm25_ops.assemble_query_batch(
        store, seg.num_docs,
        [(np.asarray(seg._query_shape(q)[0], dtype=np.int64), 0)
         for q in nodes], seg.index.doc_freq)
    assert len(qb.raw_idx) >= 1 and len(qb.tail_docs) > 5
    steps = bm25_ops.query_chunks(qb, bm25_ops.Rung(32, 3, 1, 5),
                                  store.n_packed, store.n_raw)
    assert len(steps) > 10
    assert _bits(seg.topk_batch(nodes, 10)) == _bits(base)


def test_postings_dispatched_counts_what_the_steps_carry(plane):
    """With pruning defeated (k >= documents) a disjunction hands every
    posting of its terms to the device: the counter moves by the sum of
    their document frequencies."""
    ms, seg, _ = plane
    terms = ["w0", "w7", "w120", "w333"]
    df = sum(int(seg.index.doc_freq[seg.index.term_id(t)]) for t in terms)
    before = metrics.SEARCH_POSTINGS_DISPATCHED.value
    seg.topk_batch([QOr([QTerm(t) for t in terms])], N_DOCS)
    assert metrics.SEARCH_POSTINGS_DISPATCHED.value - before == df


# ---- every posting brings its document's length: no norms gather (PR 34)

SCORERS = ("bm25", "tfidf", "lm_dirichlet", "jelinek_mercer", "dfi")


def _step_jaxpr(store, rung, scorer, with_hits=False):
    import jax
    (ints, floats), = bm25_ops.query_chunks(
        bm25_ops._NO_QUERIES, rung, store.n_packed, store.n_raw,
        min_steps=1)
    body = bm25_ops.accumulate_body(rung, store.ndocs_pad, with_hits, True,
                                    scorer)
    return jax.make_jaxpr(body)(*store.tiles, ints, floats, 1.2, 0.75, 9.0)


def _gathers(jaxpr):
    """Every gather of the jaxpr, those of its nested jaxprs too."""
    import jax
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


@pytest.mark.parametrize("nq", [1, 8, 32])
@pytest.mark.parametrize("with_hits", [False, True])
def test_accumulate_step_takes_no_norms_and_gathers_rows_only(
        plane, nq, with_hits):
    """The step has no (ndocs_pad,) operand, and all it gathers is rows:
    a 128-lane row of a tile plane per index, or one base doc a row —
    never an element a posting."""
    ms, seg, _ = plane
    store = seg._device_store()
    rung = next(r for r in seg._rungs(store) if r.nq == nq)
    nd = store.ndocs_pad
    assert nd not in (store.block_base.shape[0], store.raw_docs.shape[0])
    closed = _step_jaxpr(store, rung, "bm25", with_hits)
    assert all(v.aval.shape != (nd,) for v in closed.jaxpr.invars)
    seen = list(_gathers(closed.jaxpr))
    # gaps, base, tfs, lengths of the packed plane; docs, tfs, lengths
    # of the raw plane; the tails are gathered on the host
    assert len(seen) == 7
    for eqn in seen:
        operand, idx = (v.aval for v in eqn.invars)
        assert operand.shape != (nd,)
        rows = idx.shape[0]
        assert rows in (rung.nb, rung.nr)              # an index a ROW
        assert eqn.outvars[0].aval.shape in ((rows,), (rows, bm25_ops.BLOCK))


@pytest.mark.parametrize("scorer", SCORERS)
def test_only_a_scorer_that_reads_lengths_gathers_the_length_planes(
        plane, scorer):
    ms, seg, _ = plane
    store = seg._device_store()
    rung = seg._rungs(store)[0]
    closed = _step_jaxpr(store, rung, scorer)
    ops = [eqn.invars[0] for eqn in _gathers(closed.jaxpr)]
    block_dls, raw_dls = closed.jaxpr.invars[3], closed.jaxpr.invars[6]
    assert (block_dls.aval.dtype, raw_dls.aval.dtype) == ("uint16", "int32")
    want = 0 if scorer == "tfidf" else 1
    assert sum(v is block_dls for v in ops) == want
    assert sum(v is raw_dls for v in ops) == want


def _norms_gather_step(rung, ndocs_pad, with_hits, scorer):
    """The accumulate step as it was before the length planes: `dl` of
    every posting gathered from the (ndocs_pad,) norms table by document
    id — the old expressions, kept here as the bits to match."""
    import jax.numpy as jnp
    nb, nr, tt = rung.nb, rung.nr, rung.tt

    def contrib_of(norms, docs, tfs, w, k1, b, avg):
        valid = jnp.logical_and(docs >= 0, tfs > 0)
        safe_docs = jnp.where(valid, docs, 0)
        tfsf = tfs.astype(jnp.float32)
        dl = norms[safe_docs].astype(jnp.float32)
        if scorer == "tfidf":
            c = w * jnp.sqrt(tfsf)
        elif scorer == "lm_dirichlet":
            c = jnp.log1p(tfsf / (k1 * w)) + jnp.log(k1 / (dl + k1))
            c = jnp.maximum(c, 0.0) + bm25_ops.MATCH_EPS
        elif scorer == "jelinek_mercer":
            c = jnp.log1p(((1.0 - k1) * tfsf / jnp.maximum(dl, 1.0)) /
                          (k1 * w))
        elif scorer == "dfi":
            e = w * dl
            excess = (tfsf - e) / jnp.sqrt(jnp.maximum(e, 1e-9))
            c = jnp.where(tfsf > e, jnp.log2(1.0 + excess), 0.0) + \
                bm25_ops.MATCH_EPS
        else:
            denom = tfsf + k1 * (1.0 - b + b * dl / avg)
            c = w * (k1 + 1.0) * tfsf / jnp.maximum(denom, 1e-9)
        return jnp.where(valid, c, 0.0), valid, safe_docs

    def step(block_base, block_gaps, block_tfs8, _block_dls, raw_docs,
             raw_tfs, _raw_dls, norms, ints, floats, k1, b, avgdl, scores,
             hits):
        avg = jnp.maximum(jnp.float32(avgdl), 1e-9)
        o = 2 * nb + 2 * nr
        sections = (
            (*bm25_ops._decode_rows(block_base, block_gaps, block_tfs8,
                                    ints[:nb]),
             floats[:nb, None], ints[nb:2 * nb, None]),
            (raw_docs[ints[2 * nb:2 * nb + nr]],
             raw_tfs[ints[2 * nb:2 * nb + nr]],
             floats[nb:nb + nr, None], ints[2 * nb + nr:o, None]),
            (ints[o:o + tt], ints[o + tt:o + 2 * tt],
             floats[nb + nr:], ints[o + 3 * tt:]))
        for docs, tfs, w, qid in sections:
            c, valid, safe = contrib_of(norms, docs, tfs, w, k1, b, avg)
            at = (qid * ndocs_pad + safe).reshape(-1)
            scores = scores.at[at].add(c.reshape(-1))
            hits = hits.at[at].add(valid.reshape(-1).astype(jnp.int32))
        return scores, hits

    return step


@pytest.mark.parametrize("scorer", SCORERS)
def test_scores_are_bit_for_bit_those_of_the_norms_gather(plane, scorer):
    """(vals, docs) of the plane rung equal, bit for bit, what the same
    store scores with every length gathered from `norms` — disjunctions
    and a conjunction, packed rows, the raw row and tails, cut into
    several steps — and stay within f32 of the host's float64."""
    import jax
    import jax.numpy as jnp
    ms, seg, _ = plane
    store = seg._device_store()
    nodes = _questions(41, 6) + [QOr([QTerm("w3"), QTerm("w390")]),
                                 QAnd([QTerm("w3"), QTerm("w5")])]
    shapes = [seg._query_shape(q) for q in nodes]
    queries = [(np.asarray(tids, dtype=np.int64), req)
               for tids, req, _, _ in shapes]
    idf_of = None
    if scorer in bm25_ops.LM_SCORERS:
        def idf_of(tids):
            return bm25_ops.term_weight_for(
                scorer, seg.num_docs, None, seg.index.ctf[tids],
                float(seg.index.total_tokens))
    qb = bm25_ops.assemble_query_batch(
        store, seg.num_docs, queries, seg.index.doc_freq, scorer,
        idf_of=idf_of)
    assert len(qb.row_idx) and len(qb.raw_idx) and len(qb.tail_docs)
    np.testing.assert_array_equal(qb.tail_dls,
                                  seg.index.norms[qb.tail_docs])
    rung = bm25_ops.Rung(8, 64, 1, 16)        # several steps a section
    k1 = bm25_ops.scorer_param(scorer, 1.2)
    avgdl = seg.index.avgdl
    nd = store.ndocs_pad
    chunks = bm25_ops.query_chunks(qb, rung, store.n_packed, store.n_raw)
    assert len(chunks) > 3

    new_first = jax.jit(bm25_ops.accumulate_body(rung, nd, True, True,
                                                 scorer))
    new_next = jax.jit(bm25_ops.accumulate_body(rung, nd, True, False,
                                                scorer))
    old = jax.jit(_norms_gather_step(rung, nd, True, scorer))
    topk = jax.jit(bm25_ops.topk_body(nd, rung.nq, True, False, 10))
    planes = ()
    ref = (jnp.zeros(rung.nq * nd, jnp.float32),
           jnp.zeros(rung.nq * nd, jnp.int32))
    for ints, floats in chunks:
        planes = (new_next if planes else new_first)(
            *store.tiles, ints, floats, k1, 0.75, avgdl, *planes)
        ref = old(*store.tiles, store.norms, ints, floats, k1, 0.75,
                  avgdl, *ref)
    np.testing.assert_array_equal(
        np.asarray(planes[0]).view(np.uint32),
        np.asarray(ref[0]).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(planes[1]), np.asarray(ref[1]))
    require = bm25_ops._pad_to(qb.require, rung.nq, 0)
    got_v, got_d = (np.asarray(a) for a in topk(require, *planes))
    ref_v, ref_d = (np.asarray(a) for a in topk(require, *ref))
    assert got_v.view(np.uint32).tolist() == ref_v.view(np.uint32).tolist()
    assert got_d.tolist() == ref_d.tolist()
    assert (got_v[:len(nodes), 0] > 0).sum() >= len(nodes) - 2
    # and the host's float64 over the same candidates
    for qi, (tids, req, _, _) in enumerate(shapes):
        keep = got_v[qi] > 0
        hs, hd = seg._cpu_score(got_d[qi][keep], tids, 10, scorer)
        order = np.argsort(got_d[qi][keep])
        np.testing.assert_allclose(
            got_v[qi][keep][order], hs[np.argsort(hd)], rtol=2e-5)


def test_length_planes_are_counted_where_the_store_is(plane):
    """`hbm_bytes` holds the planes, the gauge what is resident of them,
    and the store still prebuilds 24 programs."""
    import gc
    ms, seg, _ = plane
    store = seg._device_store()
    n_p, n_r = store.block_base.shape[0], store.raw_docs.shape[0]
    assert store.block_dls.shape == store.block_tfs8.shape == (n_p, 128)
    assert store.raw_dls.shape == store.raw_tfs.shape == (n_r, 128)
    assert store.length_bytes == n_p * 128 * 2 + n_r * 128 * 4
    assert store.hbm_bytes == n_p * (4 + 128 * 5) + n_r * 128 * 12
    assert len(bm25_ops.plane_program_keys(seg._rungs(store))) == 24
    # lane for lane the norms of the decoded documents, 0 on padding
    docs, tfs = bm25_ops._decode_rows(
        store.block_base, store.block_gaps, store.block_tfs8,
        np.arange(n_p, dtype=np.int32))
    docs, dls = np.asarray(docs), np.asarray(store.block_dls)
    norms = np.asarray(store.norms)
    assert (dls[docs >= 0] == norms[docs[docs >= 0]]).all()
    assert not dls[docs < 0].any()
    rdocs, rdls = np.asarray(store.raw_docs), np.asarray(store.raw_dls)
    assert (rdls[rdocs >= 0] == norms[rdocs[rdocs >= 0]]).all()
    assert not rdls[rdocs < 0].any()
    # the gauge: up by a new segment's planes, down when it goes
    an = get_analyzer("simple")
    docs = _corpus(seed=6)[:500]
    g0 = metrics.SEARCH_POSTING_LENGTH_BYTES.value
    other = SegmentSearcher(build_field_index(docs, an), an, len(docs))
    held = other._device_store().length_bytes
    assert held > 0
    assert metrics.SEARCH_POSTING_LENGTH_BYTES.value - g0 == held
    assert metrics.REGISTRY.snapshot()["SearchPostingLengthBytes"] == \
        g0 + held                                  # what /metrics renders
    del other
    gc.collect()
    assert metrics.SEARCH_POSTING_LENGTH_BYTES.value == g0


def test_dense_path_is_a_closed_set_too(monkeypatch):
    """A small corpus answers from the dense steps: built by CREATE INDEX,
    none by a search; a 17-term query takes two steps, a 33-term three."""
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", DENSE_BUDGET)
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE d (id INT, body TEXT)")
    docs = _corpus(9)[:500]
    c.execute("INSERT INTO d VALUES " + ", ".join(
        f"({i}, '{t}')" for i, t in enumerate(docs)))
    before = metrics.SEARCH_PROGRAMS_PREBUILT.value
    c.execute("CREATE INDEX ON d USING inverted (body) "
              "WITH (tokenizer = 'simple')")
    assert "dense_topk" in {p["family"]
                            for p in obs_device.PROGRAMS.snapshot()}
    assert metrics.SEARCH_PROGRAMS_PREBUILT.value - before in (0, 18)
    c.execute("SET serene_result_cache = off")
    with _Builds() as b:
        for n in (1, 2, 16, 17, 33):
            q = " | ".join(f"w{t}" for t in range(n))
            rows = c.execute(
                f"SELECT id, bm25(body) s FROM d WHERE body @@ '{q}' "
                "ORDER BY s DESC LIMIT 10").rows()
            assert len(rows) == 10
        rows_and = c.execute(
            "SELECT id FROM d WHERE body @@ 'w0 & w1' "
            "ORDER BY bm25(body) DESC LIMIT 5").rows()
        assert rows_and
    assert (b.jax, b.ledger) == (0, 0)


# -- stages and the request timeline -------------------------------------------


def _search_db(n=800):
    db = Database()
    c = db.connect()
    c.execute('CREATE TABLE passages ("_id" VARCHAR, "_source" VARCHAR, '
              "body VARCHAR)")
    docs = _corpus(13)[:n]
    c.execute("INSERT INTO passages VALUES " + ", ".join(
        f"('{i}', '{{\"n\": {i}}}', '{t}')" for i, t in enumerate(docs)))
    c.execute("CREATE INDEX ON passages USING inverted (body) "
              "WITH (tokenizer = 'simple')")
    return db


def _stages_add_up(entry):
    assert sum(entry["stages"].values()) == entry["duration_ns"]
    assert set(entry["stages"]) - {"other"} <= set(trace_mod.STAGES)


def test_search_stages_keep_the_request_sum(monkeypatch):
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", DENSE_BUDGET)
    db = _search_db()
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    try:
        tids, lock = [], threading.Lock()

        def search(i):
            cc = db.connect()
            # the batcher is what is under test, whatever the suite-wide
            # default (scripts/verify_tier1.sh has a leg that forces it off)
            cc.execute("SET serene_search_batch = on")
            cc.execute("SELECT \"_id\", bm25(body) s FROM passages "
                       f"WHERE body @@ 'w{i % 5} | w{i + 20} | w1' "
                       "ORDER BY s DESC LIMIT 10")
            with lock:
                tids.append(cc._active_trace.trace_id)

        seen = set()
        for _ in range(6):
            ts = [threading.Thread(target=search, args=(i,))
                  for i in range(8)]
            [t.start() for t in ts]
            [t.join(timeout=60) for t in ts]
            for tid in tids:
                e = FLIGHT.get(tid)
                if e is not None:
                    _stages_add_up(e)
                    seen |= set(e["stages"])
            if "batch_wait" in seen:
                break
        assert {"search_plan", "search_host_score", "device_enqueue",
                "device_wait", "batch_wait"} <= seen, seen
        # a member of a coalesced dispatch carries the dispatch's device
        # stages though another thread ran it, and is a device answer
        waited = [FLIGHT.get(t) for t in tids
                  if FLIGHT.get(t) and "batch_wait" in FLIGHT.get(t)["stages"]]
        assert any("device_enqueue" in e["stages"] and
                   e["answered"] == "device" for e in waited)
    finally:
        SETTINGS.set_global("serene_result_cache", prior)


def test_es_search_is_one_request_on_one_timeline(monkeypatch):
    from serenedb_tpu.server.es_api import EsApi
    from serenedb_tpu.server.http_server import Router
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", DENSE_BUDGET)
    db = _search_db(400)
    router = Router(EsApi(db))
    body = json.dumps({"query": {"match": {"body": "w1 w2 w30"}},
                       "size": 10}).encode()
    router.handle("POST", "/passages/_search", body)      # warm
    n0 = metrics.REQUEST_LATENCY_HIST.count
    dev0 = metrics.STATEMENTS_ANSWERED_DEVICE.value
    status, data, _ = router.handle("POST", "/passages/_search", body)
    assert status == 200
    hits = json.loads(data)["hits"]
    assert len(hits["hits"]) == 10 and hits["total"]["value"] >= 10
    assert metrics.REQUEST_LATENCY_HIST.count - n0 == 1
    assert metrics.STATEMENTS_ANSWERED_DEVICE.value - dev0 == 1
    entry = FLIGHT.last()
    assert entry["query"].startswith("POST /passages/_search")
    _stages_add_up(entry)
    # both statements are there: the scored SELECT and the exact total
    assert sum(s["name"] == "execute" for s in entry["spans"]) == 2
    assert {"plan", "search_plan", "device_enqueue", "device_wait",
            "fd_encode"} <= set(entry["stages"])
    fams = {p["family"] for p in
            obs_device.stats_section()["programs"]}
    assert "dense_topk" in fams or "bm25_accumulate" in fams


# -- a `_search`'s front and back doors, and the step buffers, have a stage
# (ISSUE 35): structure, not timing ---------------------------------------------


def _owner_of(entry, t_ns):
    """The stage that owns the instant `t_ns` (an offset from the
    request's start) on the request's timeline; "other" under none."""
    for name, b, e in entry["timeline"]:
        if b <= t_ns < e:
            return name
    return "other"


def test_a_search_runs_its_parse_its_buffers_and_its_response_under_stages(
        monkeypatch):
    """The DSL's translation and both SQL texts' parse under `fd_parse`,
    the accumulate steps' buffers and the doc masks under `search_plan`,
    the response's assembly under `fd_encode`: each function is wrapped
    to note WHEN it ran, and the request's timeline says whose instant
    that was."""
    from serenedb_tpu.search.searcher import SegmentSearcher
    from serenedb_tpu.server import es_api as es_mod
    from serenedb_tpu.server.http_server import RequestClock, Router
    from serenedb_tpu.sql import parser as sql_parser
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", 0)     # plane steps
    # every phrase match set is "large": a doc mask, not host candidates
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    db = _search_db(800)
    router = Router(es_mod.EsApi(db))
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    ran: list = []

    def noting(label, fn):
        def wrapped(*a, **kw):
            ran.append((label, trace_mod.time.perf_counter_ns()))
            return fn(*a, **kw)
        return wrapped

    class Json:                       # es_api's own `json`, loads noted
        dumps = staticmethod(json.dumps)
        loads = staticmethod(noting("response", json.loads))

    try:
        docs = _corpus(13)[:800]
        w = docs[3].split()
        phrase = f"{w[0]} {w[1]}"
        bodies = [{"query": {"match": {"body": "w1 w2 w30"}}, "size": 10},
                  {"query": {"match_phrase": {"body": phrase}}, "size": 10}]
        for body in bodies:                                  # warm
            assert router.handle("POST", "/passages/_search",
                                 json.dumps(body).encode())[0] == 200
        monkeypatch.setattr(sql_parser, "parse",
                            noting("parse", sql_parser.parse))
        monkeypatch.setattr(es_mod.EsApi, "_translate_query", noting(
            "translate", es_mod.EsApi._translate_query))
        monkeypatch.setattr(bm25_ops, "query_chunks",
                            noting("chunks", bm25_ops.query_chunks))
        monkeypatch.setattr(bm25_ops, "doc_masks",
                            noting("masks", bm25_ops.doc_masks))
        monkeypatch.setattr(es_mod, "json", Json)
        owners = {}
        for body in bodies:
            del ran[:]
            clock = RequestClock()
            status, data, _ = router.handle(
                "POST", "/passages/_search", json.dumps(body).encode(),
                clock)
            clock.end()
            assert status == 200 and json.loads(data)["hits"]["hits"]
            entry = clock.trace.entry
            _stages_add_up(entry)
            for label, t in ran:
                owners.setdefault(label, set()).add(
                    _owner_of(entry, t - clock.trace.t0_ns))
            # both texts of the request were parsed on its timeline
            assert sum(label == "parse" for label, _ in ran) == 2
        assert owners == {"parse": {"fd_parse"}, "translate": {"fd_parse"},
                          "chunks": {"search_plan"},
                          "masks": {"search_plan"},
                          "response": {"fd_encode"}}, owners
    finally:
        SETTINGS.set_global("serene_result_cache", prior)


@pytest.mark.parametrize("n_queries", [1, 5, 20])          # rungs 1, 8, 32
@pytest.mark.parametrize("form", ["or", "and", "masked"])
def test_every_rung_and_form_calls_prebuilt_programs_on_arrays_only(
        plane, n_queries, form, monkeypatch):
    """After `prebuild`, a batch of every rung in every form (union,
    conjunction, a phrase under a doc mask) builds nothing, though its
    host operands are committed before the call now: the prebuild's
    calls went through the same commit, so a scalar reaches the program
    with the type it was traced with. Every jitted function is handed
    `jax.Array`s only."""
    import jax

    from serenedb_tpu.search.query import QPhrase
    ms, seg, _ = plane
    # no candidate list is short enough for the host rung: every
    # question goes to the chip, a phrase's match set as a doc mask
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    if form == "masked":
        docs = _corpus()
        nodes = []
        for d in docs[100:100 + n_queries]:
            w = d.split()
            nodes.append(QPhrase([w[0], w[1]]))
    else:
        rng = np.random.default_rng(n_queries)
        nodes = [(QAnd if form == "and" else QOr)(
            [QTerm(f"w{t}") for t in rng.choice(40, 3, replace=False)])
            for _ in range(n_queries)]
    handed: list = []
    with obs_device.PROGRAMS._lock:
        progs = [p for (fam, _k), p in obs_device.PROGRAMS._progs.items()
                 if fam in ("bm25_accumulate", "bm25_topk")]
    inner = [p.fn for p in progs]
    for p, fn in zip(progs, inner):
        p.fn = (lambda *xs, _fn=fn: (handed.extend(xs), _fn(*xs))[1])
    try:
        with _Builds() as b:
            got = ms.topk_batch(nodes, 10)
    finally:
        for p, fn in zip(progs, inner):
            p.fn = fn
    assert (b.jax, b.ledger) == (0, 0)
    assert handed and all(isinstance(x, jax.Array) for x in handed)
    serial = [ms.topk_batch([q], 10)[0] for q in nodes]
    assert _bits(got) == _bits(serial)


# -- a store's dense terms as resident rows of the plane kernel ------------------

#: a dense budget of 24 rows of the corpus's 3,072 padded documents: the
#: whole matrix (512 rows) does not fit, 24 of its dense terms' rows do
RESIDENT_ROWS = 24
ND_PAD = 3072


@pytest.fixture(scope="module")
def resident():
    """A single-segment searcher on the plane kernel whose 24 most
    frequent dense terms are resident rows (the budget set BEFORE the
    prebuild), with the fragment cache out of the way."""
    mp = pytest.MonkeyPatch()
    mp.setattr(bm25_ops, "DENSE_HBM_BUDGET", RESIDENT_ROWS * ND_PAD * 4)
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    an = get_analyzer("simple")
    docs = _corpus()
    seg = SegmentSearcher(build_field_index(docs, an), an, len(docs))
    ms = MultiSearcher(an)
    ms.add_segment(seg, 0)
    before = _ledger("compiles", "storms")
    built = ms.prebuild()
    yield ms, seg, (built, _ledger("compiles", "storms", since=before))
    SETTINGS.set_global("serene_result_cache", prior)
    mp.undo()


def _rows_of(seg):
    store = seg._device_store()
    return store, seg._resident_rows("bm25", seg.index.avgdl)


def _many_resident(seg, n):
    """A disjunction of the `n` most frequent terms and three rare ones:
    more resident terms than one resident step's DENSE_SLOTS."""
    order = np.argsort(-seg.index.doc_freq, kind="stable")
    terms = [str(seg.index.terms_str[t]) for t in order[:n]]
    return QOr([QTerm(t) for t in terms + ["w390", "w377", "w301"]])


def _same_topk(got, want):
    """Scores within f32 of the host's float64; an id that differs only
    where the scores tie."""
    (scores, docs), (ws, wd) = got, want
    assert len(scores) == len(ws)
    np.testing.assert_allclose(scores, ws, rtol=2e-5)
    wd = wd.tolist()
    for a, c in zip(docs.tolist(), wd):
        assert a == c or (a in wd and np.isclose(
            ws[wd.index(a)], scores[docs.tolist().index(a)], rtol=2e-5))


def test_resident_rows_are_the_most_frequent_dense_terms(resident):
    """The rows held are dense terms (`_dense_terms`: the count bitsets'
    rule), the most frequent first, as many as the budget holds; each
    row is the term's saturation at its documents and 0 elsewhere."""
    _ms, seg, _ = resident
    store, rows = _rows_of(seg)
    every = np.arange(len(seg.index.doc_freq))
    dense = seg._dense_terms(every)
    held = every[rows.row_of >= 0]
    assert store.ndocs_pad == ND_PAD and not seg._use_dense(
        store, "bm25", seg.index.avgdl)
    assert len(held) == RESIDENT_ROWS < dense.sum()
    assert dense[held].all()
    df = seg.index.doc_freq
    assert df[held].min() >= df[dense & (rows.row_of < 0)].max()
    assert rows.St.shape == (RESIDENT_ROWS, ND_PAD)
    St = np.asarray(rows.St)
    for t in held[::5]:
        docs, tfs = seg.index.postings(int(t))
        want = bm25_ops._sat_exact(tfs, seg.index.norms[docs], 1.2, 0.75,
                                   seg.index.avgdl, "bm25")
        row = St[rows.row_of[t]]
        np.testing.assert_allclose(row[docs], want, rtol=2e-6)
        assert np.count_nonzero(row) == len(docs)


@pytest.mark.parametrize("budget_rows", [0, 7.9, 8, 30.5, 24, 200])
def test_resident_rows_stay_in_the_budget_and_are_counted(budget_rows,
                                                          monkeypatch):
    """Whatever the budget, the matrix holds no more bytes than it (its
    rows in a sixteenth of their octave, a multiple of 8), the gauge
    rises by its bytes while it is held and falls when it goes; under
    8 rows no term is resident and nothing is built."""
    import gc
    budget = int(budget_rows * ND_PAD * 4)
    monkeypatch.setattr(bm25_ops, "DENSE_HBM_BUDGET", budget)
    an = get_analyzer("simple")
    docs = _corpus()
    seg = SegmentSearcher(build_field_index(docs, an), an, len(docs))
    g0 = metrics.SEARCH_RESIDENT_ROW_BYTES.value
    store, rows = _rows_of(seg)
    if budget_rows < 8:
        assert rows is None
        assert metrics.SEARCH_RESIDENT_ROW_BYTES.value == g0
        return
    n = int((rows.row_of >= 0).sum())
    assert rows.v_pad % 8 == 0 and n <= rows.v_pad
    assert rows.St.nbytes == rows.v_pad * ND_PAD * 4 <= budget
    assert metrics.SEARCH_RESIDENT_ROW_BYTES.value - g0 == rows.St.nbytes
    assert metrics.REGISTRY.snapshot()["SearchResidentRowBytes"] == \
        g0 + rows.St.nbytes                         # what /metrics renders
    del rows, seg
    gc.collect()
    assert metrics.SEARCH_RESIDENT_ROW_BYTES.value == g0


@pytest.mark.parametrize("form", ["or", "and", "masked", "steps", "many"])
@pytest.mark.parametrize("n_queries", [1, 5, 20, 70])   # rungs 1, 8, 32; split
def test_resident_plane_answers_as_the_host(resident, form, n_queries,
                                            monkeypatch):
    """On a store with dense and sparse terms the plane rung's top-10 is
    the host's: `cpu_topk_wand` for unions and conjunctions, `_cpu_score`
    over a phrase's match set for a phrase under a doc mask — in batches
    of every rung and past the largest, cut into many accumulate steps
    (`steps`), and over more resident terms than one resident step
    holds (`many`)."""
    from serenedb_tpu.search.query import QPhrase
    ms, seg, _ = resident
    # no candidate list is short enough for the host rung
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    rng = np.random.default_rng(n_queries)
    if form == "masked":
        nodes = [QPhrase(d.split()[:2])
                 for d in _corpus()[200:200 + n_queries]]
    elif form == "and":
        nodes = [QAnd([QTerm(f"w{t}")
                       for t in rng.choice(30, 3, replace=False)])
                 for _ in range(n_queries)]
    elif form == "many":
        nodes = [_many_resident(seg, int(n)) for n in
                 rng.integers(RESIDENT_ROWS - 6, RESIDENT_ROWS + 1,
                              n_queries)]
    else:
        nodes = _questions(60 + n_queries, n_queries)
    # the first question holds a resident term in every form
    nodes[0] = {"or": QOr([QTerm("w1"), QTerm("w350")]),
                "steps": QOr([QTerm("w1"), QTerm("w350")]),
                "and": QAnd([QTerm("w1"), QTerm("w7")])}.get(form, nodes[0])
    if form == "steps":
        monkeypatch.setattr(
            bm25_ops, "score_rungs",
            lambda nd, cap, acc: (bm25_ops.Rung(32, 3, 1, 5),))
    r0 = metrics.SEARCH_POSTINGS_RESIDENT.value
    got = seg.topk_batch(nodes, 10)
    assert metrics.SEARCH_POSTINGS_RESIDENT.value > r0
    checked = 0
    for q, res in zip(nodes, got):
        tids, req, _mask, empty = seg._query_shape(q)
        if empty or not tids:
            assert len(res[0]) == 0
            continue
        if form == "masked":
            want = seg._cpu_score(seg._phrase_docs(q), tids, 10)
            want = (want[0][want[0] > 0], want[1][want[0] > 0])
        else:
            want = seg.cpu_topk_wand(tids, 10, require_all=req)
        _same_topk(res, want)
        checked += len(want[0]) > 0
    assert checked >= n_queries // 2


def test_resident_path_is_taken_and_a_store_without_dense_terms_takes_none(
        resident, monkeypatch):
    """`SearchPostingsResident` moves on a store with resident rows, by
    no more than `SearchPostingsDispatched`; a store none of whose terms
    is dense (each in under 1/32 of its documents) adds none and answers
    as the host."""
    ms, seg, _ = resident
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    r0 = metrics.SEARCH_POSTINGS_RESIDENT.value
    d0 = metrics.SEARCH_POSTINGS_DISPATCHED.value
    seg.topk_batch(_questions(5, 8), 10)
    held = metrics.SEARCH_POSTINGS_RESIDENT.value - r0
    assert 0 < held < metrics.SEARCH_POSTINGS_DISPATCHED.value - d0
    rng = np.random.default_rng(2)
    docs = [" ".join(f"u{t}" for t in rng.choice(20000, 12))
            for _ in range(3000)]
    an = get_analyzer("simple")
    sparse = SegmentSearcher(build_field_index(docs, an), an, len(docs))
    assert sparse.prebuild() >= 0
    assert not sparse._dense_terms(
        np.arange(len(sparse.index.doc_freq))).any()
    assert sparse._resident_rows("bm25", sparse.index.avgdl) is None
    nodes = [QOr([QTerm(w) for w in d.split()[:4]]) for d in docs[:9]]
    r0 = metrics.SEARCH_POSTINGS_RESIDENT.value
    d0 = metrics.SEARCH_POSTINGS_DISPATCHED.value
    got = sparse.topk_batch(nodes, 10)
    assert metrics.SEARCH_POSTINGS_RESIDENT.value == r0
    assert metrics.SEARCH_POSTINGS_DISPATCHED.value > d0
    for q, res in zip(nodes, got):
        _same_topk(res, sparse.cpu_topk_wand(sparse._query_shape(q)[0], 10))


def test_resident_rows_add_in_one_order_whatever_the_batch(resident,
                                                           monkeypatch):
    """Bit for bit, a question alone is the same question in a batch
    split past the largest rung, in a batch cut into many accumulate
    steps, and beside questions that take a second resident step."""
    ms, seg, _ = resident
    monkeypatch.setattr(SegmentSearcher, "MAXSCORE_CAND_CAP", 0)
    nodes = _questions(29, 60) + [_many_resident(seg, RESIDENT_ROWS),
                                  QAnd([QTerm("w0"), QTerm("w5")])] + \
        _questions(30, 8)
    with _Builds() as b:
        whole = seg.topk_batch(nodes, 10)
        alone = [seg.topk_batch([q], 10)[0] for q in nodes]
    assert (b.jax, b.ledger) == (0, 0)
    assert _bits(whole) == _bits(alone)
    monkeypatch.setattr(
        bm25_ops, "score_rungs",
        lambda nd, cap, acc: (bm25_ops.Rung(32, 3, 1, 5),))
    assert _bits(seg.topk_batch(nodes, 10)) == _bits(alone)


def test_resident_prebuild_builds_every_program_and_500_questions_none(
        resident):
    """`prebuild` builds the plane programs and the resident steps — 24
    + 12 in a fresh process — and every program of both sets is built
    after it; 500 mixed questions in batches of every size, and 40
    through the batcher, build nothing and look up only those families."""
    ms, seg, (built, compiled) = resident
    store, rows = _rows_of(seg)
    rungs = seg._rungs(store)
    keys = bm25_ops.resident_program_keys(rungs)
    assert len(keys) == len(set(keys)) == 12
    assert set(compiled) <= {"bm25_accumulate", "bm25_topk",
                             "bm25_resident", "dense_build"}
    assert compiled.get("bm25_resident", 0) <= 12
    assert built == sum(n for f, n in compiled.items()
                        if f != "dense_build") <= 36
    for nq, with_hits, first in keys:
        assert bm25_ops._resident_program(rows, nq, first,
                                          with_hits).called
    kk = min(bm25_ops.pad_k(1), store.ndocs_pad)
    for rung, with_hits, first in bm25_ops.plane_program_keys(rungs):
        assert (bm25_ops._topk_program(
            store.ndocs_pad, rung.nq, with_hits, kk,
            first == bm25_ops.MASKED)
            if first in (None, bm25_ops.MASKED) else
            bm25_ops._accumulate_program(store, rung, with_hits, first,
                                         "bm25")).called
    nodes = _questions(77, 500)
    rng = np.random.default_rng(9)
    before = _ledger("hits", "misses")
    with _Builds() as b:
        at = 0
        while at < len(nodes):
            n = int(rng.choice([1, 2, 5, 8, 9, 31, 32, 33]))
            seg.topk_batch(nodes[at:at + n], int(rng.integers(1, 11)))
            at += n
        outs = [None] * 40
        bar = threading.Barrier(40)

        def submit(i):
            bar.wait(timeout=30)
            outs[i] = batched_topk(ms, nodes[i], 10)[0]
        ts = [threading.Thread(target=submit, args=(i,)) for i in range(40)]
        [t.start() for t in ts]
        [t.join(timeout=120) for t in ts]
    assert (b.jax, b.ledger) == (0, 0)
    moved = {f for f in _ledger("hits", "misses", since=before)
             if f.startswith(("bm25_", "dense_"))}
    assert moved == {"bm25_accumulate", "bm25_topk", "bm25_resident"}
    assert ms.prebuild() == 0


# -- a question's WAND plan computes block-row bounds only where they are read --

def _plan_mix(seg):
    """Questions of which the default MAXSCORE_CAND_CAP sends some to
    the host rung and some to the plane rung: generated ones, unions of
    resident terms with a few heavy terms the budget did not hold, and
    unions of more frequent terms than are resident."""
    rng = np.random.default_rng(3)
    order = np.argsort(-seg.index.doc_freq, kind="stable")

    def union(ranks):
        return QOr([QTerm(str(seg.index.terms_str[order[i]]))
                    for i in ranks])
    mixed = [union(np.concatenate([
        rng.choice(RESIDENT_ROWS, int(rng.integers(2, 12)), replace=False),
        rng.choice(np.arange(RESIDENT_ROWS, 120), int(rng.integers(1, 5)),
                   replace=False)])) for _ in range(14)]
    return _questions(11, 12) + mixed + \
        [_many_resident(seg, n) for n in (20, 28, 30)]


def _planned(seg, monkeypatch, eager=False):
    """Every WAND plan the searcher makes from here on, by its question's
    term ids, from an empty plan memo; `eager` asks each plan for the
    kept rows of every heavy term as soon as it is made."""
    seen = {}
    plan_of = SegmentSearcher._wand_plan_cached

    def spy(self, store, tids, *args):
        plan = plan_of(self, store, tids, *args)
        if plan is not None and eager:
            for t in tids:
                if store.heavy[int(t)]:
                    plan.kept(store, int(t))
        seen[tuple(int(t) for t in tids)] = plan
        return plan
    monkeypatch.setattr(SegmentSearcher, "_wand_plan_cached", spy)
    vars(seg._device_store()).pop("_plan_cache", None)
    return seen


def _reference_kept(seg, tids, theta):
    """Every heavy term's block rows that may reach `theta`, all at once
    as the bound is defined: a row's own upper bound plus, for every
    other term of the question (heavy ones first, then light ones, each
    in question order), the range max of that term's per-bucket bounds
    over the row's documents."""
    store, fi = seg._device_store(), seg.index
    avg = fi.avgdl
    idf = bm25_ops.idf_for("bm25", seg.num_docs,
                           fi.doc_freq[np.asarray(tids, dtype=np.int64)])
    terms = [(int(t), float(w)) for t, w in zip(tids, idf)]
    terms = [x for x in terms if store.heavy[x[0]]] + \
        [x for x in terms if not store.heavy[x[0]]]
    shift = 10
    while (store.ndocs_pad >> shift) + 1 > 16384:
        shift += 1
    out = {}
    for tid, w in terms:
        if not store.heavy[tid]:
            continue
        b0 = int(store.block_offsets[tid])
        r = np.arange(b0, int(store.block_offsets[tid + 1]), dtype=np.int64)
        ub = w * bm25_ops._sat_exact(store.block_bmax_tf[r],
                                     store.block_bmin_dl[r], 1.2, 0.75, avg,
                                     "bm25")
        s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
        first = store.flat_docs[s + (r - b0) * 128] >> shift
        last = store.flat_docs[np.minimum(s + (r - b0 + 1) * 128, e) - 1] \
            >> shift
        other = np.zeros(len(r))
        for tid2, w2 in terms:
            if tid2 != tid:
                tab = bm25_ops._bucket_tables(store, tid2, avg, 1.2, 0.75,
                                              "bm25", shift)
                other += w2 * np.maximum(
                    bm25_ops._range_max(tab, first, last), 0.0)
        out[tid] = r[ub + other >= theta]
    return out


def _bounds_wanted(seg, nodes, seen, tiers):
    """Per planned question (term ids, plan, the heavy terms the plane
    rung scatters: the non-resident ones of a question it scored, none
    of a question the host scored)."""
    store, rows = _rows_of(seg)
    out = []
    for q, tier in zip(nodes, tiers):
        tids = tuple(int(t) for t in seg._query_shape(q)[0])
        plan = seen.get(tids)
        if plan is None:
            continue
        out.append((tids, plan, set() if tier == "host" else
                    {t for t in tids
                     if store.heavy[t] and rows.row_of[t] < 0}))
    return out


def test_a_lazy_plan_answers_bit_for_bit_as_an_eager_one(resident,
                                                        monkeypatch):
    """On a store with resident rows, a mix of questions at the default
    candidate cap answers bit for bit whether each plan computes the
    kept rows of the terms asked or of every heavy term; the rows a
    device-scored question's non-resident heavy terms keep are the
    definition's, and those of the eager plan's every heavy term too."""
    ms, seg, _ = resident
    nodes = _plan_mix(seg)
    tiers = [None] * len(nodes)
    seen = _planned(seg, monkeypatch)
    lazy = seg.topk_batch(nodes, 10, tiers=tiers)
    wanted = _bounds_wanted(seg, nodes, seen, tiers)
    eager_seen = _planned(seg, monkeypatch, eager=True)
    eager_tiers = [None] * len(nodes)
    assert _bits(seg.topk_batch(nodes, 10, tiers=eager_tiers)) == _bits(lazy)
    assert eager_tiers == tiers
    store = seg._device_store()
    scattered = 0
    for tids, plan, want in wanted:
        ref = _reference_kept(seg, tids, plan.theta)
        assert set(plan._kept) == want
        for t in want:
            assert np.array_equal(plan._kept[t], ref[t]), t
        scattered += len(want)
        eager = eager_seen[tids]
        assert eager.theta == plan.theta
        assert set(eager._kept) == set(ref)
        for t, r in ref.items():
            assert eager.kept(store, t).dtype == np.int64
            assert np.array_equal(eager.kept(store, t), r), t
    hosted = sum(tier == "host" for tier in tiers)
    assert scattered and hosted and len(wanted) > hosted


def test_no_bound_is_computed_for_a_resident_term_or_a_hosted_question(
        resident, monkeypatch):
    """The plane rung computes a block-row bound only for the heavy terms
    it scatters: `_range_max` runs once per (such term, other term of
    its question), and `SearchPlanBoundsComputed` /
    `SearchPlanBoundsSkipped` count every heavy term of a planned
    question once, skipped where it is resident or its question went
    to the host."""
    ms, seg, _ = resident
    calls = []
    range_max = bm25_ops._range_max

    def counted(tab, lo, hi):
        calls.append(len(lo))
        return range_max(tab, lo, hi)
    monkeypatch.setattr(bm25_ops, "_range_max", counted)
    nodes = _plan_mix(seg)
    tiers = [None] * len(nodes)
    seen = _planned(seg, monkeypatch)
    c0 = metrics.SEARCH_PLAN_BOUNDS_COMPUTED.value
    s0 = metrics.SEARCH_PLAN_BOUNDS_SKIPPED.value
    seg.topk_batch(nodes, 10, tiers=tiers)
    store = seg._device_store()
    wanted = _bounds_wanted(seg, nodes, seen, tiers)
    heavy = sum(sum(bool(store.heavy[t]) for t in tids)
                for tids, _, _ in wanted)
    asked = sum(len(want) for _, _, want in wanted)
    assert metrics.SEARCH_PLAN_BOUNDS_COMPUTED.value - c0 == asked
    assert metrics.SEARCH_PLAN_BOUNDS_SKIPPED.value - s0 == heavy - asked
    assert 0 < asked < heavy - asked
    assert len(calls) == sum(len(tids) - tids.count(t)
                             for tids, _, want in wanted for t in want)
    assert metrics.REGISTRY.snapshot()["SearchPlanBoundsSkipped"] == \
        metrics.SEARCH_PLAN_BOUNDS_SKIPPED.value    # what /metrics renders


def test_the_host_reference_and_a_batch_without_resident_rows_get_every_kept_row(
        resident, monkeypatch):
    """`cpu_topk_wand` reads the definition's kept rows of every heavy
    term it scores, and answers as exhaustive scoring; a batch assembled
    with no resident rows (the mesh scorer's) carries, for every heavy
    term of every question, the rows the definition keeps. The mesh
    rung plans nothing: its answers are the host's, and neither counter
    moves."""
    from serenedb_tpu.search.searcher import _maxscore_split
    ms, seg, _ = resident
    store, fi = seg._device_store(), seg.index
    nodes = _plan_mix(seg)
    seen = _planned(seg, monkeypatch)
    for q in nodes:
        tids = [int(t) for t in seg._query_shape(q)[0]]
        got = seg.cpu_topk_wand(tids, 10)
        plan = seen[tuple(tids)]
        if plan is None:
            continue
        ref = _reference_kept(seg, tids, plan.theta)
        ess = set(tids) - _maxscore_split(plan) or set(tids)
        assert set(plan._kept) == {t for t in ess if store.heavy[t]}
        for t in plan._kept:
            assert np.array_equal(plan._kept[t], ref[t]), t
        want = seg._cpu_score(np.arange(seg.num_docs, dtype=np.int32),
                              tids, 10)
        _same_topk(got, (want[0][want[0] > 0], want[1][want[0] > 0]))

    queries = [(np.asarray(seg._query_shape(q)[0], dtype=np.int64), 0)
               for q in nodes]
    plans = [seen[tuple(t.tolist())] for t, _ in queries]
    assert sum(p is not None for p in plans) > len(plans) // 2

    class Reference:
        def __init__(self, tids, plan):
            self.rows = _reference_kept(seg, tids.tolist(), plan.theta)

        def kept(self, store, tid):
            return self.rows[tid]
    vars(store).pop("_plan_cache", None)
    fresh = [seg._wand_plan_cached(store, t.tolist(), 10, fi.avgdl, "bm25",
                                   None) for t, _ in queries]
    got = bm25_ops.assemble_query_batch(store, seg.num_docs, queries,
                                        fi.doc_freq, plans=fresh)
    want = bm25_ops.assemble_query_batch(
        store, seg.num_docs, queries, fi.doc_freq,
        plans=[p and Reference(t, p) for (t, _), p in zip(queries, plans)])
    for name in ("row_idx", "row_qid", "raw_idx", "raw_qid", "tail_docs"):
        assert np.array_equal(getattr(got, name), getattr(want, name)), name
    assert got.n_postings == want.n_postings
    for (t, _), p in zip(queries, fresh):
        if p is not None:
            assert set(p._kept) == {int(x) for x in t if store.heavy[x]}

    c0 = metrics.SEARCH_PLAN_BOUNDS_COMPUTED.value
    s0 = metrics.SEARCH_PLAN_BOUNDS_SKIPPED.value
    unions = [q for q in nodes if isinstance(q, QOr)][:8]
    mesh = seg.topk_batch(unions, 10, mesh_n=2)
    assert (metrics.SEARCH_PLAN_BOUNDS_COMPUTED.value,
            metrics.SEARCH_PLAN_BOUNDS_SKIPPED.value) == (c0, s0)
    for q, res in zip(unions, mesh):
        _same_topk(res, seg.cpu_topk_wand(seg._query_shape(q)[0], 10))
