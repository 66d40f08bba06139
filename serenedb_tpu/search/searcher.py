"""Index-backed query evaluation: filters on CPU set algebra, scoring on TPU.

Reference analog: prepared queries over a DirectoryReader snapshot —
ScanMode::Stream (filter → doc iterator) and ScanMode::TopK (parallel scored
collectors) (reference: server/connector/duckdb_search_full_scan.hpp:54-76).

Split of labor (SURVEY.md §7 phase 2): term dictionary lookups and boolean
doc-set algebra stay on CPU (pointer-chasing), BM25 scoring + top-k runs as
the dense block kernel in ops/bm25.py. Results must match the brute-force
semantics contract in search/query.py — asserted by parity tests.

Scoring semantics: a document's score is the sum of BM25 contributions of
every positive leaf term of the query (phrase members and prefix expansions
included); NOT-subtrees and phrase adjacency affect *matching* only.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.device import pad_len
from ..obs import device as obs_device
from ..obs.trace import stage
from ..ops import bm25 as bm25_ops
from ..utils import metrics
from ..utils.config import REGISTRY
from .analysis import Analyzer
from .automaton import intersect_sorted, levenshtein_nfa
from .query import (QAnd, QFuzzy, QNode, QNot, QNothing, QOr, QPhrase,
                    QPrefix, QRegex, QTerm, parse_query)
from .segment import BLOCK, FieldIndex

K1 = 1.2
B = 0.75  # reference defaults: libs/iresearch/search/bm25.hpp

def _batch_cap() -> int:
    """Queries the batcher may coalesce into one `topk_batch` call
    (`serene_search_batch_max`): with the store's padded document count,
    what the closed set of scoring programs is enumerated from."""
    return max(int(REGISTRY.get_global("serene_search_batch_max")), 1)


def _maxscore_split(plan) -> set:
    """Non-essential terms of a WandPlan: the ascending-maxscore prefix
    whose cumulative sum stays below θ — docs containing only those terms
    can never reach the top-k. Shared by the device candidate generation
    and the CPU WAND baseline so the split rule cannot diverge."""
    cum = 0.0
    non_ess = set()
    for tid, ms in sorted(plan.maxscore.items(), key=lambda t: t[1]):
        if cum + ms < plan.theta:
            cum += ms
            non_ess.add(tid)
        else:
            break
    return non_ess


class MatchMemo:
    """The phrase match sets one request has evaluated, by (segment
    searcher, phrase signature): sorted doc ids. It lives as long as the
    request that opened it (`request_matches`) and holds nothing of any
    other — no cache: the page and the total of one `_search` read one
    evaluation. `joined` is the view a coalesced dispatch works under:
    it reads and fills every member's own memo (the key is structural,
    so two members asking the same phrase share one join)."""

    __slots__ = ("parts",)

    def __init__(self, parts: Optional[list] = None):
        self.parts = [{}] if parts is None else parts

    @classmethod
    def joined(cls, memos: list) -> Optional["MatchMemo"]:
        parts = [p for m in memos if m is not None for p in m.parts]
        return cls(parts) if parts else None

    def get(self, key):
        for p in self.parts:
            hit = p.get(key)
            if hit is not None:
                return hit
        return None

    def put(self, key, docs: np.ndarray) -> None:
        for p in self.parts:
            p[key] = docs


#: the MatchMemo of the request this context serves, or None. Pool tasks
#: copy the context; the batcher carries each member's into the dispatch
#: that scores it (search/batcher.py).
REQUEST_MATCHES: contextvars.ContextVar = contextvars.ContextVar(
    "sdb_request_matches", default=None)


@contextlib.contextmanager
def request_matches():
    """`with request_matches():` — the statements run inside belong to
    one request and share its phrase match sets."""
    tok = REQUEST_MATCHES.set(MatchMemo())
    try:
        yield
    finally:
        REQUEST_MATCHES.reset(tok)


def _plain_phrase(node: QNode) -> bool:
    """A phrase of plain terms, adjacent (slop 0, one alternative per
    slot): what the array join matches. Sloppy phrases and synonym
    groups keep the per-document matcher."""
    return isinstance(node, QPhrase) and node.slop == 0 and \
        len(node.groups) > 1 and all(len(g) == 1 for g in node.groups)


def _conjunction_of_terms(node: QNode) -> bool:
    return isinstance(node, QAnd) and len(node.args) > 0 and \
        all(isinstance(a, QTerm) for a in node.args)


def _in_sorted(hay: np.ndarray, needles: np.ndarray) -> np.ndarray:
    """Bool per needle: is it in the sorted array `hay`."""
    if not len(hay):
        return np.zeros(len(needles), dtype=bool)
    ix = np.searchsorted(hay, needles)
    ix[ix == len(hay)] = len(hay) - 1
    return hay[ix] == needles


def _bits_of(flags: np.ndarray) -> np.ndarray:
    """Bool flags packed into a doc bitset: `ceil(len / 64)` uint64 words,
    doc d at bit (d & 7) of BYTE d >> 3 (so a bit test reads the byte
    view and no word order enters)."""
    packed = np.packbits(flags, bitorder="little")
    out = np.zeros(-(-len(flags) // 64), dtype=np.uint64)
    out.view(np.uint8)[:len(packed)] = packed
    return out


def _bits_at(bits: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Bool per id: is its bit set in the doc bitset."""
    return (bits.view(np.uint8)[ids >> 3] >> (ids & 7).astype(np.uint8)) \
        & 1 != 0


class SegmentSearcher:
    def __init__(self, index: FieldIndex, analyzer: Analyzer, num_docs: int):
        self.index = index
        self.analyzer = analyzer
        self.num_docs = num_docs
        self._dev = None
        # tid → doc bitset of a dense term (`count_filter`)
        self._doc_bits: dict[int, np.ndarray] = {}
        self._doc_bits_lock = threading.Lock()

    # -- device posting store (lazy, cached) ------------------------------

    def _device_store(self) -> bm25_ops.BlockStore:
        if self._dev is None:
            self._dev = bm25_ops.build_block_store(
                self.index.offsets, self.index.post_docs,
                self.index.post_tfs, self.index.doc_freq,
                self.index.norms, self.num_docs)
            held = self._dev.length_bytes
            metrics.SEARCH_POSTING_LENGTH_BYTES.add(held)
            weakref.finalize(self._dev,
                             metrics.SEARCH_POSTING_LENGTH_BYTES.sub, held)
        return self._dev

    def _dense_store(self, scorer: str,
                     avgdl: float) -> Optional[bm25_ops.DenseStore]:
        """Dense saturation matrix, cached per (scorer shape, avgdl) —
        segments are immutable, and avgdl only drifts when collection
        stats change: every term's rows for the small-corpus dense path
        (`_use_dense`), else the resident rows of the dense terms
        (`bm25_ops.resident_terms`) that the plane kernel adds in
        place of their postings — None where no dense term fits."""
        cache = getattr(self, "_dense_cache", None)
        if cache is None:
            cache = self._dense_cache = {}
        store = self._device_store()
        whole = self._use_dense(store, scorer, avgdl)
        # tfidf's St (sqrt tf) is avgdl-independent — don't rebuild it when
        # collection stats drift
        key = (whole, "tfidf") if scorer == "tfidf" \
            else (whole, "bm25", round(avgdl, 6))
        if key not in cache:
            if len(cache) >= 2:   # St is the dominant HBM tenant — keep ≤2
                cache.clear()
            terms = None
            if not whole:
                every = np.arange(len(self.index.doc_freq))
                terms = bm25_ops.resident_terms(
                    self.index.doc_freq, self._dense_terms(every),
                    store.ndocs_pad)
            hit = None
            if terms is None or len(terms):
                hit = bm25_ops.build_dense_store(
                    store, self.index.doc_freq, avgdl, K1, B, scorer, terms)
                metrics.SEARCH_RESIDENT_ROW_BYTES.add(hit.St.nbytes)
                weakref.finalize(hit, metrics.SEARCH_RESIDENT_ROW_BYTES.sub,
                                 hit.St.nbytes)
            cache[key] = hit
        return cache[key]

    def _resident_rows(self, scorer: str,
                       avgdl: float) -> Optional[bm25_ops.DenseStore]:
        """The resident rows the plane kernel adds for this store, or
        None where it adds none: a scorer that is not `w · sat` (the LM
        family), no avgdl to saturate with, or no dense term within the
        budget."""
        if scorer not in ("bm25", "tfidf") or \
                (scorer == "bm25" and avgdl <= 0.0):
            return None
        return self._dense_store(scorer, avgdl)

    # -- filter evaluation (CPU doc-set algebra) --------------------------

    def eval_filter(self, node: QNode) -> np.ndarray:
        """Sorted doc ids matching the query node. Memoized in the
        process-wide fragment cache (cache/fragments.py): segments are
        immutable, so a filter doc set is valid for this object's whole
        lifetime — the ES shard-request-cache analog. Recursive
        sub-nodes memoize individually, so `a AND b` reuses a cached
        `a`. Unknown node shapes and `serene_result_cache = off`
        sessions compute straight through."""
        from ..cache.fragments import FRAGMENTS, qnode_sig
        sig = qnode_sig(node)
        return FRAGMENTS.cached(
            self, None if sig is None else ("filter", sig),
            lambda: self._eval_filter_uncached(node))

    def _eval_filter_uncached(self, node: QNode) -> np.ndarray:
        if isinstance(node, QTerm):
            tid = self.index.term_id(node.term)
            if tid < 0:
                return np.empty(0, dtype=np.int32)
            return self.index.postings(tid)[0]
        if isinstance(node, QPrefix):
            return self._union_postings(self.index.prefix_term_ids(
                node.prefix))
        if isinstance(node, QFuzzy):
            return self._union_postings(self._fuzzy_term_ids(node))
        if isinstance(node, QRegex):
            return self._union_postings(self._regex_term_ids(node))
        if isinstance(node, QPhrase):
            if _plain_phrase(node):
                return self._phrase_docs(node)
            return self._eval_phrase(node.groups, node.slop)
        if isinstance(node, QNothing):
            return np.empty(0, dtype=np.int32)
        if isinstance(node, QAnd):
            if not node.args:
                return np.empty(0, dtype=np.int32)
            pos = [a for a in node.args if not isinstance(a, QNot)]
            neg = [a for a in node.args if isinstance(a, QNot)]
            if pos:
                acc = self.eval_filter(pos[0])
                for a in pos[1:]:
                    acc = np.intersect1d(acc, self.eval_filter(a),
                                         assume_unique=True)
            else:
                acc = np.arange(self.num_docs, dtype=np.int32)
            for a in neg:
                acc = np.setdiff1d(acc, self.eval_filter(a.arg),
                                   assume_unique=True)
            return acc
        if isinstance(node, QOr):
            parts = [self.eval_filter(a) for a in node.args]
            return np.unique(np.concatenate(parts)) if parts \
                else np.empty(0, dtype=np.int32)
        if isinstance(node, QNot):
            inner = self.eval_filter(node.arg)
            return np.setdiff1d(np.arange(self.num_docs, dtype=np.int32),
                                inner, assume_unique=True)
        return np.empty(0, dtype=np.int32)

    def _union_postings(self, tids) -> np.ndarray:
        """Sorted unique doc ids across the postings of several terms
        (multi-term leaves: prefix / fuzzy / regex expansions)."""
        parts = [self.index.postings(t)[0] for t in tids]
        return np.unique(np.concatenate(parts)) if parts \
            else np.empty(0, dtype=np.int32)

    # -- counting (the exact total of a search, no doc set built) ---------

    def _union_term_ids(self, node: QNode) -> Optional[list]:
        """Term ids whose posting lists' union IS the node's doc set, or
        None where the node is not such a union (conjunction, negation,
        phrase, an unknown shape)."""
        if isinstance(node, QTerm):
            tid = self.index.term_id(node.term)
            return [tid] if tid >= 0 else []
        if isinstance(node, QPrefix):
            return list(self.index.prefix_term_ids(node.prefix))
        if isinstance(node, QFuzzy):
            return list(self._fuzzy_term_ids(node))
        if isinstance(node, QRegex):
            return list(self._regex_term_ids(node))
        if isinstance(node, QNothing):
            return []
        if isinstance(node, QOr):
            out: list = []
            for a in node.args:
                tids = self._union_term_ids(a)
                if tids is None:
                    return None
                out.extend(tids)
            return out
        return None

    def _dense_terms(self, tids: np.ndarray) -> np.ndarray:
        """Bool per term id: its doc bitset is no larger than its own
        int32 posting list (`doc_freq * 32 >= num_docs`, in whole
        words). Those keep a bitset, so all of a segment's bitsets
        together never exceed the bytes of `post_docs`."""
        row_bytes = -(-self.num_docs // 64) * 8
        return self.index.doc_freq[tids].astype(np.int64) * 4 >= row_bytes

    def _term_bits(self, tid: int) -> np.ndarray:
        """A dense term's doc bitset, built once: segments are immutable
        (the pattern is `FieldIndex.ctf`'s). `prebuild` builds them all;
        a segment that never went through it builds on first use."""
        row = self._doc_bits.get(tid)
        if row is None:
            flags = np.zeros(self.num_docs, dtype=bool)
            flags[self.index.postings(tid)[0]] = True
            row = _bits_of(flags)
            with self._doc_bits_lock:
                if tid in self._doc_bits:
                    return self._doc_bits[tid]
                self._doc_bits[tid] = row
            metrics.SEARCH_COUNT_BITSET_BYTES.add(row.nbytes)
        return row

    @property
    def count_bitset_bytes(self) -> int:
        return sum(r.nbytes for r in list(self._doc_bits.values()))

    def count_filter(self, node: QNode,
                     valid_bits: Optional[np.ndarray] = None) -> int:
        """`len(eval_filter(node))` without a sorted doc set built for
        the count, where the node's shape allows:
        - a union of posting lists (a term, a disjunction of such, a
          prefix / fuzzy / regex expansion): the dense terms' bitsets
          are OR-ed into one accumulator and its set bits counted, then
          the sparse terms' (short) lists add the ids whose bit is not
          set;
        - a conjunction of terms: the dense terms' bitsets AND-ed, the
          sparse terms' lists intersected rarest first and probed
          against them (`_count_conjunction`);
        - a phrase of plain terms: the size of its positional join
          (`_phrase_docs`: the request's own, where its page already
          ran it).
        Every other shape takes the length of its doc set, as before,
        and so does one whose doc set the fragment cache already holds
        (a Stream scan left it: that is the cache's hit).
        `valid_bits`: the doc bitset of the column's non-NULL rows in
        this segment's doc space, AND-ed in (a predicate over a NULL
        text is never true)."""
        from ..cache.fragments import FRAGMENTS, qnode_sig
        tids = self._union_term_ids(node)
        counted = tids is not None or _conjunction_of_terms(node) or \
            _plain_phrase(node)
        held = None
        if counted:
            sig = qnode_sig(node)
            held = FRAGMENTS.probe(
                self, None if sig is None else ("filter", sig))
        if not counted or held is not None:
            metrics.SEARCH_COUNT_MATERIALIZED.add()
            if held is not None:
                FRAGMENTS.count_hits(1)
            docs = held if held is not None else self.eval_filter(node)
            return self._count_valid(docs, valid_bits)
        if tids is None:
            metrics.SEARCH_COUNT_INTERSECTED.add()
            if isinstance(node, QPhrase):
                return self._count_valid(self._phrase_docs(node),
                                         valid_bits)
            return self._count_conjunction(node, valid_bits)
        metrics.SEARCH_COUNT_BITSET.add()
        # a term given twice counts once
        tids = np.unique(np.asarray(tids, dtype=np.int64))
        dense = self._dense_terms(tids)
        acc = functools.reduce(
            np.bitwise_or, [self._term_bits(int(t)) for t in tids[dense]],
            np.zeros(-(-self.num_docs // 64), dtype=np.uint64))
        if valid_bits is not None:
            acc &= valid_bits
        n = int(np.bitwise_count(acc).sum())
        sparse = [self.index.postings(int(t))[0] for t in tids[~dense]]
        if sparse:
            ids = sparse[0] if len(sparse) == 1 \
                else np.unique(np.concatenate(sparse))
            fresh = ~_bits_at(acc, ids)
            if valid_bits is not None:
                fresh &= _bits_at(valid_bits, ids)
            n += int(fresh.sum())
        return n

    @staticmethod
    def _count_valid(docs: np.ndarray,
                     valid_bits: Optional[np.ndarray]) -> int:
        if valid_bits is not None and len(docs):
            return int(_bits_at(valid_bits, docs).sum())
        return len(docs)

    def _count_conjunction(self, node: QAnd,
                           valid_bits: Optional[np.ndarray]) -> int:
        """How many documents hold every term of a conjunction of terms:
        no list of a dense term is read."""
        tids = [self.index.term_id(a.term) for a in node.args]
        if min(tids) < 0:
            return 0
        tids = np.unique(np.asarray(tids, dtype=np.int64))
        dense = self._dense_terms(tids)
        rows = [self._term_bits(int(t)) for t in tids[dense]]
        if valid_bits is not None:
            rows.append(valid_bits)
        acc = functools.reduce(np.bitwise_and, rows) if rows else None
        if dense.all():
            return int(np.bitwise_count(acc).sum())
        docs = self._conjunction_docs(tids[~dense])
        return self._count_valid(docs, acc)

    # -- phrases (the positional join, on arrays) -------------------------

    def _conjunction_docs(self, tids) -> np.ndarray:
        """Sorted doc ids that hold every term of `tids`: the posting
        lists intersected rarest first, each step one searchsorted of
        the survivors into the next list."""
        fi = self.index
        tids = sorted({int(t) for t in tids},
                      key=lambda t: int(fi.doc_freq[t]))
        docs = fi.postings(tids[0])[0]
        for t in tids[1:]:
            if not len(docs):
                break
            docs = docs[_in_sorted(fi.postings(t)[0], docs)]
        return docs

    def _slot_keys(self, tid: int, docs: np.ndarray,
                   shift: int) -> np.ndarray:
        """`doc << 32 | position - shift` of every occurrence of term
        `tid` in `docs` (sorted; each holds the term), ascending — the
        key of the phrase start that would put this occurrence in slot
        `shift`. Occurrences before position `shift` start none."""
        fi = self.index
        s = int(fi.offsets[tid])
        at = s + np.searchsorted(fi.post_docs[s:int(fi.offsets[tid + 1])],
                                 docs)
        lo = fi.pos_offsets[at]
        n = fi.pos_offsets[at + 1] - lo
        # the postings' position runs gathered flat: run i starts at lo[i]
        flat = np.repeat(lo - (np.cumsum(n) - n), n) + \
            np.arange(int(n.sum()), dtype=np.int64)
        pos = fi.positions[flat].astype(np.int64) - shift
        keys = np.repeat(docs.astype(np.int64) << 32, n) + pos
        return keys[pos >= 0] if shift else keys

    def _phrase_join(self, terms: list) -> np.ndarray:
        """Sorted doc ids in which `terms` stand at consecutive
        positions: a join of (document, position) keys, slot j's shifted
        back by j, over the documents that hold every term — rarest
        slot first, each further slot read only in the documents still
        alive. No per-document Python."""
        fi = self.index
        tids = [fi.term_id(t) for t in terms]
        if min(tids) < 0:
            return np.empty(0, dtype=np.int32)
        docs = self._conjunction_docs(tids)
        metrics.SEARCH_PHRASE_CANDIDATES.add(len(docs))
        keys = np.empty(0, dtype=np.int64)
        for n, j in enumerate(sorted(range(len(tids)),
                                     key=lambda j: int(fi.doc_freq[tids[j]]))):
            if not len(docs):
                break
            slot = self._slot_keys(tids[j], docs, j)
            keys = keys[_in_sorted(slot, keys)] if n else slot
            docs = np.unique(keys >> 32).astype(np.int32)
        metrics.SEARCH_PHRASE_MATCHES.add(len(docs))
        return docs

    def _phrase_docs(self, node: QPhrase) -> np.ndarray:
        """The match set of a phrase of plain terms (`_plain_phrase`):
        from the request's memo where this request already joined it,
        else joined now, under the stage `search_phrase`."""
        from ..cache.fragments import qnode_sig
        memo = REQUEST_MATCHES.get()
        key = (self, qnode_sig(node))
        if memo is not None:
            hit = memo.get(key)
            if hit is not None:
                return hit
        with stage("search_phrase"):
            docs = self._phrase_join([g[0] for g in node.groups])
        if memo is not None:
            memo.put(key, docs)
        return docs

    def _eval_phrase(self, groups: list[list[str]],
                     slop: int = 0) -> np.ndarray:
        """Phrase over per-position alternative groups: each slot is the
        union of its alternatives' postings (synonym expansions), slots
        must land on consecutive doc positions — or, with slop > 0, in
        order with total extra gap <= slop (Lucene `"..."~N`, minus its
        bounded-reorder allowance; same contract as query._sloppy_match).
        Per document, in Python: what sloppy phrases and synonym groups
        take. A phrase of plain terms with slop 0 never comes here
        (`_phrase_docs`)."""
        if not groups:
            return np.empty(0, dtype=np.int32)
        gtids = [[t for t in (self.index.term_id(a) for a in g) if t >= 0]
                 for g in groups]
        if any(not g for g in gtids):
            return np.empty(0, dtype=np.int32)
        cand = self._union_postings(gtids[0])
        for g in gtids[1:]:
            cand = np.intersect1d(cand, self._union_postings(g),
                                  assume_unique=True)
        if len(groups) == 1 or len(cand) == 0:
            return cand
        # doc → union of positions across the group's alternatives
        pos_maps = []
        for g in gtids:
            merged: dict[int, set] = {}
            for t in g:
                for d, ps in self.index.positions_of(t, cand).items():
                    merged.setdefault(int(d), set()).update(
                        int(p) for p in ps)
            pos_maps.append(merged)
        from .query import _sloppy_match
        out = []
        for d in cand:
            d = int(d)
            first = pos_maps[0].get(d)
            if first is None:
                continue
            rest = [pm.get(d) for pm in pos_maps[1:]]
            if any(r is None for r in rest):
                continue
            if slop > 0:
                hit = _sloppy_match(first, rest, slop)
            else:
                hit = any(all((p + k1) in rs
                              for k1, rs in enumerate(rest, 1))
                          for p in first)
            if hit:
                out.append(d)
        return np.asarray(out, dtype=np.int32)

    def _fuzzy_term_ids(self, node: QFuzzy) -> list[int]:
        """Edit-distance expansion over the term dictionary (reference:
        levenshtein parametric automata over the burst trie; here a
        length-banded numpy prefilter + banded edit distance). Uncapped —
        indexed results must equal brute-force evaluation. Memoized per
        (term, edits) while the segment is alive (segments are
        immutable)."""
        cache = getattr(self, "_fuzzy_cache", None)
        if cache is None:
            cache = self._fuzzy_cache = {}
        key = (node.term, node.max_edits)
        hit = cache.get(key)
        if hit is not None:
            return hit
        start, end = levenshtein_nfa(node.term, node.max_edits)
        out = intersect_sorted(start, end, self.index.terms_str)
        cache[key] = out
        return out

    def _regex_term_ids(self, node: QRegex) -> list[int]:
        """Full-term regex expansion over the term dictionary (reference:
        by_regexp runs an automaton over the burst trie; here a linear scan
        of the sorted dictionary — segments are immutable, so memoized)."""
        cache = getattr(self, "_regex_cache", None)
        if cache is None:
            cache = self._regex_cache = {}
        hit = cache.get(node.pattern)
        if hit is not None:
            return hit
        rx = node.compiled
        out = intersect_sorted(rx.start, rx.end, self.index.terms_str)
        cache[node.pattern] = out
        return out

    # -- scoring (device) --------------------------------------------------

    def scoring_terms(self, node: QNode) -> list[int]:
        """Positive leaf term ids contributing to the score."""
        out: list[int] = []

        def rec(nd):
            if isinstance(nd, QTerm):
                t = self.index.term_id(nd.term)
                if t >= 0:
                    out.append(t)
            elif isinstance(nd, QPhrase):
                for term in nd.terms:
                    t = self.index.term_id(term)
                    if t >= 0:
                        out.append(t)
            elif isinstance(nd, QPrefix):
                out.extend(int(t) for t in
                           self.index.prefix_term_ids(nd.prefix))
            elif isinstance(nd, QFuzzy):
                out.extend(self._fuzzy_term_ids(nd))
            elif isinstance(nd, QRegex):
                out.extend(self._regex_term_ids(nd))
            elif isinstance(nd, (QAnd, QOr)):
                for a in nd.args:
                    rec(a)
            # QNot: no score contribution
        rec(node)
        seen = set()
        uniq = []
        for t in out:
            if t not in seen:
                seen.add(t)
                uniq.append(t)
        return uniq

    def _query_shape(self, node: QNode) -> tuple[list[int], int, bool, bool]:
        """(scoring term ids, require_all, needs_exact_mask, always_empty).

        always_empty: a pure conjunction containing a term absent from the
        index can never match (scoring_terms silently drops absent terms, so
        require_all alone would degrade the AND)."""
        tids = self.scoring_terms(node)
        require_all = 0
        needs_mask = False
        empty = False
        if isinstance(node, (QTerm, QPrefix, QFuzzy, QRegex)):
            pass
        elif isinstance(node, QOr) and all(
                isinstance(a, QTerm) for a in node.args):
            pass
        elif isinstance(node, QAnd) and all(
                isinstance(a, QTerm) for a in node.args):
            require_all = len(tids)
            if any(self.index.term_id(a.term) < 0 for a in node.args):
                empty = True
        else:
            needs_mask = True
        return tids, require_all, needs_mask, empty

    def _wand_plan_cached(self, store, tids, k: int, avgdl: float,
                          scorer: str, idf_of):
        """wand_plan with a per-store memo — segments are immutable, and
        batched QPS workloads repeat query shapes."""
        tid_arr = np.asarray(tids, dtype=np.int64)
        if idf_of is not None:
            idf = np.asarray(idf_of(tid_arr), dtype=np.float32)
        else:
            idf = bm25_ops.idf_for(scorer, self.num_docs,
                                   self.index.doc_freq[tid_arr])
        cache = getattr(store, "_plan_cache", None)
        if cache is None:
            cache = store._plan_cache = {}
        if len(cache) > 8192:  # stale stats (avgdl/idf drift) accumulate keys
            cache.clear()
        key = (tuple(int(t) for t in tids), k, round(avgdl, 6), scorer,
               idf.tobytes())
        if key in cache:
            return cache[key]
        plan = bm25_ops.wand_plan(store, tids, idf, k, avgdl, K1, B, scorer)
        cache[key] = plan
        return plan

    # candidate cap for the sparse MaxScore path: above this, the dense
    # device kernel amortizes better than host gather-scoring
    MAXSCORE_CAND_CAP = 4096

    def _maxscore_candidates(self, plan, tids, k: int) -> Optional[np.ndarray]:
        """MaxScore essential-list split: if the non-essential terms' max
        scores sum below θ, docs containing ONLY non-essential terms can
        never reach the top-k, so the candidate set is the union of the
        essential terms' postings. Returns sorted candidate doc ids when
        the sparse path applies (small enough and ≥ k docs), else None.

        Reference analog: the max-score optimization of
        block_disjunction.hpp / max_score_iterator."""
        non_ess = _maxscore_split(plan)
        if not non_ess:
            return None
        ess = [t for t in tids if int(t) not in non_ess]
        if not ess:
            return None
        fi = self.index
        total = sum(int(fi.doc_freq[int(t)]) for t in ess)
        if total > self.MAXSCORE_CAND_CAP:
            return None
        parts = [fi.postings(int(t))[0] for t in ess]
        cand = np.unique(np.concatenate(parts)) if parts else None
        if cand is None or len(cand) < k:
            return None  # too few candidates to fill k exact slots
        return cand.astype(np.int32)

    def topk(self, node: QNode, k: int, scorer: str = "bm25",
             mesh_n: int = 0) -> tuple[np.ndarray, np.ndarray]:
        return self.topk_batch([node], k, scorer, mesh_n=mesh_n)[0]

    # cap on per-dispatch accumulator entries (B × ndocs_pad f32): bounds
    # HBM at large corpora — the rung ladder stops below it, and a batch
    # splits into query chunks instead of materializing (256, 8.8M) at
    # MS-MARCO scale
    ACC_ENTRY_CAP = 128 * 1024 * 1024

    def _rungs(self, store) -> tuple:
        """This store's ladder of program shapes, from what can be seen
        of it: its padded document count, the batcher's cap, the
        accumulator cap. The one list `prebuild` builds and
        `topk_batch` picks from."""
        return bm25_ops.score_rungs(store.ndocs_pad, _batch_cap(),
                                    self.ACC_ENTRY_CAP)

    def _use_dense(self, store, scorer: str, avgdl: float) -> bool:
        """The small-corpus dense path answers this store's searches
        (what `prebuild` builds for, and what `topk_batch` dispatches)."""
        return (scorer not in bm25_ops.LM_SCORERS and
                (scorer == "tfidf" or avgdl > 0.0) and
                bm25_ops.dense_fits(store.ndocs_pad,
                                    len(self.index.doc_freq)))

    def prebuild(self, avgdl: Optional[float] = None,
                 scorer: str = "bm25") -> int:
        """Upload this segment's posting store and build the closed set
        of programs its searches dispatch (ops/bm25.py), so that none is
        built by a search: the dense steps where the saturation matrix
        fits, else the plane kernel's accumulate and top-k steps (the
        top-k under doc masks among them, a phrase's), for every rung up
        to the batcher's cap at the first top-k bucket;
        and the dense terms' doc bitsets that `count_filter` ORs.
        Returns how many programs this call built."""
        if self.num_docs == 0:
            return 0
        every = np.arange(len(self.index.doc_freq))
        for tid in every[self._dense_terms(every)]:
            self._term_bits(int(tid))
        store = self._device_store()
        avgdl = self.index.avgdl if avgdl is None else avgdl
        kk = min(bm25_ops.pad_k(1), store.ndocs_pad)
        with obs_device.announced_builds():
            if self._use_dense(store, scorer, avgdl):
                built = bm25_ops.prebuild_dense_programs(
                    self._dense_store(scorer, avgdl), self._rungs(store),
                    kk)
            else:
                built = bm25_ops.prebuild_plane_programs(
                    store, self._rungs(store), kk, scorer)
                rows = self._resident_rows(scorer, avgdl)
                if rows is not None:
                    built += bm25_ops.prebuild_resident_programs(
                        rows, self._rungs(store))
        metrics.SEARCH_PROGRAMS_PREBUILT.add(built)
        return built

    def topk_batch(self, nodes: list[QNode], k: int, scorer: str = "bm25",
                   idf_of=None, avgdl_override=None, mesh_n: int = 0,
                   tiers: Optional[list] = None,
                   ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Top-k (scores, doc ids) for a batch of queries in ONE device
        dispatch (amortizes dispatch latency — the QPS regime): the
        batch is fitted to a rung of the store's closed program set
        (ops/bm25.py), and what exceeds the largest rung is split into
        several dispatches. Pure term disjunctions/conjunctions and
        phrases of plain terms run fully on the ladder; other shapes
        (NOT, nested booleans, sloppy and synonym phrases) get an
        exact-match CPU mask applied to the device scores. `tiers`, when
        given, is filled per query with "device" or "host": where its
        top-k was scored.

        The ladder — where a question is scored is chosen from the store
        and the question, never from the backend:
        1. mesh: `mesh_n` > 1, that many devices and neither a
           conjunction nor a doc mask in the batch — posting rows shard
           across the mesh (`score_topk_mesh`);
        2. dense: the saturation matrix fits (`_use_dense`) — row
           gathers over the `DenseStore`, no host planning;
        3. candidates on the host: a prunable disjunction whose
           essential terms (MaxScore) leave at most MAXSCORE_CAND_CAP
           candidates, or a phrase whose match set is no larger, is
           scored by `_cpu_score` and takes no slot of the dispatch;
        4. plane: everything else adds the store's resident rows of its
           dense terms (bm25 and tfidf: `_resident_rows`) and
           accumulates the other terms' WAND-kept posting rows into the
           score plane, then takes its top-k (`score_topk_planes`), all
           programs of the set `prebuild` built.
        A conjunction of terms rides rungs 2 and 4 in their `require`
        form (the hits plane). A phrase is a conjunction with a narrower
        match set, joined once per request (`_phrase_docs`), and the set
        bounds its top-k INSIDE the ladder: as rung 3's candidate list,
        or as a doc mask the top-k step of rungs 2 and 4 applies (which
        makes the hits plane needless: it scores as the union of its
        terms). Its device top-k is final; nothing is scored again.
        `cpu_topk_wand` is the host reference the tests compare with;
        no search is served by it."""
        if tiers is None:
            tiers = [None] * len(nodes)
        if self.num_docs == 0:
            tiers[:] = ["host"] * len(nodes)
            return [(np.empty(0, dtype=np.float32),
                     np.empty(0, dtype=np.int32))] * len(nodes)
        if scorer in bm25_ops.LM_SCORERS and idf_of is None:
            # LM-family weights are collection probabilities, not idf
            ctf, total = self.index.ctf, float(self.index.total_tokens)

            def idf_of(tids, _ctf=ctf, _tot=total):
                return bm25_ops.term_weight_for(
                    scorer, self.num_docs, None, _ctf[tids], _tot)
        store = self._device_store()
        rungs = self._rungs(store)
        max_b = rungs[-1].nq
        if len(nodes) > max_b:
            out = []
            for i in range(0, len(nodes), max_b):
                part = [None] * len(nodes[i:i + max_b])
                out.extend(self.topk_batch(nodes[i:i + max_b], k, scorer,
                                           idf_of, avgdl_override, mesh_n,
                                           part))
                tiers[i:i + max_b] = part
            return out
        nd_pad = store.ndocs_pad
        avgdl = (avgdl_override if avgdl_override is not None
                 else self.index.avgdl)
        k_true = min(max(k, 1), max(self.num_docs, 1))
        kk = min(bm25_ops.pad_k(k_true), nd_pad)
        plans: list = [None] * len(nodes)
        host_results: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        with stage("search_plan"):
            shapes = [self._query_shape(n) for n in nodes]
            queries = [(np.asarray(tids, dtype=np.int64) if not empty
                        else np.empty(0, dtype=np.int64), req)
                       for tids, req, _, empty in shapes]
            # block-max WAND applies to pure disjunctions whose device
            # top-k is final (no exact-match mask re-ranking a subset
            # afterwards); the LM scorers don't decompose as w·sat, so
            # their bounds don't hold
            prunable = [req == 0 and not needs_mask and not empty and
                        scorer not in bm25_ops.LM_SCORERS
                        for _, req, needs_mask, empty in shapes]
            masks: dict[int, np.ndarray] = {}
            for qi, node in enumerate(nodes):
                tids = shapes[qi][0]
                if not (tids and _plain_phrase(node)):
                    continue
                match = self._phrase_docs(node)
                shapes[qi] = (tids, 0, False, False)   # no mask afterwards
                if len(match) > self.MAXSCORE_CAND_CAP:
                    masks[qi] = match
                    continue
                with stage("search_host_score"):
                    host_results[qi] = self._cpu_score(
                        match, tids, k, scorer, idf_of, avgdl_override)
                queries[qi] = (np.empty(0, dtype=np.int64), 0)
            use_mesh = mesh_n > 1 and len(jax.devices()) >= mesh_n and \
                not masks and not any(req for _, req in queries)
            use_dense = not use_mesh and self._use_dense(store, scorer,
                                                         avgdl)
            if not use_mesh and not use_dense and \
                    store.norms_host is not None and \
                    (scorer == "tfidf" or avgdl > 0.0):
                for qi, (tids, req, needs_mask, empty) in enumerate(shapes):
                    if not (prunable[qi] and tids):
                        continue
                    plan = self._wand_plan_cached(store, tids, k_true,
                                                  avgdl, scorer, idf_of)
                    if plan is None:
                        continue
                    plans[qi] = plan
                    cand = self._maxscore_candidates(plan, tids, k_true)
                    if cand is not None:
                        with stage("search_host_score"):
                            host_results[qi] = self._cpu_score(
                                cand, tids, k, scorer, idf_of,
                                avgdl_override)
                        queries[qi] = (np.empty(0, dtype=np.int64), 0)
            live = any(len(q[0]) > 0 for q in queries)
            for qi, q in enumerate(queries):
                tiers[qi] = "device" if len(q[0]) > 0 else "host"
            resident = None
            if use_dense:
                slots, require = bm25_ops.dense_slots(
                    queries, self.num_docs, self.index.doc_freq, scorer,
                    idf_of)
                n_postings = n_resident = sum(
                    int(self.index.doc_freq[t].sum()) for t, _ in slots)
            elif live:
                if use_mesh:
                    # the mesh programs are jitted per n_queries: pad the
                    # query axis to a power of two with no-op empties
                    queries += [(np.empty(0, dtype=np.int64), 0)] * (
                        bm25_ops._pow2(len(queries), 1) - len(queries))
                rows = None if use_mesh else \
                    self._resident_rows(scorer, avgdl)
                n_resident = 0
                if rows is not None:
                    # the resident terms' rows are added whole; only the
                    # other terms' postings go to the accumulate steps
                    held = [rows.row_of[t] >= 0 for t, _ in queries]
                    slots, _ = bm25_ops.dense_slots(
                        [(t[h], r) for (t, r), h in zip(queries, held)],
                        self.num_docs, self.index.doc_freq, scorer, idf_of)
                    resident = (rows, [(rows.row_of[t], w)
                                       for t, w in slots])
                    n_resident = self._resident_postings(
                        store, slots, queries, plans)
                    queries = [(t[~h], r)
                               for (t, r), h in zip(queries, held)]
                qb = bm25_ops.assemble_query_batch(
                    store, self.num_docs, queries, self.index.doc_freq,
                    scorer, idf_of=idf_of, plans=plans)
                n_postings = qb.n_postings + n_resident
        if live:
            t_d = time.perf_counter_ns()
            # `device_prepare` wraps the dispatch's calls, as it wraps a
            # SQL offload: the steps' buffers, uploads and calls carve
            # their own stages out of it, and what is left — program
            # lookups, the ledger's notes between the calls — is its own
            with stage("device_prepare"):
                if use_mesh:
                    # mesh-sharded scoring: posting-row sections shard
                    # across the devices, score planes psum over ICI
                    # (SURVEY §5.7 — "scale one query across all
                    # compute"). require-free shapes only; _finish_batch
                    # applies exact-match masks as usual.
                    out = bm25_ops.score_topk_mesh(
                        store, qb, nd_pad, kk, mesh_n,
                        bm25_ops.scorer_param(scorer, K1), B, avgdl,
                        scorer)
                elif use_dense:
                    # small-corpus dense path: row gathers, no host WAND
                    # planning needed (the dense kernel is not
                    # scatter-bound)
                    out = bm25_ops.dense_score_topk(
                        self._dense_store(scorer, avgdl), slots, require,
                        bm25_ops.rung_for(rungs, len(queries)).nq, kk,
                        masks)
                else:
                    out = bm25_ops.score_topk_planes(
                        store, qb, bm25_ops.rung_for(rungs, len(queries)),
                        kk, bm25_ops.scorer_param(scorer, K1), B, avgdl,
                        scorer, masks, resident)
            vals, docs = obs_device.fetch_all(out)
            metrics.DEVICE_DISPATCH_HIST.observe_ns(
                time.perf_counter_ns() - t_d)
            metrics.SEARCH_POSTINGS_DISPATCHED.add(n_postings)
            metrics.SEARCH_POSTINGS_RESIDENT.add(n_resident)
        else:  # every query resolved host-side — skip the dispatch entirely
            vals = np.zeros((len(queries), kk), dtype=np.float32)
            docs = np.zeros((len(queries), kk), dtype=np.int32)
        with stage("search_host_score"):
            return self._finish_batch(nodes, shapes, vals, docs,
                                      host_results, k, scorer, idf_of,
                                      avgdl_override, nd_pad, tiers)

    def _resident_postings(self, store, slots, queries, plans) -> int:
        """The postings the resident terms' rows stand for, counted as
        `assemble_query_batch` counts the rows it hands over: a pruned
        disjunction's kept rows, else the whole list."""
        n = 0
        for qi, (t, _w) in enumerate(slots):
            plan = plans[qi] if queries[qi][1] == 0 else None
            for tid in t.tolist():
                n += int(store.row_count[plan.kept[tid]].sum()) \
                    if plan is not None and store.heavy[tid] \
                    else int(self.index.doc_freq[tid])
        return n

    def _finish_batch(self, nodes, shapes, vals, docs, host_results, k,
                      scorer, idf_of, avgdl_override, nd_pad, tiers,
                      ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Shared device-result postprocessing: host-resolved queries,
        always-empty conjunctions, zero-score matches, exact-match mask
        application (with CPU rescore when a non-match cracked the
        device top-k)."""
        out = []
        for qi, (node, (tids, req, needs_mask, empty)) in enumerate(
                zip(nodes, shapes)):
            if qi in host_results:
                scores, dd = host_results[qi]
                keep = scores > 0.0
                out.append((scores[keep][:k], dd[keep][:k]))
                continue
            scores, dd = vals[qi], docs[qi]
            if empty:
                out.append((np.empty(0, dtype=np.float32),
                            np.empty(0, dtype=np.int32)))
                continue
            if not tids:
                # no scoring terms (e.g. pure negation): matches exist but
                # all score 0 — return the first k matches with zero scores
                match = self.eval_filter(node)[:k]
                out.append((np.zeros(len(match), dtype=np.float32),
                            match.astype(np.int32)))
                continue
            if needs_mask:
                match = self.eval_filter(node)
                mset = np.zeros(nd_pad, dtype=bool)
                mset[match] = True
                ok = mset[dd]
                if (~ok[scores > 0.0]).any() and len(match) > 0:
                    # a non-match made device top-k → the survivors may not
                    # be the true top-k of the match set; exact CPU rescore
                    scores, dd = self._cpu_score(match, tids, k, scorer,
                                                 idf_of, avgdl_override)
                    tiers[qi] = "host"
                    if isinstance(node, QPhrase):
                        metrics.SEARCH_PHRASE_RESCORED.add()
                else:
                    scores, dd = scores[ok], dd[ok]
            keep = scores > 0.0
            scores, dd = scores[keep], dd[keep]
            out.append((scores[:k], dd[:k]))
        return out

    def cpu_topk_wand(self, tids: list[int], k: int, scorer: str = "bm25",
                      idf_of=None, avgdl_override=None,
                      require_all: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """Host top-k with block-max WAND + MaxScore pruning — the honest
        CPU competitor (reference: search/block_disjunction.hpp +
        max_score_iterator; Lucene/Tantivy-class baselines implement the
        same family). Numpy-vectorized block-at-a-time variant:

        1. champion pass → θ, a lower bound on the k-th score (exact
           scoring of the best upper-bound block rows + light tails);
        2. MaxScore split: terms whose max scores cumulatively stay below
           θ are non-essential — their postings alone can't lift a doc
           into the top-k, so candidates come from essential terms only;
        3. block-max pruning: essential heavy terms drop whole 128-doc
           blocks whose own upper bound plus the OTHER terms' maxscore sum
           cannot reach θ;
        4. exact scoring of the surviving candidates over all terms.

        Exact top-k: every dropped doc is provably below θ ≤ true k-th
        score. Falls back to exhaustive scoring when no safe θ exists.
        Conjunctions (require_all=N) intersect postings first — WAND is a
        disjunction optimization (reference: conjunction.hpp is a
        separate, already-selective iterator)."""
        store = self._device_store()
        fi = self.index
        avgdl = max(avgdl_override if avgdl_override is not None
                    else fi.avgdl, 1e-9)
        if require_all > 0:
            docs = self._conjunction_docs(tids) if len(tids) else \
                np.empty(0, dtype=np.int32)
            return self._cpu_score(docs, tids, k, scorer, idf_of,
                                   avgdl_override)
        plan = None
        if scorer not in bm25_ops.LM_SCORERS:
            plan = self._wand_plan_cached(store, tids, min(k, max(
                self.num_docs, 1)), avgdl, scorer, idf_of)
        if plan is None:
            # no safe threshold (tiny result set / LM scorer): exhaustive
            docs = self._union_postings([int(t) for t in tids])
            return self._cpu_score(docs, tids, k, scorer, idf_of,
                                   avgdl_override)
        theta = plan.theta
        non_ess = _maxscore_split(plan)
        ess = [int(t) for t in tids if int(t) not in non_ess]
        if not ess:
            ess = [int(t) for t in tids]
        parts = []
        for tid in ess:
            if store.heavy[tid] and tid in plan.kept:
                # block-max pruning: plan.kept already dropped rows that
                # can't reach θ together with the other terms' bounds
                s = int(store.offsets[tid])
                b0 = int(store.block_offsets[tid])
                e = int(store.offsets[tid + 1])
                loc = plan.kept[tid] - b0
                if len(loc) == 0:
                    continue
                spans = [store.flat_docs[s + i * bm25_ops.BLOCK:
                                         min(s + (i + 1) * bm25_ops.BLOCK, e)]
                         for i in loc]
                parts.append(np.concatenate(spans))
            else:
                pd = fi.postings(tid)[0]
                parts.append(pd)
        cand = np.unique(np.concatenate(parts)) if parts \
            else np.empty(0, dtype=np.int32)
        scores, dd = self._cpu_score(cand, tids, k, scorer, idf_of,
                                     avgdl_override)
        keep = scores > 0.0
        return scores[keep][:k], dd[keep][:k]

    def _cpu_score(self, docs: np.ndarray, tids: list[int], k: int,
                   scorer: str = "bm25", idf_of=None,
                   avgdl_override=None) -> tuple[np.ndarray, np.ndarray]:
        scores = np.zeros(len(docs), dtype=np.float64)
        tid_arr = np.asarray(tids, dtype=np.int64)
        if idf_of is not None:
            idf = idf_of(tid_arr)
        elif scorer in bm25_ops.LM_SCORERS:
            idf = bm25_ops.term_weight_for(
                scorer, self.num_docs, None, self.index.ctf[tid_arr],
                float(self.index.total_tokens))
        else:
            idf = bm25_ops.idf_for(scorer, self.num_docs,
                                   self.index.doc_freq[tid_arr])
        dl = self.index.norms[docs].astype(np.float64)
        avgdl = max(avgdl_override if avgdl_override is not None
                    else self.index.avgdl, 1e-9)
        for qi, tid in enumerate(tids):
            pd, pt = self.index.postings(tid)
            ix = np.searchsorted(pd, docs)
            ix = np.clip(ix, 0, max(len(pd) - 1, 0))
            hit = (len(pd) > 0) & (pd[ix] == docs)
            tf = np.where(hit, pt[np.clip(ix, 0, max(len(pd) - 1, 0))],
                          0).astype(np.float64)
            w = float(idf[qi])
            if scorer == "tfidf":
                scores += w * np.sqrt(tf)
            elif scorer == "lm_dirichlet":
                mu = bm25_ops.LM_MU
                c = np.log1p(tf / (mu * w)) + np.log(mu / (dl + mu))
                scores += np.where(
                    tf > 0, np.maximum(c, 0.0) + bm25_ops.MATCH_EPS, 0.0)
            elif scorer == "jelinek_mercer":
                lam = bm25_ops.JM_LAMBDA
                scores += np.log1p(((1 - lam) * tf / np.maximum(dl, 1.0)) /
                                   (lam * w))
            elif scorer == "dfi":
                e = w * dl
                excess = (tf - e) / np.sqrt(np.maximum(e, 1e-9))
                scores += np.where(
                    tf > 0,
                    np.where(tf > e, np.log2(1.0 + excess), 0.0) +
                    bm25_ops.MATCH_EPS, 0.0)
            else:
                denom = tf + K1 * (1 - B + B * dl / avgdl)
                scores += w * (K1 + 1) * tf / np.maximum(denom, 1e-9)
        order = np.argsort(-scores, kind="stable")[:k]
        return (scores[order].astype(np.float32),
                docs[order].astype(np.int32))


def _run_segment_shards(run_segment, segments: list, cap: int) -> list:
    """Drive the per-segment collectors, one result per segment in
    SEGMENT ORDER. With `serene_shards` > 1 the segment set partitions
    round-robin into per-shard groups (exec/shard.py's partitioning
    function) and each shard's group runs as ONE pool task — the
    sharded-tier unit of work — otherwise each segment is its own task.
    Either way the caller's single-heap merge consumes the identical
    per-segment outputs, so results are bit-identical at any shard or
    worker count."""
    from ..exec import shard as shard_mod
    from ..parallel.pool import get_pool
    if cap <= 1 or len(segments) <= 1:
        return [run_segment(sb) for sb in segments]
    n_shards = shard_mod.shard_count(None)
    if n_shards > 1:
        groups = shard_mod.group_round_robin(
            list(enumerate(segments)), n_shards)

        def run_group(entries):
            return [(i, run_segment(sb)) for i, sb in entries]

        parts = shard_mod.run_shard_tasks(None, run_group, groups)
        outs: list = [None] * len(segments)
        for chunk in parts:
            for i, out in chunk:
                outs[i] = out
        return outs
    return get_pool().ensure_started().map_ordered(
        run_segment, list(segments), cap)


def merge_segment_topk(seg_outs: list, bases: list[int], n_queries: int,
                       k: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Single-heap merge of per-segment top-k collector outputs.

    seg_outs[si][qi] = (scores, local doc ids) for segment si. Ordering
    is (score desc, global doc id asc) — the doc-id tie-break makes the
    merged ranking a pure function of the data, independent of segment
    count, arrival order, or worker scheduling."""
    import heapq
    results = []
    for qi in range(n_queries):
        entries: list[tuple[float, int]] = []
        for out, base in zip(seg_outs, bases):
            sc, dd = out[qi]
            entries.extend(zip(sc.tolist(),
                               (dd.astype(np.int64) + base).tolist()))
        cand = heapq.nlargest(k, entries, key=lambda t: (t[0], -t[1]))
        results.append((
            np.asarray([c[0] for c in cand], dtype=np.float32),
            np.asarray([c[1] for c in cand], dtype=np.int64)))
    return results


def _combine_topk(seg_outs: list, bases: list[int], n_queries: int,
                  k: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Cross-segment top-k combine dispatcher: the host single-heap
    merge (the parity oracle), or — when the sharded tier is active
    with `serene_shard_combine` resolving to device — an IN-PROGRAM
    merge: each shard's candidate set reduces with an exact per-shard
    top-k inside one shard_map program and the shards meet in a single
    `all_gather` hop (exec/shard.py's round-robin segment grouping).
    Selection is a pure (score desc, doc asc) order on the candidate
    union, so both combines pick the identical entries in the identical
    order — bit-identity by construction, asserted by the
    tests/test_multichip.py parity matrix."""
    from ..exec import shard as shard_mod
    if len(seg_outs) > 1 and k > 0 and n_queries > 0:
        n_shards = shard_mod.shard_count(None)
        if n_shards > 1 and shard_mod.combine_mode(None) == "device":
            out = _device_merge_topk(seg_outs, bases, n_queries, k,
                                     n_shards)
            if out is not None:
                return out
    return merge_segment_topk(seg_outs, bases, n_queries, k)


# compiled shard_map merge programs live in the obs/device compile
# ledger keyed by (padded candidate width, padded k, padded query
# count, mesh width) — pow2 padding keeps the compile-shape population
# bounded under varied query mixes, the ledger LRU bounds it hard

#: padding doc sentinel: sorts after every real doc at equal score and
#: is trimmed host-side; real global doc ids must stay below it
_PAD_DOC = (1 << 31) - 1


def _merge_program(mesh, lp: int, kp: int, qp: int):
    import functools

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS
    m_width = mesh.shape[AXIS]
    key = (lp, kp, qp, m_width)
    kcut = min(kp, lp)

    def srt(kk, dd, ss):
        return jax.lax.sort((kk, dd, ss), num_keys=2)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS, None, None), P(AXIS, None, None)),
        out_specs=(P(), P()), check_vma=False)
    def step(sc, dc):
        # per-(shard, query) exact top-k: lexicographic two-key sort on
        # (score desc, doc asc). `+ 0.0` canonicalizes -0.0 so equal
        # scores tie exactly like the host heap's float compare; the
        # original score bits travel as a passenger operand. The whole
        # query batch merges in THIS one dispatch (vmap over the query
        # axis), the many-queries-per-dispatch discipline of the
        # batched serving tier.
        keys = -(sc + 0.0)
        k2, d2, s2 = jax.vmap(jax.vmap(srt))(keys, dc, sc)
        k2, d2, s2 = (k2[:, :, :kcut], d2[:, :, :kcut], s2[:, :, :kcut])
        # ONE all_gather hop: every device sees every shard's top-k
        k2 = jax.lax.all_gather(k2, AXIS, tiled=True)
        d2 = jax.lax.all_gather(d2, AXIS, tiled=True)
        s2 = jax.lax.all_gather(s2, AXIS, tiled=True)
        # (S, Q, kcut) → per query one final exact selection
        k2 = jnp.moveaxis(k2, 0, 1).reshape(qp, -1)
        d2 = jnp.moveaxis(d2, 0, 1).reshape(qp, -1)
        s2 = jnp.moveaxis(s2, 0, 1).reshape(qp, -1)
        _, dfin, sfin = jax.vmap(srt)(k2, d2, s2)
        return sfin[:, :kp], dfin[:, :kp]

    from ..obs import device as obs_device
    return obs_device.compiled("search_merge", key, lambda: step)


def _device_merge_topk(seg_outs: list, bases: list[int], n_queries: int,
                       k: int, n_shards: int):
    """In-program sharded top-k merge — the WHOLE query batch in one
    collective dispatch (queries stack on a vmapped axis, pow2-padded);
    None → caller falls back to the host heap (doc ids past int32, NaN
    scores, degenerate grouping, no candidates at all)."""
    import time

    import jax

    from ..exec import shard as shard_mod
    from ..obs.trace import current_trace
    from ..parallel import mesh as mesh_mod
    from ..utils import metrics

    groups = shard_mod.group_round_robin(
        list(range(len(seg_outs))), n_shards)
    if len(groups) <= 1:
        return None
    # admission: every global doc id must fit below the int32 padding
    # sentinel, and scores must be NaN-free (NaN breaks the sort/heap
    # order equivalence)
    for out, base in zip(seg_outs, bases):
        for sc, dd in out:
            if len(dd) and int(np.asarray(dd).max()) + base >= _PAD_DOC:
                return None
            if len(sc) and np.isnan(np.asarray(sc)).any():
                return None
    S = len(groups)
    mesh = mesh_mod.data_mesh(S)
    m_width = mesh.shape[mesh_mod.AXIS]
    s_pad = -(-S // m_width) * m_width
    # per-(shard, query) candidate lists, one shared padded width
    cands: list[list[tuple[np.ndarray, np.ndarray]]] = []
    lmax = 0
    for idxs in groups:
        row = []
        for qi in range(n_queries):
            sc = np.concatenate(
                [np.asarray(seg_outs[si][qi][0], dtype=np.float32)
                 for si in idxs])
            dd = np.concatenate(
                [np.asarray(seg_outs[si][qi][1]).astype(np.int64) +
                 bases[si] for si in idxs])
            lmax = max(lmax, len(sc))
            row.append((sc, dd))
        cands.append(row)
    if lmax == 0:
        return [(np.empty(0, dtype=np.float32),
                 np.empty(0, dtype=np.int64))] * n_queries
    lp = 1 << (lmax - 1).bit_length()
    kp = 1 << (max(k, 1) - 1).bit_length()
    qp = 1 << (max(n_queries, 1) - 1).bit_length()
    scores = np.full((s_pad, qp, lp), -np.inf, dtype=np.float32)
    docs = np.full((s_pad, qp, lp), _PAD_DOC, dtype=np.int32)
    for i, row in enumerate(cands):
        for qi, (sc, dd) in enumerate(row):
            scores[i, qi, :len(sc)] = sc
            docs[i, qi, :len(dd)] = dd.astype(np.int32)
    jitted = _merge_program(mesh, lp, kp, qp)
    sh = mesh_mod.data_sharding(mesh, 3)
    t_d = time.perf_counter_ns()
    metrics.DEVICE_OFFLOADS.add()
    metrics.COLLECTIVE_DISPATCHES.add()
    from ..obs import device as obs_device
    from ..obs.resources import wait_scope
    with wait_scope("Device", "CollectiveCombine"):
        # the candidate planes bypass DEVICE_CACHE (per-dispatch data):
        # commit() keeps their transfer bytes in the device ledger
        ss, dd2 = obs_device.fetch_all(
            jitted(obs_device.commit(scores, sh),
                   obs_device.commit(docs, sh)))
    dt = time.perf_counter_ns() - t_d
    metrics.COLLECTIVE_COMBINE_NS.add(dt)
    metrics.DEVICE_DISPATCH_HIST.observe_ns(dt)
    trace = current_trace()
    if trace is not None:
        trace.add("collective_dispatch", "device", t_d,
                  time.perf_counter_ns(), shards=S, op="topk_merge",
                  queries=n_queries)
    results = []
    for qi in range(n_queries):
        sq, dq = ss[qi][:k], dd2[qi][:k]
        real = dq != _PAD_DOC
        results.append((sq[real].astype(np.float32),
                        dq[real].astype(np.int64)))
    return results


class MultiSearcher:
    """Searches across immutable segments of one column (reference:
    DirectoryReader over segment readers, SURVEY.md §2.7). Doc ids are
    global row indices (segment base + local id); scoring uses GLOBAL
    collection statistics so multi-segment scores equal a single-segment
    build of the same data."""

    def __init__(self, analyzer: Analyzer):
        self.analyzer = analyzer
        self.segments: list[tuple[SegmentSearcher, int]] = []  # (seg, base)
        # (a column's validity array, its doc bitset per segment)
        self._valid_bits: Optional[tuple] = None

    def add_segment(self, searcher: SegmentSearcher, base_row: int):
        self.segments.append((searcher, base_row))

    @property
    def num_docs(self) -> int:
        return sum(s.num_docs for s, _ in self.segments)

    @property
    def global_avgdl(self) -> float:
        total_tokens = sum(s.index.total_tokens for s, _ in self.segments)
        n = self.num_docs
        return (total_tokens / n) if n else 0.0

    def _global_df(self, term: str) -> int:
        df = 0
        for s, _ in self.segments:
            tid = s.index.term_id(term)
            if tid >= 0:
                df += int(s.index.doc_freq[tid])
        return df

    def _global_ctf(self, term: str) -> int:
        ctf = 0
        for s, _ in self.segments:
            tid = s.index.term_id(term)
            if tid >= 0:
                ctf += int(s.index.ctf[tid])
        return ctf

    def eval_filter(self, node: QNode) -> np.ndarray:
        parts = []
        for s, base in self.segments:
            local = s.eval_filter(node)
            if len(local):
                parts.append(local.astype(np.int64) + base)
        return np.concatenate(parts).astype(np.int64) if parts \
            else np.empty(0, dtype=np.int64)

    def count_filter(self, node: QNode,
                     validity: Optional[np.ndarray] = None) -> int:
        """`len(eval_filter(node))` over the rows `validity` (the
        column's, by global row; None: every row) does not mark NULL:
        the sum of the segments' counts, their doc spaces being
        disjoint. The validity is packed into per-segment doc bitsets
        once per validity array (a pinned column keeps its own)."""
        bits = [None] * len(self.segments)
        if validity is not None:
            cached = self._valid_bits
            if cached is None or cached[0] is not validity:
                cached = self._valid_bits = (validity, [
                    _bits_of(validity[base:base + s.num_docs])
                    for s, base in self.segments])
            bits = cached[1]
        return sum(s.count_filter(node, b)
                   for (s, _), b in zip(self.segments, bits))

    def topk(self, node: QNode, k: int, scorer: str = "bm25",
             mesh_n: int = 0) -> tuple[np.ndarray, np.ndarray]:
        return self.topk_batch([node], k, scorer, mesh_n=mesh_n)[0]

    def topk_batch(self, nodes: list[QNode], k: int, scorer: str = "bm25",
                   mesh_n: int = 0,
                   ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Fragments memoize PER QUERY (cache/fragments.cached_batch): a
        coalesced batch probes each member's own (sig, k, scorer) key, the
        misses score together in one segment dispatch, and each result
        stores back under its own key — so a fragment computed inside any
        batch serves the same query arriving alone later and vice versa
        (sound because per-query results are batch-composition-independent,
        the serving parity contract, and the reason serene_search_batch
        stays out of the result cache's settings digest)."""
        from ..cache.fragments import FRAGMENTS, qnode_sig
        sigs = [qnode_sig(n) for n in nodes]
        # where each query's top-k was scored, over its segments: "device"
        # if a device program scored it in any, "host" if only host tiers
        # did, None if every segment's fragment was cached
        scored: list = [None] * len(nodes)
        scored_lock = threading.Lock()    # segments score on pool threads

        def score_segment(seg, idxs, **kw):
            tiers = [None] * len(idxs)
            out = seg.topk_batch([nodes[i] for i in idxs], k, scorer,
                                 mesh_n=mesh_n, tiers=tiers, **kw)
            with scored_lock:
                for i, tier in zip(idxs, tiers):
                    if scored[i] != "device":
                        scored[i] = tier
            return out

        def count_scored():
            metrics.SEARCH_QUERIES_SCORED_DEVICE.add(
                sum(t == "device" for t in scored))
            metrics.SEARCH_QUERIES_SCORED_HOST.add(
                sum(t == "host" for t in scored))

        if len(self.segments) == 1:
            seg, base = self.segments[0]
            # single segment: local stats ARE the global stats — the
            # fragment is a pure function of the segment alone
            shapes = [None if s is None else ("topk1", s, k, scorer, mesh_n)
                      for s in sigs]
            out = FRAGMENTS.cached_batch(
                seg, shapes, lambda idxs: score_segment(seg, idxs))
            count_scored()
            return [(s, d.astype(np.int64) + base) for s, d in out]
        idf_factory = self._segment_idf_factory(nodes, scorer)
        avgdl = self.global_avgdl
        # a segment's scored output depends on GLOBAL collection stats
        # (idf/avgdl span every segment), which are a pure function of
        # the segment SET — key the whole membership, so an append
        # recomputes scores exactly as correctness requires while
        # filter fragments (above) survive it
        segset = tuple(FRAGMENTS.segment_uid(s) for s, _ in self.segments)

        def run_segment(seg_base):
            seg, _base = seg_base
            shapes = [None if s is None else ("topk", s, k, scorer, mesh_n,
                                              segset) for s in sigs]
            return FRAGMENTS.cached_batch(
                seg, shapes,
                lambda idxs: score_segment(seg, idxs,
                                           idf_of=idf_factory(seg),
                                           avgdl_override=avgdl))

        # segments are independent top-k collectors: search them on the
        # shared worker pool (reference: parallel scored collectors over
        # the search thread pool). With a device mesh active the mesh IS
        # the parallelism — keep the segment loop serial then.
        from ..parallel.pool import get_pool, session_workers
        cap = 1 if mesh_n > 1 else session_workers(None)
        seg_outs = _run_segment_shards(run_segment, self.segments, cap)
        count_scored()
        return _combine_topk(seg_outs,
                             [b for _, b in self.segments],
                             len(nodes), k)

    def prebuild(self) -> int:
        """Every segment's posting store uploaded and its scoring
        programs built (`SegmentSearcher.prebuild`), under the collection
        statistics a search will score with: what an index build or
        refresh calls before it publishes this searcher."""
        avgdl = None if len(self.segments) == 1 else self.global_avgdl
        return sum(seg.prebuild(avgdl) for seg, _ in self.segments)

    def probe_topk(self, node: QNode, k: int, scorer: str = "bm25",
                   mesh_n: int = 0,
                   ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        """Pure fragment-cache probe: the merged top-k iff EVERY segment's
        fragment for this query is already cached, else None — no scoring,
        no stores. The batcher consults this BEFORE enqueueing so cache
        hits never wait out a coalescing window or occupy a batch slot.
        Hit gauges bump only on full success; partial probes stay silent
        (the batch dispatch re-probes those segments and counts them
        once)."""
        from ..cache.fragments import FRAGMENTS, enabled, qnode_sig
        if not enabled() or not self.segments:
            return None
        sig = qnode_sig(node)
        if sig is None:
            return None
        if len(self.segments) == 1:
            seg, base = self.segments[0]
            hit = FRAGMENTS.probe(seg, ("topk1", sig, k, scorer, mesh_n))
            if hit is None:
                return None
            FRAGMENTS.count_hits(1)
            s, d = hit
            return s, d.astype(np.int64) + base
        segset = tuple(FRAGMENTS.segment_uid(s) for s, _ in self.segments)
        outs = []
        for seg, _base in self.segments:
            hit = FRAGMENTS.probe(seg, ("topk", sig, k, scorer, mesh_n,
                                        segset))
            if hit is None:
                return None
            outs.append([hit])
        FRAGMENTS.count_hits(len(self.segments))
        return merge_segment_topk(outs, [b for _, b in self.segments],
                                  1, k)[0]

    def _segment_idf_factory(self, nodes: list[QNode], scorer: str):
        """seg → idf_of closure over GLOBAL collection stats. One pass:
        global df per query term STRING (terms have different ids per
        segment), shared by every segment's closure."""
        n_total = max(self.num_docs, 1)
        term_strings: set[str] = set()
        for node in nodes:
            for seg, _ in self.segments:
                ts = seg.index.terms_str
                term_strings.update(str(ts[t])
                                    for t in seg.scoring_terms(node))
        global_df = {s: self._global_df(s) for s in term_strings}
        lm = scorer in bm25_ops.LM_SCORERS
        global_ctf = ({s: self._global_ctf(s) for s in term_strings}
                      if lm else {})
        total_tokens = (float(sum(s.index.total_tokens
                                  for s, _ in self.segments)) if lm else 0.0)

        def factory(seg):
            terms_str = seg.index.terms_str

            def idf_of(tids, _ts=terms_str):
                if lm:
                    ctfs = np.asarray(
                        [global_ctf[str(_ts[t])] for t in tids],
                        dtype=np.int64)
                    return bm25_ops.term_weight_for(
                        scorer, n_total, None, ctfs, total_tokens)
                dfs = np.asarray([global_df[str(_ts[t])] for t in tids],
                                 dtype=np.int64)
                return bm25_ops.idf_for(scorer, n_total, dfs)

            return idf_of
        return factory

    def cpu_topk(self, node: QNode, k: int, scorer: str = "bm25",
                 ) -> tuple[np.ndarray, np.ndarray]:
        """Host-only top-k: block-max WAND per segment on the worker
        pool, merged by one heap — the multi-segment analog of
        SegmentSearcher.cpu_topk_wand (reference: ScanMode::TopK parallel
        scored collectors). Exact-match-mask shapes score their match set
        directly; pure negations return zero-scored matches."""
        idf_factory = self._segment_idf_factory([node], scorer)
        avgdl = self.global_avgdl
        from ..cache.fragments import FRAGMENTS, qnode_sig
        sig = qnode_sig(node)
        segset = tuple(FRAGMENTS.segment_uid(s) for s, _ in self.segments)

        def run_segment(seg_base):
            seg, _base = seg_base

            def compute():
                idf_of = idf_factory(seg)
                tids, req, needs_mask, empty = seg._query_shape(node)
                if empty:
                    return (np.empty(0, dtype=np.float32),
                            np.empty(0, dtype=np.int32))
                if not tids:
                    match = seg.eval_filter(node)[:k]
                    return (np.zeros(len(match), dtype=np.float32),
                            match.astype(np.int32))
                if needs_mask:
                    match = seg.eval_filter(node)
                    sc, dd = seg._cpu_score(match, tids, k, scorer,
                                            idf_of, avgdl)
                    keep = sc > 0.0
                    return (sc[keep][:k], dd[keep][:k])
                return seg.cpu_topk_wand(tids, k, scorer, idf_of=idf_of,
                                         avgdl_override=avgdl,
                                         require_all=req)

            shape = None if sig is None else ("wand", sig, k, scorer,
                                              segset)
            return FRAGMENTS.cached(seg, shape, compute)

        from ..parallel.pool import session_workers
        cap = session_workers(None)
        outs = _run_segment_shards(run_segment, self.segments, cap)
        return _combine_topk([[o] for o in outs],
                             [b for _, b in self.segments], 1, k)[0]


@dataclass
class SearchIndex:
    """A built index over one or more text columns of a table provider.
    Each column holds a MultiSearcher over immutable segments; appends add
    segments (incremental refresh), row mutations force full rebuilds."""

    columns: list[str]
    using: str
    options: dict
    analyzer_name: str
    searchers: dict[str, MultiSearcher]   # column → multi-segment searcher
    data_version: int
    mutation_epoch: int = 0
    indexed_rows: int = 0

    def searcher(self, column: str) -> Optional[MultiSearcher]:
        return self.searchers.get(column)

    def analyzer_name_for(self, column: str) -> str:
        """The column's own tokenizer (multi-column indexes may configure
        one per column — reference: USING inverted(text imdb_en, label))."""
        col_toks = (self.options or {}).get("column_tokenizers", {}) or {}
        return col_toks.get(column, self.analyzer_name)
