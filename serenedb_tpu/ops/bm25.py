"""BM25 block scoring + top-k on device — the search-side flagship kernel.

Reference analog: the hot loop of SURVEY.md §3.3 — block_disjunction over
block_128 postings with BM25 ScoreFunction per 128-doc block and WAND
block-max skipping (libs/iresearch/search/bm25.hpp, block_disjunction.hpp,
formats/posting/wand_writer.hpp).

TPU re-formulation (zero per-query posting transfers):

- At index-build time, postings of *heavy* terms (df ≥ HEAVY_DF) are packed
  into device-resident (n_blocks, 128) doc / tf / doc-length tiles — the
  block_128 layout is exactly one TPU lane row. Light terms stay in the
  flat arrays.
- A query ships only: the block-row indices of its heavy terms (a few KB),
  a gathered tail array for its light terms, and per-term idf weights.
- One fused XLA program gathers the tiles, computes BM25 contributions,
  scatter-adds into a dense per-doc accumulator, and takes top-k.

Block-max pruning re-enters as *masking* (drop block rows whose upper bound
can't reach a threshold) rather than branching; the dense pass is exact.

Scoring follows the Lucene/IResearch BM25 ("k1=1.2, b=0.75", reference
bm25.hpp:30-80): idf = ln(1 + (N - df + 0.5)/(df + 0.5)),
score = Σ_t idf_t · (k1 + 1) · tf/(tf + k1·(1 − b + b·dl/avgdl)).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import device as obs_device
from ..obs.trace import stage

BLOCK = 128
HEAVY_DF = 32     # terms with at least this many postings get block tiles


def idf_lucene(n_docs: int, doc_freq: np.ndarray) -> np.ndarray:
    df = doc_freq.astype(np.float64)
    return np.log(1.0 + (n_docs - df + 0.5) / (df + 0.5)).astype(np.float32)


def idf_tfidf(n_docs: int, doc_freq: np.ndarray) -> np.ndarray:
    """IResearch TFIDF idf: 1 + ln(N / (df + 1)) (reference: tfidf.cpp)."""
    df = doc_freq.astype(np.float64)
    return (1.0 + np.log(max(n_docs, 1) / (df + 1.0))).astype(np.float32)


def idf_for(scorer: str, n_docs: int, doc_freq: np.ndarray) -> np.ndarray:
    return idf_tfidf(n_docs, doc_freq) if scorer == "tfidf" \
        else idf_lucene(n_docs, doc_freq)


# language-model scorer family (reference: libs/iresearch/search/
# lm_dirichlet.cpp, jelinek_mercer.cpp, dfi.cpp). Their per-term weight is
# the collection probability p_t = ctf_t / total_tokens, not an idf; the
# hyper-parameter (µ or λ) rides the k1 float slot of the shared kernel.
LM_SCORERS = ("lm_dirichlet", "jelinek_mercer", "dfi")
LM_MU = 2000.0     # Dirichlet µ (Lucene LMDirichletSimilarity default)
JM_LAMBDA = 0.1    # Jelinek-Mercer λ (short-query default)
#: per-matched-posting score floor: lm_dirichlet/dfi legitimately score 0
#: on weak matches, but downstream keep-filters use score>0 ⇔ matched.
#: Far below score resolution, so ranking is unchanged.
MATCH_EPS = 1e-6


def scorer_param(scorer: str, k1: float) -> float:
    """The value carried in the kernel's k1 slot for this scorer."""
    if scorer == "lm_dirichlet":
        return LM_MU
    if scorer == "jelinek_mercer":
        return JM_LAMBDA
    return k1


def term_weight_for(scorer: str, n_docs: int, doc_freq: np.ndarray,
                    ctf: Optional[np.ndarray] = None,
                    total_tokens: float = 0.0) -> np.ndarray:
    """Per-term weight: idf for bm25/tfidf, collection probability p_t for
    the LM family."""
    if scorer in LM_SCORERS:
        total = max(float(total_tokens), 1.0)
        p = np.asarray(ctf, dtype=np.float64) / total
        return np.maximum(p, 1e-12).astype(np.float32)
    return idf_for(scorer, n_docs, doc_freq)


@dataclass
class BlockStore:
    """Device-resident posting tiles for one field index.

    HBM layout (the reference's block_128 bitpacked format re-expressed for
    TPU lanes, formats/posting/format_block_128.cpp): each 128-posting row
    of a heavy term is COMPRESSED as one int32 base doc + 128 uint16
    doc-gaps + 128 uint8 tfs + 128 uint16 document lengths (5 B a
    posting slot and 4 B a row, against 12 B raw) and decoded INSIDE the
    scoring kernel (cumsum along the lane axis — a log-step scan the
    VPU handles without leaving registers). Rows that don't fit (a doc
    gap or a length ≥ 2^16, or a tf ≥ 2^8) stay in a small raw int32
    exception plane, mirroring streamvbyte's escape path.

    Every posting slot carries its document's length (`block_dls`,
    `raw_dls`: the same integers as `norms[doc]`, lane for lane with the
    tfs), so the accumulate step reads a length as it reads a tf, by
    row, and gathers nothing per element; `norms` is read only by
    `_build_dense`, linearly."""

    block_base: jax.Array      # (NP+1,) int32 — first doc of each packed row
    block_gaps: jax.Array      # (NP+1, 128) uint16 — doc deltas, slot0 = 0
    block_tfs8: jax.Array      # (NP+1, 128) uint8 — tf, 0 marks padding
    block_dls: jax.Array       # (NP+1, 128) uint16 — doc length, padding 0
    raw_docs: jax.Array        # (NR+1, 128) int32, -1 padding
    raw_tfs: jax.Array         # (NR+1, 128) int32
    raw_dls: jax.Array         # (NR+1, 128) int32 — doc length, padding 0
    norms: jax.Array           # (ndocs_pad,) int32 — `_build_dense` only
    block_offsets: np.ndarray  # (T+1,) int64 — heavy terms' GLOBAL row spans
    heavy: np.ndarray          # (T,) bool
    flat_docs: np.ndarray      # host copies for the light-term tail
    flat_tfs: np.ndarray
    offsets: np.ndarray
    ndocs_pad: int
    pad_row: int               # GLOBAL index of the all-padding block row
    row_plane: np.ndarray      # (NB_total+1,) uint8 — 0 packed, 1 raw
    row_slot: np.ndarray       # (NB_total+1,) int32 — index within plane
    n_packed: int              # NP (packed pad slot = NP)
    n_raw: int                 # NR (raw pad slot = NR)
    # block-max (WAND) metadata, host-resident: per heavy block row the max
    # tf and min doc length — a score upper bound valid for any avgdl
    # (reference: formats/posting/wand_writer.hpp impact pairs)
    block_bmax_tf: np.ndarray = None   # (NB_total+1,) int32
    block_bmin_dl: np.ndarray = None   # (NB_total+1,) int32
    norms_host: np.ndarray = None      # (num_docs,) int32
    # valid (non-padding) postings per heavy block row, host-resident:
    # what SearchPostingsDispatched counts for the rows handed to a
    # scoring program
    row_count: np.ndarray = None       # (NB_total+1,) int32

    @property
    def tiles(self) -> tuple:
        """The device arrays a scoring program takes, in its order."""
        return (self.block_base, self.block_gaps, self.block_tfs8,
                self.block_dls, self.raw_docs, self.raw_tfs, self.raw_dls)

    @property
    def hbm_bytes(self) -> int:
        """Posting-tile HBM footprint, the per-posting lengths among it
        (the `norms` table, 4 B a document, is not a tile and not
        counted)."""
        return sum(a.nbytes for a in self.tiles)

    @property
    def length_bytes(self) -> int:
        """HBM held by the per-posting document lengths."""
        return self.block_dls.nbytes + self.raw_dls.nbytes

    @property
    def hbm_bytes_raw_equiv(self) -> int:
        """What the same rows would cost as raw int32 doc+tf+length
        tiles."""
        n_rows = len(self.row_plane)
        return n_rows * BLOCK * 12


def _bucket(n: int, floor: int, align: int = 1) -> int:
    """n (at least `floor`) rounded up to a sixteenth of its octave and
    to `align`: arrays whose length follows the data come in a few sizes
    per octave, so the scoring programs, which XLA compiles per shape,
    are shared by stores of like size — for at most an eighth more
    memory."""
    n = max(int(n), floor)
    g = max(1 << max(n.bit_length() - 4, 0), align)
    return -(-n // g) * g


def build_block_store(offsets: np.ndarray, post_docs: np.ndarray,
                      post_tfs: np.ndarray, doc_freq: np.ndarray,
                      norms: np.ndarray, num_docs: int) -> BlockStore:
    T = len(doc_freq)
    heavy = doc_freq >= HEAVY_DF
    nb_per = np.where(heavy, -(-doc_freq.astype(np.int64) // BLOCK), 0)
    block_offsets = np.zeros(T + 1, dtype=np.int64)
    np.cumsum(nb_per, out=block_offsets[1:])
    nb_total = int(block_offsets[-1])
    norms_h = np.ascontiguousarray(norms[:num_docs], dtype=np.int32)

    # Vectorized tile assembly: scatter every heavy posting into its
    # (row, lane) slot, -1/0 padding elsewhere.
    bdocs = np.full((nb_total + 1, BLOCK), -1, dtype=np.int32)
    btfs = np.zeros((nb_total + 1, BLOCK), dtype=np.int32)
    heavy_tids = np.flatnonzero(heavy)
    if len(heavy_tids):
        df_h = doc_freq[heavy_tids].astype(np.int64)
        pt = np.repeat(heavy_tids, df_h)                # term of each posting
        within = np.arange(len(pt), dtype=np.int64) - \
            np.repeat(np.cumsum(df_h) - df_h, df_h)     # rank within term
        src = np.repeat(offsets[heavy_tids], df_h) + within
        grow = np.repeat(block_offsets[heavy_tids], df_h) + within // BLOCK
        lane = within % BLOCK
        bdocs[grow, lane] = post_docs[src]
        btfs[grow, lane] = post_tfs[src]
    bmax_tf = btfs.max(axis=1).astype(np.int32)
    row_count = np.count_nonzero(btfs, axis=1).astype(np.int32)
    # every slot's document length, lane for lane with the tfs: what the
    # accumulate step reads in place of norms[doc]. bmin_dl in place, no
    # second full-size temporary: pads to int32-max for the min, then 0
    bdls = norms_h[np.clip(bdocs, 0, None)] if num_docs \
        else np.zeros_like(bdocs)
    pad = bdocs < 0
    np.putmask(bdls, pad, np.iinfo(np.int32).max)
    bmin_dl = bdls.min(axis=1).astype(np.int32)
    np.putmask(bdls, pad, 0)
    del pad
    bmin_dl[-1] = np.iinfo(np.int32).max   # all-pad row

    # Pack: forward-fill pads with the last real doc so gaps stay small,
    # then delta-encode along the lane axis (in place — beside the three
    # tiles the build holds one full-size temporary at a time; tiles
    # reach GBs at 8M documents).
    docs_ff = np.maximum.accumulate(bdocs, axis=1)
    base = docs_ff[:, 0].copy()
    docs_ff[:, 1:] = docs_ff[:, 1:] - docs_ff[:, :-1]
    docs_ff[:, 0] = 0
    gaps = docs_ff                      # reuse: docs_ff IS the gap array now
    packable = ((gaps.max(axis=1) < (1 << 16)) &
                (bdls.max(axis=1) < (1 << 16)) &
                (bmax_tf < (1 << 8)) & (base >= 0))
    packable[-1] = False     # keep the global pad row in the raw plane
    row_plane = np.where(packable, 0, 1).astype(np.uint8)
    row_slot = np.zeros(nb_total + 1, dtype=np.int32)
    row_slot[packable] = np.arange(int(packable.sum()), dtype=np.int32)
    row_slot[~packable] = np.arange(int((~packable).sum()), dtype=np.int32)
    n_packed = int(packable.sum())
    n_raw = int((~packable).sum())

    # both planes are allocated in bucketed sizes (rows past the pad slot
    # are all-padding too), so stores of like size share their programs
    np_rows = _bucket(n_packed + 1, 1)
    nr_rows = _bucket(n_raw + 1, 1)
    pk_base = np.zeros(np_rows, dtype=np.int32)
    pk_gaps = np.zeros((np_rows, BLOCK), dtype=np.uint16)
    pk_tfs = np.zeros((np_rows, BLOCK), dtype=np.uint8)
    pk_base[:n_packed] = base[packable]
    pk_gaps[:n_packed] = gaps[packable].astype(np.uint16)
    del gaps, docs_ff
    r_docs = np.full((nr_rows, BLOCK), -1, dtype=np.int32)
    r_tfs = np.zeros((nr_rows, BLOCK), dtype=np.int32)
    r_docs[:n_raw] = bdocs[~packable]
    del bdocs
    pk_tfs[:n_packed] = btfs[packable].astype(np.uint8)
    r_tfs[:n_raw] = btfs[~packable]
    del btfs
    pk_dls = np.zeros((np_rows, BLOCK), dtype=np.uint16)
    r_dls = np.zeros((nr_rows, BLOCK), dtype=np.int32)
    pk_dls[:n_packed] = bdls[packable].astype(np.uint16)
    r_dls[:n_raw] = bdls[~packable]
    del bdls

    nd_pad = _bucket(num_docs, 1024, 1024)
    norms_pad = np.zeros(nd_pad, dtype=np.int32)
    norms_pad[:num_docs] = norms[:num_docs]
    return BlockStore(
        block_base=jnp.asarray(pk_base),
        block_gaps=jnp.asarray(pk_gaps),
        block_tfs8=jnp.asarray(pk_tfs),
        block_dls=jnp.asarray(pk_dls),
        raw_docs=jnp.asarray(r_docs),
        raw_tfs=jnp.asarray(r_tfs),
        raw_dls=jnp.asarray(r_dls),
        norms=jnp.asarray(norms_pad),
        block_offsets=block_offsets,
        heavy=heavy,
        flat_docs=post_docs,
        flat_tfs=post_tfs,
        offsets=offsets,
        ndocs_pad=nd_pad,
        pad_row=nb_total,
        row_plane=row_plane,
        row_slot=row_slot,
        n_packed=n_packed,
        n_raw=n_raw,
        block_bmax_tf=bmax_tf,
        block_bmin_dl=bmin_dl,
        norms_host=norms_h,
        row_count=row_count,
    )


@dataclass
class QueryBatch:
    """Host-assembled inputs for one scoring dispatch covering B queries,
    at their own lengths: `query_chunks` cuts them into the fixed
    capacities of a rung. All arrays are tiny relative to the posting
    store (KBs per query). Heavy-term rows split across the two tile
    planes (packed / raw)."""

    row_idx: np.ndarray    # (NB,) int32 PACKED-plane row gather indices
    row_w: np.ndarray      # (NB,) f32 idf weight of the row's term
    row_qid: np.ndarray    # (NB,) int32 query index of the row
    raw_idx: np.ndarray    # (NR,) int32 RAW-plane row gather indices
    raw_w: np.ndarray      # (NR,) f32
    raw_qid: np.ndarray    # (NR,) int32
    tail_docs: np.ndarray  # (TT,) int32 light-term postings (docs)
    tail_tfs: np.ndarray   # (TT,) int32
    tail_dls: np.ndarray   # (TT,) int32 their documents' lengths
    tail_w: np.ndarray     # (TT,) f32
    tail_qid: np.ndarray   # (TT,) int32
    require: np.ndarray    # (B,) int32 — 0 = disjunction, else min hits
    n_queries: int         # B
    n_postings: int = 0    # valid postings in the rows and tails above


def _sat_exact(tfs: np.ndarray, dls: np.ndarray, k1: float, b: float,
               avg: float, scorer: str) -> np.ndarray:
    """Per-posting saturation term of the score (score = w · sat)."""
    tfs = tfs.astype(np.float64)
    if scorer == "tfidf":
        return np.sqrt(tfs)
    denom = tfs + k1 * (1.0 - b + b * dls.astype(np.float64) /
                        max(avg, 1e-9))
    return (k1 + 1.0) * tfs / np.maximum(denom, 1e-9)


def _sparse_table(arr: np.ndarray) -> np.ndarray:
    """Range-max sparse table: tab[j, i] = max(arr[i : i + 2^j])."""
    n = len(arr)
    levels = max(1, int(n).bit_length())
    tab = np.full((levels, n), -np.inf)
    tab[0] = arr
    for j in range(1, levels):
        half = 1 << (j - 1)
        m = n - (1 << j) + 1
        if m <= 0:
            break
        tab[j, :m] = np.maximum(tab[j - 1, :m], tab[j - 1, half:half + m])
    return tab


def _range_max(tab: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Vectorized max(arr[lo..hi]) (inclusive) over a sparse table."""
    length = (hi - lo + 1).astype(np.float64)
    j = np.floor(np.log2(np.maximum(length, 1.0))).astype(np.int64)
    j = np.minimum(j, tab.shape[0] - 1)
    left = tab[j, lo]
    right = tab[j, np.maximum(hi + 1 - (1 << j), 0)]
    return np.maximum(left, right)


def _bucket_tables(store: BlockStore, tid: int, avg: float, k1: float,
                   b: float, scorer: str, shift: int) -> np.ndarray:
    """Sparse range-max table of the term's per-doc-bucket max *sat* value
    (w-free; the caller scales by idf). Cached on the store — segments are
    immutable and avg is fixed per (segment, collection-stats) pair."""
    cache = getattr(store, "_bucket_cache", None)
    if cache is None:
        cache = store._bucket_cache = {}
    if len(cache) > 512:  # tables are up to ~1MB each — bound host RAM
        cache.clear()
    key = (tid, round(avg, 6), scorer, shift, k1, b)
    hit = cache.get(key)
    if hit is not None:
        return hit
    n_buckets = (store.ndocs_pad >> shift) + 1
    arr = np.zeros(n_buckets)
    s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
    if store.heavy[tid]:
        b0, b1 = int(store.block_offsets[tid]), int(store.block_offsets[tid + 1])
        r = np.arange(b0, b1, dtype=np.int64)
        sat = _sat_exact(store.block_bmax_tf[r], store.block_bmin_dl[r],
                         k1, b, avg, scorer)
        loc = r - b0
        first = store.flat_docs[s + loc * BLOCK]
        last = store.flat_docs[np.minimum(s + (loc + 1) * BLOCK, e) - 1]
        bs, be = first >> shift, last >> shift
        np.maximum.at(arr, bs, sat)
        np.maximum.at(arr, be, sat)
        for i in np.flatnonzero(be - bs >= 2):  # blocks spanning ≥3 buckets
            arr[bs[i] + 1:be[i]] = np.maximum(arr[bs[i] + 1:be[i]], sat[i])
    elif e > s:
        d = store.flat_docs[s:e]
        sat = _sat_exact(store.flat_tfs[s:e], store.norms_host[d],
                         k1, b, avg, scorer)
        np.maximum.at(arr, d >> shift, sat)
    tab = _sparse_table(arr).astype(np.float32)  # bounds stay valid: the
    # float32 rounding of a float64 max can go either way, but callers add
    # an epsilon margin on θ, and the champion pass (exact) sets θ — a
    # half-ULP of slack on a bound dominated by that margin is immaterial
    cache[key] = tab
    return tab


@dataclass
class WandPlan:
    """Threshold + bounds for one pure-disjunction query (WAND family).

    theta: lower bound on the k-th final score (from exact champion
    scoring); maxscore: {tid: w·max sat} for every query term; kept:
    {tid: surviving global block-row indices} for heavy terms after
    block-max row pruning against theta."""

    theta: float
    maxscore: dict
    kept: dict


def wand_plan(store: BlockStore, term_ids, idf: np.ndarray, k: int,
              avg: float, k1: float, b: float, scorer: str,
              champions: int = 16) -> Optional[WandPlan]:
    """Block-max WAND planning for one pure-disjunction query.

    Reference analog: wand_writer.hpp block-max metadata consumed by
    block_disjunction's skip logic. TPU re-formulation: instead of
    data-dependent skipping inside the kernel (shape-hostile), the HOST
    derives a threshold θ — a lower bound on the k-th final score, from
    exact scoring of the `champions` best block rows plus all light-term
    tails. θ powers two exact optimizations chosen by the caller:

    1. MaxScore essential-list split: terms whose max scores sum below θ
       cannot alone lift a doc into the top-k, so candidate docs are the
       remaining ("essential") terms' postings only — selective queries
       collapse to a small sparse scoring problem.
    2. Block-row pruning for the dense path: a heavy block row is dropped
       when its own w·sat(block_max_tf, block_min_dl) plus, for every
       OTHER query term, the max of that term's per-bucket upper bounds
       over the row's doc range (sparse-table range-max, cached per
       segment) cannot reach θ.

    Both preserve exact top-k: any doc losing a contribution is provably
    below the true k-th score. Returns None when not applicable (θ=0 or
    no heavy terms).
    """
    heavy_ts, light_ts = [], []
    for j, tid in enumerate(term_ids):
        (heavy_ts if store.heavy[int(tid)] else light_ts).append(
            (int(tid), float(idf[j])))
    if not heavy_ts:
        return None
    norms = store.norms_host
    # per-row upper bounds of each heavy term
    rows_per, ub_per = [], []
    maxscore = {}
    for tid, w in heavy_ts:
        b0, b1 = int(store.block_offsets[tid]), int(store.block_offsets[tid + 1])
        r = np.arange(b0, b1, dtype=np.int64)
        ub = w * _sat_exact(store.block_bmax_tf[r], store.block_bmin_dl[r],
                            k1, b, avg, scorer)
        rows_per.append(r)
        ub_per.append(ub)
        maxscore[tid] = float(ub.max()) if len(ub) else 0.0
    light_contribs = []  # (docs, contribs) for the champion accumulation
    for tid, w in light_ts:
        s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
        if e <= s:
            maxscore[tid] = 0.0
            continue
        d = store.flat_docs[s:e]
        c = w * _sat_exact(store.flat_tfs[s:e], norms[d], k1, b, avg, scorer)
        light_contribs.append((d, c))
        maxscore[tid] = float(c.max())

    # champion pass: exact host scoring of the top-C rows by upper bound
    all_ub = np.concatenate(ub_per)
    all_rows = np.concatenate(rows_per)
    all_w = np.concatenate([np.full(len(r), w)
                            for (_, w), r in zip(heavy_ts, rows_per)])
    all_tid = np.concatenate([np.full(len(r), tid, dtype=np.int64)
                              for (tid, _), r in zip(heavy_ts, rows_per)])
    C = min(len(all_ub), max(champions, 2 * ((k + BLOCK - 1) // BLOCK)))
    champ = np.argpartition(-all_ub, C - 1)[:C] if C < len(all_ub) \
        else np.arange(len(all_ub))
    docs_parts, contrib_parts = [], []
    for ci in champ:
        tid, w, row = int(all_tid[ci]), float(all_w[ci]), int(all_rows[ci])
        b0 = int(store.block_offsets[tid])
        s = int(store.offsets[tid]) + (row - b0) * BLOCK
        e = min(s + BLOCK, int(store.offsets[tid + 1]))
        d = store.flat_docs[s:e]
        docs_parts.append(d)
        contrib_parts.append(w * _sat_exact(store.flat_tfs[s:e], norms[d],
                                            k1, b, avg, scorer))
    for d, c in light_contribs:
        docs_parts.append(d)
        contrib_parts.append(c)
    if not docs_parts:
        return None
    docs_all = np.concatenate(docs_parts)
    contrib_all = np.concatenate(contrib_parts)
    uniq, inv = np.unique(docs_all, return_inverse=True)
    totals = np.bincount(inv, weights=contrib_all)
    if len(totals) < k:
        return None  # fewer champion docs than k → no safe threshold
    theta = float(np.partition(totals, len(totals) - k)[len(totals) - k])
    # device scores are float32 while this pass is float64 — shave an
    # epsilon off θ so borderline rows are kept, never wrongly dropped
    theta *= 1.0 - 1e-5
    if theta <= 0.0:
        return None

    # doc-space bucket size: ≥1024 docs, ≤16384 buckets
    shift = 10
    while (store.ndocs_pad >> shift) + 1 > 16384:
        shift += 1
    kept = {}
    for (tid, _w), r, ub in zip(heavy_ts, rows_per, ub_per):
        if len(r) == 0:
            kept[tid] = r
            continue
        b0 = int(store.block_offsets[tid])
        s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
        loc = r - b0
        first = store.flat_docs[s + loc * BLOCK]
        last = store.flat_docs[np.minimum(s + (loc + 1) * BLOCK, e) - 1]
        lo_b, hi_b = first >> shift, last >> shift
        other = np.zeros(len(r))
        for tid2, w2 in heavy_ts + light_ts:
            if tid2 == tid:
                continue
            tab = _bucket_tables(store, tid2, avg, k1, b, scorer, shift)
            other += w2 * np.maximum(_range_max(tab, lo_b, hi_b), 0.0)
        kept[tid] = r[ub + other >= theta]
    return WandPlan(theta=theta, maxscore=maxscore, kept=kept)


def assemble_query_batch(store: BlockStore, n_docs: int,
                         queries: list[tuple[np.ndarray, int]],
                         doc_freq: np.ndarray,
                         scorer: str = "bm25", idf_of=None,
                         plans=None) -> QueryBatch:
    """queries: list of (term_ids, require_all) per query. Weights are the
    scorer's per-term idf (computed here so one dispatch covers all);
    idf_of overrides with global collection stats for multi-segment
    searches.

    plans: optional per-query WandPlan list (see wand_plan) — a plan's
    kept-rows replace the term's full block-row span, dropping rows
    provably unable to reach the top-k before the device gather.
    """
    rows, row_w, row_q = [], [], []
    rrows, rrow_w, rrow_q = [], [], []
    tails_d, tails_f, tails_w, tails_q = [], [], [], []
    require = []
    n_postings = 0
    for qi, (term_ids, req) in enumerate(queries):
        require.append(req)
        tid_arr = np.asarray(term_ids, dtype=np.int64)
        if not len(term_ids):
            idf = np.empty(0, dtype=np.float32)
        elif idf_of is not None:
            idf = np.asarray(idf_of(tid_arr), dtype=np.float32)
        else:
            idf = idf_for(scorer, n_docs, doc_freq[tid_arr])
        kept = None
        if plans is not None and plans[qi] is not None and req == 0:
            kept = plans[qi].kept
        for k, tid in enumerate(term_ids):
            tid = int(tid)
            w = float(idf[k])
            if store.heavy[tid]:
                if kept is not None:
                    r = kept[tid].astype(np.int64)
                else:
                    b0 = int(store.block_offsets[tid])
                    b1 = int(store.block_offsets[tid + 1])
                    r = np.arange(b0, b1, dtype=np.int64)
                n_postings += int(store.row_count[r].sum())
                # split the term's global rows across the two planes
                plane = store.row_plane[r]
                pk = store.row_slot[r[plane == 0]]
                rw = store.row_slot[r[plane == 1]]
                if len(pk):
                    rows.append(pk)
                    row_w.append(np.full(len(pk), w, dtype=np.float32))
                    row_q.append(np.full(len(pk), qi, dtype=np.int32))
                if len(rw):
                    rrows.append(rw)
                    rrow_w.append(np.full(len(rw), w, dtype=np.float32))
                    rrow_q.append(np.full(len(rw), qi, dtype=np.int32))
            else:
                s, e = int(store.offsets[tid]), int(store.offsets[tid + 1])
                n_postings += e - s
                tails_d.append(store.flat_docs[s:e])
                tails_f.append(store.flat_tfs[s:e])
                tails_w.append(np.full(e - s, w, dtype=np.float32))
                tails_q.append(np.full(e - s, qi, dtype=np.int32))

    def cat(parts, dtype):
        return np.concatenate(parts).astype(dtype, copy=False) if parts \
            else np.empty(0, dtype=dtype)

    tail_docs = cat(tails_d, np.int32)
    return QueryBatch(
        row_idx=cat(rows, np.int32),
        row_w=cat(row_w, np.float32),
        row_qid=cat(row_q, np.int32),
        raw_idx=cat(rrows, np.int32),
        raw_w=cat(rrow_w, np.float32),
        raw_qid=cat(rrow_q, np.int32),
        tail_docs=tail_docs,
        tail_tfs=cat(tails_f, np.int32),
        tail_dls=store.norms_host[tail_docs],
        tail_w=cat(tails_w, np.float32),
        tail_qid=cat(tails_q, np.int32),
        require=np.asarray(require, dtype=np.int32),
        n_queries=len(queries),
        n_postings=n_postings,
    )


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


def _pad_to(a: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full(n, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


# ------------------------------------------------- the closed program set
#
# A scoring dispatch is a FIXED-CAPACITY accumulate step, called as often
# as the batch needs on a donated score plane, then one top-k step. What
# a program is compiled for is a `Rung` — the plane's query rows and the
# step's three capacities — chosen from what the store shows (its padded
# document count) and the batcher's cap, never from the batch: so the set
# of programs is closed, listed by `plane_program_keys` /
# `dense_program_keys`, and built before the index answers its first
# search (`SegmentSearcher.prebuild`). A batch picks the smallest rung
# that holds its queries; more queries than the largest rung split into
# several dispatches (searcher.topk_batch).
#
# Parity: a (query, document) cell receives at most one contribution per
# term, and the steps run in sequence. The resident steps come first:
# the store's resident terms (`resident_terms`) add their whole rows, in
# slot order — the query's own term order —, and a pad slot adds
# nothing. Then each accumulate step adds packed rows, then raw rows, then
# tails. `query_chunks` never puts a query's raw rows in an earlier step
# than its packed rows, nor its tails before its raw rows, so a cell's
# f32 additions happen in ONE order — resident rows, packed rows, raw
# rows, tails, each in query-term order — however the batch was composed
# or cut. Which terms are resident is the store's alone, never the
# batch's.

#: the largest plane a rung may ask for, in queries
NQ_TOP = 32
#: slots (query terms) one dense step adds
DENSE_SLOTS = 16


class Rung(NamedTuple):
    nq: int    # queries = rows of the score plane
    nb: int    # packed rows per accumulate step
    nr: int    # raw rows per accumulate step
    tt: int    # light-term postings per accumulate step


def score_rungs(ndocs_pad: int, batch_cap: int,
                acc_entries: int) -> tuple[Rung, ...]:
    """The ladder of at most three rungs for a store of `ndocs_pad`
    padded documents behind a batcher that coalesces up to `batch_cap`
    queries, a score plane holding at most `acc_entries` cells."""
    top = max(1, min(NQ_TOP, int(batch_cap), acc_entries // ndocs_pad))
    top = 1 << (top.bit_length() - 1)
    return tuple(Rung(nq, min(4096, 1024 * nq), min(128, 32 * nq),
                      min(8192, 2048 * nq))
                 for nq in sorted({1, min(8, top), top}))


def rung_for(rungs: tuple[Rung, ...], n_queries: int) -> Rung:
    """The smallest rung whose plane holds `n_queries` (the caller has
    split what exceeds the largest)."""
    return next(r for r in rungs if r.nq >= n_queries)


def query_chunks(qb: QueryBatch, rung: Rung, pad_packed: int,
                 pad_raw: int, min_steps: int = 0,
                 ) -> list[tuple[np.ndarray, np.ndarray]]:
    """The batch cut into accumulate steps: per step ONE int32 and ONE f32
    buffer (two host->device transfers), each of the rung's fixed size.

    ints: [row_idx | row_qid | raw_idx | raw_qid
           | tail_docs | tail_tfs | tail_dls | tail_qid]
    floats: [row_w | raw_w | tail_w]

    Packed rows fill steps from the first; raw rows start in the step
    that holds the LAST packed rows, tails in the one that holds the last
    raw rows (the parity note above). Unused capacity gathers the planes'
    pad slots / carries doc -1 with weight 0: it scatters nothing."""
    n_p = -(-len(qb.row_idx) // rung.nb)
    r0 = max(n_p - 1, 0)
    n_r = -(-len(qb.raw_idx) // rung.nr)
    t0 = max(r0 + n_r - 1, r0)
    n_t = -(-len(qb.tail_docs) // rung.tt)
    n_steps = max(n_p, r0 + n_r, t0 + n_t, min_steps)
    out = []
    for c in range(n_steps):
        p = slice(c * rung.nb, (c + 1) * rung.nb)
        r = slice(max(c - r0, 0) * rung.nr, max(c - r0 + 1, 0) * rung.nr)
        t = slice(max(c - t0, 0) * rung.tt, max(c - t0 + 1, 0) * rung.tt)
        ints = np.concatenate([
            _pad_to(qb.row_idx[p], rung.nb, pad_packed),
            _pad_to(qb.row_qid[p], rung.nb, 0),
            _pad_to(qb.raw_idx[r], rung.nr, pad_raw),
            _pad_to(qb.raw_qid[r], rung.nr, 0),
            _pad_to(qb.tail_docs[t], rung.tt, -1),
            _pad_to(qb.tail_tfs[t], rung.tt, 0),
            _pad_to(qb.tail_dls[t], rung.tt, 0),
            _pad_to(qb.tail_qid[t], rung.tt, 0)]).astype(np.int32)
        floats = np.concatenate([
            _pad_to(qb.row_w[p], rung.nb, 0.0),
            _pad_to(qb.raw_w[r], rung.nr, 0.0),
            _pad_to(qb.tail_w[t], rung.tt, 0.0)]).astype(np.float32)
        out.append((ints, floats))
    return out


_NO_QUERIES = QueryBatch(
    *(np.empty(0, dtype=t) for t in (
        np.int32, np.float32, np.int32, np.int32, np.float32, np.int32,
        np.int32, np.int32, np.int32, np.float32, np.int32, np.int32)), 0)


def accumulate_body(rung: Rung, ndocs_pad: int, with_hits: bool,
                    first: bool, scorer: str):
    """The traced body of one accumulate step: unpack the two buffers,
    add this step's contributions to the score plane (and the hit plane
    under a conjunction) — planes made here when `first`, else the
    donated ones handed in."""
    nb, nr, tt = rung.nb, rung.nr, rung.tt

    def step(block_base, block_gaps, block_tfs8, block_dls, raw_docs,
             raw_tfs, raw_dls, ints, floats, k1, b, avgdl, *planes):
        o = 2 * nb + 2 * nr
        scores, hits = _accumulate_scores(
            block_base, block_gaps, block_tfs8, block_dls, raw_docs,
            raw_tfs, raw_dls,
            ints[:nb], floats[:nb], ints[nb:2 * nb],
            ints[2 * nb:2 * nb + nr], floats[nb:nb + nr],
            ints[2 * nb + nr:o],
            ints[o:o + tt], ints[o + tt:o + 2 * tt],
            ints[o + 2 * tt:o + 3 * tt],
            floats[nb + nr:nb + nr + tt], ints[o + 3 * tt:o + 4 * tt],
            ndocs_pad, rung.nq, with_hits, k1, b, avgdl, scorer,
            None if first else planes[0],
            None if first or not with_hits else planes[1])
        return (scores, hits) if with_hits else (scores,)

    return step


def topk_body(ndocs_pad: int, nq: int, with_hits: bool, with_mask: bool,
              k: int):
    """The traced body of the top-k step: require-mask, then the
    queries' own doc masks (`with_mask`: (nq, ndocs_pad) uint8 flags, a
    phrase's match set; a row of ones for a query that has none), then
    the per-query top-k. A masked-out document scores 0, as one that
    misses a conjunction does."""
    def step(require, scores, *rest):
        scores = scores.reshape(nq, ndocs_pad)
        if with_hits:
            need = require[:, None]
            scores = jnp.where(
                jnp.logical_or(need <= 0,
                               rest[0].reshape(nq, ndocs_pad) >= need),
                scores, 0.0)
        if with_mask:
            scores = jnp.where(rest[-1] != 0, scores, 0.0)
        return jax.lax.top_k(scores, k)

    return step


def _accumulate_program(store: BlockStore, rung: Rung, with_hits: bool,
                        first: bool, scorer: str):
    # the planes' allocated rows are part of the key: XLA compiles per
    # argument shape, and the ledger has to count what it really builds
    key = (store.block_base.shape[0], store.raw_docs.shape[0],
           store.ndocs_pad, tuple(rung), with_hits, first, scorer)
    return obs_device.compiled(
        "bm25_accumulate", key,
        lambda: accumulate_body(rung, store.ndocs_pad, with_hits, first,
                                scorer),
        donate_argnums=() if first else
        tuple(range(12, 14 if with_hits else 13)))


def _topk_program(ndocs_pad: int, nq: int, with_hits: bool, k: int,
                  with_mask: bool = False):
    return obs_device.compiled(
        "bm25_topk", (ndocs_pad, nq, with_hits, with_mask, k),
        lambda: topk_body(ndocs_pad, nq, with_hits, with_mask, k))


#: `first` of a program key that names the top-k step under doc masks
MASKED = "masked"


def plane_program_keys(rungs: tuple[Rung, ...]) -> list[tuple]:
    """Every plane-kernel program a batch that fits one of `rungs` can
    dispatch: (rung, with_hits, first) per accumulate step, first = None
    for the rung's top-k step and MASKED for its top-k step under doc
    masks."""
    return [(rung, with_hits, first)
            for rung in rungs
            for with_hits in (False, True)
            for first in (True, False, None, MASKED)]


def doc_masks(masks: dict, nq: int, ndocs_pad: int) -> np.ndarray:
    """The (nq, ndocs_pad) uint8 flags a masked top-k step reads:
    `masks` = {query row: sorted doc ids it may return}; a row without
    an entry admits every document."""
    flags = np.ones((nq, ndocs_pad), dtype=np.uint8)
    for qi, docs in masks.items():
        flags[qi] = 0
        flags[qi, docs] = 1
    return flags


def score_topk_planes(store: BlockStore, qb: QueryBatch, rung: Rung,
                      k: int, k1: float, b: float, avgdl: float,
                      scorer: str, masks: Optional[dict] = None,
                      resident: Optional[tuple] = None):
    """One dispatch of a batch that fits `rung`: the resident steps
    where `resident` = (the store's resident rows, a DenseStore; [(rows,
    weights) per query]) holds any slot, then its accumulate steps in
    sequence on the donated planes, then the top-k step — under the
    queries' doc masks where `masks` ({query row: doc ids}) holds any.
    `qb` holds what is not resident. Returns the device (vals, docs),
    each (rung.nq, k): rows past qb.n_queries are padding."""
    with_hits = bool(qb.require.any())
    planes: tuple = ()
    if resident is not None:
        rows, slots = resident
        for c in range(_slot_steps(slots, 0)):
            with stage("search_plan"):
                tids, w = _slot_block(slots, c, rung.nq)
            planes = _resident_program(rows, rung.nq, not planes,
                                       with_hits)(rows.St, tids, w, *planes)
    with stage("search_plan"):
        steps = query_chunks(qb, rung, store.n_packed, store.n_raw,
                             min_steps=0 if planes else 1)
    for ints, floats in steps:
        prog = _accumulate_program(store, rung, with_hits, not planes,
                                   scorer)
        planes = prog(*store.tiles, ints, floats, k1, b, avgdl, *planes)
    with stage("search_plan"):
        if masks:
            planes += (doc_masks(masks, rung.nq, store.ndocs_pad),)
        require = _pad_to(qb.require, rung.nq, 0)
    return _topk_program(store.ndocs_pad, rung.nq, with_hits, k,
                         bool(masks))(require, *planes)


def prebuild_plane_programs(store: BlockStore, rungs: tuple[Rung, ...],
                            k: int, scorer: str) -> int:
    """Build every program of `plane_program_keys` by running it once on
    an all-padding step — first step, next step on the planes it gave,
    top-k, masked top-k — so that no search has to. The (rung,
    conjunction) chains are independent and compile side by side.
    Returns how many programs this call built."""
    from concurrent.futures import ThreadPoolExecutor
    chains: dict = {}
    for rung, with_hits, first in plane_program_keys(rungs):
        prog = _topk_program(store.ndocs_pad, rung.nq, with_hits, k,
                             first == MASKED) \
            if first in (None, MASKED) else \
            _accumulate_program(store, rung, with_hits, first, scorer)
        chains.setdefault((rung, with_hits), []).append((first, prog))
    todo = {key: steps for key, steps in chains.items()
            if not all(prog.called for _, prog in steps)}

    def run(item):
        (rung, _with_hits), steps = item
        (ints, floats), = query_chunks(_NO_QUERIES, rung, store.n_packed,
                                       store.n_raw, min_steps=1)
        planes: tuple = ()
        tops = []
        for first, prog in steps:
            if first in (None, MASKED):
                tops.append(prog(
                    np.zeros(rung.nq, dtype=np.int32), *planes,
                    *((doc_masks({}, rung.nq, store.ndocs_pad),)
                      if first == MASKED else ())))
            else:
                planes = prog(*store.tiles, ints, floats, 1.2, 0.75, 1.0,
                              *planes)
        jax.block_until_ready(tops)

    built = sum(not prog.called for steps in todo.values()
                for _, prog in steps)
    if todo:
        with ThreadPoolExecutor(max_workers=len(todo)) as pool:
            list(pool.map(run, todo.items()))
    return built


def _decode_rows(block_base, block_gaps, block_tfs8, row_idx):
    """In-kernel decompression of packed posting rows: docs = base +
    lane-axis prefix sum of the uint16 gaps (the TPU analog of the
    reference's SIMD streamvbyte/bitpack decode, format_block_128.cpp);
    tf=0 marks padding."""
    gaps = block_gaps[row_idx].astype(jnp.int32)        # (NB, 128)
    docs = block_base[row_idx][:, None] + jnp.cumsum(gaps, axis=1)
    tfs = block_tfs8[row_idx].astype(jnp.int32)
    valid = tfs > 0
    return jnp.where(valid, docs, -1), tfs


def _accumulate_scores(block_base, block_gaps, block_tfs8, block_dls,
                       raw_docs, raw_tfs, raw_dls, row_idx, row_w, row_qid,
                       raw_idx, raw_w, raw_qid, tail_docs, tail_tfs,
                       tail_dls, tail_w, tail_qid, ndocs_pad: int,
                       n_queries: int, with_hits: bool, k1: float,
                       b: float, avgdl, scorer: str = "bm25", scores=None,
                       hits=None):
    """Fused gather+decode → score → batched scatter-accumulate into
    flat (B x ndocs,) score planes (+ hit counts when with_hits): onto
    the planes handed in, or fresh ones. Every posting comes with its
    document's length, read by ROW beside its tf (`block_dls`,
    `raw_dls`, `tail_dls`): nothing is gathered per element. Shared by
    the accumulate step and the mesh-sharded path, whose shards each
    accumulate their posting-row slice before a psum merge."""
    avg = jnp.maximum(jnp.float32(avgdl), 1e-9)

    def contrib_of(docs, tfs, dl, w):
        valid = jnp.logical_and(docs >= 0, tfs > 0)
        safe_docs = jnp.where(valid, docs, 0)
        tfsf = tfs.astype(jnp.float32)
        if scorer == "tfidf":
            c = w * jnp.sqrt(tfsf)
        elif scorer == "lm_dirichlet":
            # w = p_t (collection probability), k1 slot = µ. Lucene
            # LMDirichletSimilarity shape, clamped at 0
            # (reference: lm_dirichlet.cpp)
            dl = dl.astype(jnp.float32)
            mu = k1
            c = (jnp.log1p(tfsf / (mu * w)) +
                 jnp.log(mu / (dl + mu)))
            # + MATCH_EPS: LM scores clamp to 0 for weak matches, but the
            # engine's result filters rely on score>0 ⇔ matched
            c = jnp.maximum(c, 0.0) + MATCH_EPS
        elif scorer == "jelinek_mercer":
            # w = p_t, k1 slot = λ (reference: jelinek_mercer smoothing)
            dl = dl.astype(jnp.float32)
            lam = k1
            c = jnp.log1p(((1.0 - lam) * tfsf / jnp.maximum(dl, 1.0)) /
                          (lam * w))
        elif scorer == "dfi":
            # divergence from independence: expected tf under independence
            # is e = p_t·dl; score the standardized excess
            # (reference: dfi.cpp)
            dl = dl.astype(jnp.float32)
            e = w * dl
            excess = (tfsf - e) / jnp.sqrt(jnp.maximum(e, 1e-9))
            c = jnp.where(tfsf > e, jnp.log2(1.0 + excess), 0.0) + MATCH_EPS
        else:
            dl = dl.astype(jnp.float32)
            denom = tfsf + k1 * (1.0 - b + b * dl / avg)
            c = w * (k1 + 1.0) * tfsf / jnp.maximum(denom, 1e-9)
        return jnp.where(valid, c, 0.0), valid, safe_docs

    if scores is None:
        scores = jnp.zeros((n_queries * ndocs_pad,), dtype=jnp.float32)
    if with_hits and hits is None:
        hits = jnp.zeros((n_queries * ndocs_pad,), dtype=jnp.int32)
    reads_dl = scorer != "tfidf"   # tfidf gathers no length plane
    # packed plane: gather + in-kernel delta decode
    pdocs, ptfs = _decode_rows(block_base, block_gaps, block_tfs8, row_idx)
    wc, valid_b, safe_b = contrib_of(
        pdocs, ptfs, block_dls[row_idx] if reads_dl else None,
        row_w[:, None])
    bidx = (row_qid[:, None] * ndocs_pad + safe_b).reshape(-1)
    scores = scores.at[bidx].add(wc.reshape(-1))
    # raw exception plane (rows whose gaps/tfs/lengths overflow the
    # packed widths)
    rdocs = raw_docs[raw_idx]
    rtfs = raw_tfs[raw_idx]
    rc, valid_r, safe_r = contrib_of(
        rdocs, rtfs, raw_dls[raw_idx] if reads_dl else None,
        raw_w[:, None])
    ridx = (raw_qid[:, None] * ndocs_pad + safe_r).reshape(-1)
    scores = scores.at[ridx].add(rc.reshape(-1))
    # light-term tails
    tc, valid_t, safe_t = contrib_of(tail_docs, tail_tfs, tail_dls, tail_w)
    tidx = tail_qid * ndocs_pad + safe_t
    scores = scores.at[tidx].add(tc)
    if with_hits:
        hits = hits.at[bidx].add(valid_b.reshape(-1).astype(jnp.int32))
        hits = hits.at[ridx].add(valid_r.reshape(-1).astype(jnp.int32))
        hits = hits.at[tidx].add(valid_t.astype(jnp.int32))
    return scores, hits


def _mesh_score_fn(mesh_n: int, ndocs_pad: int, k: int, n_queries: int,
                   scorer: str, k1: float, b: float):
    """Mesh-sharded scoring program (cached per shape in the obs/device
    compile ledger — no local memo, so the bounded program LRU really
    owns these executables): posting-row sections shard across devices,
    each shard accumulates its slice with the SAME kernel as the
    single-device path, score planes psum over ICI, one top-k on the
    merged plane (reference analog: parallel per-segment top-k
    collectors, SURVEY.md §2.11 — re-expressed as XLA collectives; see
    also parallel/mesh.py)."""
    def build():
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import AXIS, make_mesh
        mesh = make_mesh(mesh_n)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=((P(),) * 7 + (P(), ) +        # tiles + avgdl
                      (P(AXIS),) * 11),             # posting-row sections
            out_specs=(P(), P()))
        def step(block_base, block_gaps, block_tfs8, block_dls, raw_docs,
                 raw_tfs, raw_dls, avgdl, row_idx, row_w, row_qid, raw_idx,
                 raw_w, raw_qid, tail_docs, tail_tfs, tail_dls, tail_w,
                 tail_qid):
            scores, _ = _accumulate_scores(
                block_base, block_gaps, block_tfs8, block_dls, raw_docs,
                raw_tfs, raw_dls, row_idx, row_w, row_qid, raw_idx, raw_w,
                raw_qid, tail_docs, tail_tfs, tail_dls, tail_w, tail_qid,
                ndocs_pad, n_queries, False, k1, b, avgdl, scorer)
            scores = jax.lax.psum(scores, AXIS)
            return jax.lax.top_k(scores.reshape(n_queries, ndocs_pad), k)

        return step

    return obs_device.compiled(
        "bm25_mesh",
        (mesh_n, ndocs_pad, k, n_queries, scorer, k1, b),
        build)


def score_topk_mesh(store, qb: "QueryBatch", ndocs_pad: int, k: int,
                    mesh_n: int, k1: float, b: float, avgdl: float,
                    scorer: str = "bm25"):
    """Score a require-free query batch over an N-device mesh. Sections
    pad to a power of two, then to a mesh multiple, with the no-op fills
    the steps use (w=0 rows contribute nothing)."""
    from ..parallel.mesh import pad_to_multiple

    def pad_sec(a, fill, floor=8):
        a = np.asarray(a)
        return pad_to_multiple(_pad_to(a, _pow2(len(a), floor), fill),
                               mesh_n, fill)

    fn = _mesh_score_fn(mesh_n, ndocs_pad, k, qb.n_queries, scorer,
                        float(k1), float(b))
    with stage("search_plan"):
        sections = [pad_sec(qb.row_idx, store.n_packed),
                    pad_sec(qb.row_w, np.float32(0.0)),
                    pad_sec(qb.row_qid, 0),
                    pad_sec(qb.raw_idx, store.n_raw),
                    pad_sec(qb.raw_w, np.float32(0.0)),
                    pad_sec(qb.raw_qid, 0),
                    pad_sec(qb.tail_docs, -1, BLOCK),
                    pad_sec(qb.tail_tfs, 0, BLOCK),
                    pad_sec(qb.tail_dls, 0, BLOCK),
                    pad_sec(qb.tail_w, np.float32(0.0), BLOCK),
                    pad_sec(qb.tail_qid, 0, BLOCK)]
    # the program's own shardings place them: not `CompiledProgram`'s
    # single-device commit
    with stage("device_upload", family="bm25_mesh"):
        operands = [jnp.float32(avgdl)] + [jnp.asarray(a)
                                           for a in sections]
    return fn(*store.tiles, *operands)


# ------------------------------------------------------------ dense path
#
# Small-corpus regime (benchmark-game scale): the scatter-accumulate kernel
# is bound by XLA's serialized scatter. When the dense (V_pad, ndocs_pad)
# saturation matrix fits an HBM budget, a query term's contribution to
# EVERY document is one contiguous row of it, and scoring becomes row
# gathers plus elementwise adds in query-term order — no scatter, no host
# WAND planning. The add order depends on the query alone, never on where
# a term sits in this segment's vocabulary, so identical documents in
# different segments get identical score bits (a whole-vocabulary matmul
# does not: its reduction order follows the column layout, and on an MXU
# its default precision is not f32). St is built ON DEVICE from the
# already-resident block tiles (+ a one-time light-term tail upload), so
# no dense matrix ever crosses the host↔device link.
#
# A store whose whole matrix does not fit keeps the rows of its DENSE
# terms resident (`resident_terms`: the terms whose doc bitset is no
# larger than their posting list, most frequent first, within the same
# budget), built by the same code: the plane kernel adds those rows to
# its planes in resident steps (`score_topk_planes`) and scatters only
# the other terms' postings. The whole matrix is the case where every
# term is resident.

DENSE_HBM_BUDGET = int(float(os.environ.get("SDB_DENSE_HBM_MB", "1024"))
                       * (1 << 20))


@dataclass
class DenseStore:
    """Device-resident dense saturation matrix for one (segment, scorer,
    avgdl): St[row_of[t], d] = sat(tf(t, d), dl(d)) with the idf weight
    factored OUT (it lives in the per-query weights). A row is 0 exactly
    where the term is absent, so a document's score is the ordered sum
    of its query terms' rows and St[row] > 0 marks exactly the term's
    matches. `row_of` maps a term id to its row, -1 for a term that has
    none; None where every term has one, at its own id."""

    St: jax.Array       # (V_pad, ndocs_pad) f32, term-major
    ndocs_pad: int
    v_pad: int
    row_of: Optional[np.ndarray] = None   # (T,) int32


def _build_dense(block_base, block_gaps, block_tfs8, pk_rows, pk_tid,
                 raw_docs, raw_tfs, raw_rows, raw_tid, light_docs,
                 light_tfs, light_tid, norms, k1, b, avgdl, *,
                 ndocs_pad: int, v_pad: int, scorer: str) -> jax.Array:
    """One-time scatter of the postings of the packed rows `pk_rows`
    (decoded from the packed planes), of the raw rows `raw_rows` and of
    the light postings into a dense term-major TF plane, each into the
    matrix row its `*_tid` names; then the scorer's saturation applied
    elementwise. Runs once per (segment, scorer, avgdl); per-query
    dispatches touch only the result."""
    tf = jnp.zeros((v_pad * ndocs_pad,), dtype=jnp.float32)
    pdocs, ptfs = _decode_rows(block_base, block_gaps, block_tfs8, pk_rows)
    for docs, tfs, tid in (
            (pdocs, ptfs, pk_tid[:, None]),
            (raw_docs[raw_rows], raw_tfs[raw_rows], raw_tid[:, None]),
            (light_docs, light_tfs, light_tid)):
        valid = docs >= 0
        at = jnp.where(valid, tid * ndocs_pad + docs, 0)
        tf = tf.at[at.reshape(-1)].add(
            jnp.where(valid, tfs.astype(jnp.float32), 0.0).reshape(-1))
    tf = tf.reshape(v_pad, ndocs_pad)
    if scorer == "tfidf":
        return jnp.sqrt(tf)
    alpha = k1 * (1.0 - b + b * norms[:ndocs_pad].astype(jnp.float32) /
                  jnp.maximum(jnp.float32(avgdl), 1e-9))
    return (k1 + 1.0) * tf / jnp.maximum(tf + alpha[None, :], 1e-9)


def dense_fits(ndocs_pad: int, vocab: int) -> bool:
    """True when the (V_pad, ndocs_pad) f32 saturation matrix fits the
    dense-path HBM budget. ndocs_pad is the block store's own padding so
    the estimate can't drift from the real allocation."""
    return ndocs_pad * _bucket(vocab, 128, 128) * 4 <= DENSE_HBM_BUDGET


def resident_terms(doc_freq: np.ndarray, dense: np.ndarray,
                   ndocs_pad: int) -> np.ndarray:
    """The term ids whose rows a store keeps resident: its dense terms
    (`dense`, a bool per term), most frequent first, as many as a
    (rows, ndocs_pad) f32 matrix holds within DENSE_HBM_BUDGET."""
    tids = np.flatnonzero(dense)
    tids = tids[np.argsort(-doc_freq[tids].astype(np.int64), kind="stable")]
    n = min(len(tids), DENSE_HBM_BUDGET // (4 * ndocs_pad))
    while n and _bucket(n, 8, 8) * ndocs_pad * 4 > DENSE_HBM_BUDGET:
        n -= 1
    return tids[:n]


def build_dense_store(store: BlockStore, doc_freq: np.ndarray,
                      avgdl: float, k1: float, b: float, scorer: str,
                      terms: Optional[np.ndarray] = None) -> DenseStore:
    """The saturation rows of `terms` (every term where None), in that
    order, built on the device from the resident tiles."""
    T = len(doc_freq)
    nd_pad = store.ndocs_pad
    if terms is None:
        v_pad, row_of = _bucket(T, 128, 128), None
        term_row = np.arange(T, dtype=np.int32)
    else:
        v_pad = _bucket(len(terms), 8, 8)
        term_row = np.full(T, -1, dtype=np.int32)
        term_row[terms] = np.arange(len(terms), dtype=np.int32)
        row_of = term_row
    # heavy terms: already device-resident as block tiles; ship the
    # plane slots of the held terms' rows and each one's matrix row.
    # Light terms: one-time flat upload (df < HEAVY_DF each, so the
    # tail is small).
    nb_total = store.pad_row
    rows_per_term = np.diff(store.block_offsets).astype(np.int64)
    row_tid = term_row[np.repeat(np.arange(T), rows_per_term)]
    held = row_tid >= 0
    packed = store.row_plane[:nb_total] == 0
    slot = store.row_slot[:nb_total]
    pk, rw = held & packed, held & ~packed
    pk_rows, pk_tid = slot[pk], row_tid[pk]
    raw_rows, raw_tid = slot[rw], row_tid[rw]
    # the planes' pad slots decode as invalid and never scatter
    n_pk = _bucket(len(pk_rows) + 1, 1)
    n_rw = _bucket(len(raw_rows) + 1, 1)
    # light terms: one boolean mask over the flat postings (vectorized —
    # vocab can reach ~260k at the budget boundary)
    df = np.diff(store.offsets).astype(np.int64)
    post_tid = np.repeat(np.arange(T, dtype=np.int32), df)
    light_mask = ~store.heavy[post_tid] & (term_row[post_tid] >= 0)
    light_docs = store.flat_docs[light_mask].astype(np.int32)
    light_tfs = store.flat_tfs[light_mask].astype(np.int32)
    light_tid = term_row[post_tid[light_mask]]
    n_pad = _pow2(len(light_docs), BLOCK)
    build = obs_device.compiled(
        "dense_build",
        (store.block_base.shape[0], store.raw_docs.shape[0], n_pk, n_rw,
         n_pad, nd_pad, v_pad, scorer),
        lambda: functools.partial(_build_dense, ndocs_pad=nd_pad,
                                  v_pad=v_pad, scorer=scorer))
    St = build(
        store.block_base, store.block_gaps, store.block_tfs8,
        _pad_to(pk_rows, n_pk, store.n_packed), _pad_to(pk_tid, n_pk, 0),
        store.raw_docs, store.raw_tfs,
        _pad_to(raw_rows, n_rw, store.n_raw), _pad_to(raw_tid, n_rw, 0),
        _pad_to(light_docs, n_pad, -1), _pad_to(light_tfs, n_pad, 0),
        _pad_to(light_tid, n_pad, 0), store.norms, k1, b, avgdl)
    return DenseStore(St=St, ndocs_pad=nd_pad, v_pad=v_pad, row_of=row_of)


def dense_body(nq: int, first: bool, last: bool, k: int,
               with_mask: bool = False):
    """The traced body of one dense step: scores[q, d] += Σ_j w[q, j] ·
    St[tids[q, j], d] over this step's DENSE_SLOTS slots, added in slot
    order j (the query's own term order; pad slots carry w = 0 and add
    exactly 0.0), counting the slots that hit; planes made here when
    `first`, else the donated ones. The `last` step masks conjunctions
    (and, `with_mask`, applies the queries' doc masks, the first of
    `planes`: `doc_masks`) and returns the exact per-query top-k instead
    of the planes."""
    def step(St, tids, w, require, *planes):
        if with_mask:
            mask, planes = planes[0], planes[1:]
        nd = St.shape[1]

        def add_slot(j, carry):
            scores, hits = carry
            rows = St[jax.lax.dynamic_index_in_dim(tids, j, 1, False)]
            wj = jax.lax.dynamic_index_in_dim(w, j, 1, False)[:, None]
            return (scores + rows * wj,
                    hits + jnp.logical_and(rows > 0,
                                           wj > 0).astype(jnp.int32))

        scores, hits = jax.lax.fori_loop(
            0, DENSE_SLOTS, add_slot,
            (jnp.zeros((nq, nd), dtype=jnp.float32),
             jnp.zeros((nq, nd), dtype=jnp.int32)) if first else planes)
        if not last:
            return scores, hits
        need = require[:, None]
        scores = jnp.where(jnp.logical_or(need <= 0, hits >= need),
                           scores, 0.0)
        if with_mask:
            scores = jnp.where(mask != 0, scores, 0.0)
        return jax.lax.top_k(scores, k)

    return step


def _dense_program(ds: DenseStore, nq: int, first: bool, last,
                   k: int):
    """`last`: False, True, or MASKED (a last step under doc masks)."""
    return obs_device.compiled(
        "dense_topk", (ds.v_pad, ds.ndocs_pad, nq, first, last, k),
        lambda: dense_body(nq, first, bool(last), k, last == MASKED),
        donate_argnums=() if first or last else (4, 5))


def dense_program_keys(rungs: tuple[Rung, ...]) -> list[tuple]:
    """Every dense step a batch that fits one of `rungs` can dispatch:
    (queries, first, last), on the ladder the plane kernel climbs; last
    = MASKED names the last step under doc masks."""
    return [(rung.nq, first, last)
            for rung in rungs
            for first in (True, False) for last in (False, True, MASKED)]


def _slot_steps(slots: list, floor: int) -> int:
    """Steps of DENSE_SLOTS that hold the longest query's slots."""
    return max(floor, -(-max((len(t) for t, _ in slots), default=0)
                        // DENSE_SLOTS))


def _slot_block(slots: list, c: int, nq: int) -> tuple:
    """Step c's (nq, DENSE_SLOTS) term rows and weights; pad slots carry
    row 0 and weight 0."""
    tids = np.zeros((nq, DENSE_SLOTS), dtype=np.int32)
    w = np.zeros((nq, DENSE_SLOTS), dtype=np.float32)
    part = slice(c * DENSE_SLOTS, (c + 1) * DENSE_SLOTS)
    for qi, (t, wq) in enumerate(slots):
        tids[qi, :len(t[part])] = t[part]
        w[qi, :len(t[part])] = wq[part]
    return tids, w


def dense_score_topk(ds: DenseStore, slots: list[tuple[np.ndarray,
                                                       np.ndarray]],
                     require: np.ndarray, nq: int, k: int,
                     masks: Optional[dict] = None):
    """One dense dispatch: the queries' (term id, weight) slots, cut into
    steps of DENSE_SLOTS and added in sequence, the last under the
    queries' doc masks where `masks` ({query row: doc ids}) holds any.
    Returns the device (vals, docs), each (nq, k): rows past len(slots)
    are padding."""
    n_steps = _slot_steps(slots, 1)
    with stage("search_plan"):
        require = _pad_to(require, nq, 0)
    out: tuple = ()
    for c in range(n_steps):
        last = c == n_steps - 1
        with stage("search_plan"):
            tids, w = _slot_block(slots, c, nq)
            if last and masks:
                out = (doc_masks(masks, nq, ds.ndocs_pad),) + out
                last = MASKED
        out = _dense_program(ds, nq, c == 0, last, k)(
            ds.St, tids, w, require, *out)
    return out


def prebuild_dense_programs(ds: DenseStore, rungs: tuple[Rung, ...],
                            k: int) -> int:
    """Build every program of `dense_program_keys` by running it once on
    an all-padding step. Returns how many programs this call built."""
    built = 0
    for nq in sorted({rung.nq for rung in rungs}):
        progs = {(first, last): _dense_program(ds, nq, first, last, k)
                 for n, first, last in dense_program_keys(rungs)
                 if n == nq}
        if all(p.called for p in progs.values()):
            continue
        built += sum(not p.called for p in progs.values())
        tids = np.zeros((nq, DENSE_SLOTS), dtype=np.int32)
        w = np.zeros((nq, DENSE_SLOTS), dtype=np.float32)
        req = np.zeros(nq, dtype=np.int32)
        mask = doc_masks({}, nq, ds.ndocs_pad)
        planes = progs[(True, False)](ds.St, tids, w, req)
        planes = progs[(False, False)](ds.St, tids, w, req, *planes)
        jax.block_until_ready((
            progs[(False, True)](ds.St, tids, w, req, *planes),
            progs[(False, MASKED)](ds.St, tids, w, req, mask, *planes),
            progs[(True, True)](ds.St, tids, w, req),
            progs[(True, MASKED)](ds.St, tids, w, req, mask)))
    return built


def dense_slots(queries: list[tuple[np.ndarray, int]], n_docs: int,
                doc_freq: np.ndarray, scorer: str, idf_of=None,
                ) -> tuple[list[tuple[np.ndarray, np.ndarray]], np.ndarray]:
    """([(term ids, weights) per query, in query order], require)."""
    slots = []
    for term_ids, _req in queries:
        tid_arr = np.asarray(term_ids, dtype=np.int64)
        if not len(tid_arr):
            idf = np.empty(0, dtype=np.float32)
        elif idf_of is not None:
            idf = np.asarray(idf_of(tid_arr), dtype=np.float32)
        else:
            idf = idf_for(scorer, n_docs, doc_freq[tid_arr])
        slots.append((tid_arr.astype(np.int32), idf))
    return slots, np.asarray([req for _, req in queries], dtype=np.int32)


# --------------------------------------------------- resident rows, planes


def resident_body(nq: int, first: bool, with_hits: bool):
    """The traced body of one resident step of the plane kernel: for
    each query row q, scores[q, d] += w[q, j] · St[tids[q, j], d] over
    its slots j in slot order — the query's own term order —, counting
    the slots that hit (`dense_body`'s sum and count, cell for cell),
    on the flat (nq * ndocs_pad,) planes the accumulate steps add to:
    made here when `first`, else the donated ones handed in (the hits
    plane only `with_hits`, as theirs). A query's slots stop at its
    last non-zero weight: the pad slots after it, which `dense_body`
    adds as exact zeros, are not read at all. Each slot is one row read
    and one row of the plane updated in place; a vector of rows
    gathered at once (`dense_body`'s `St[tids[:, j]]`) compiles for a
    v5e, past one query, into chunked copies of the whole matrix."""
    def step(St, tids, w, *planes):
        nd = St.shape[1]
        if first:
            planes = (jnp.zeros((nq * nd,), dtype=jnp.float32),) + \
                ((jnp.zeros((nq * nd,), dtype=jnp.int32),)
                 if with_hits else ())
        used = jnp.max(jnp.where(w != 0, jnp.arange(1, w.shape[1] + 1), 0),
                       axis=1)

        def add_query(q, planes):
            def add_slot(j, planes):
                row = jax.lax.dynamic_index_in_dim(St, tids[q, j], 0, False)
                wj = w[q, j]
                at = q * nd
                scores = planes[0]
                cur = jax.lax.dynamic_slice_in_dim(scores, at, nd)
                out = (jax.lax.dynamic_update_slice_in_dim(
                    scores, cur + row * wj, at, 0),)
                if with_hits:
                    hits = planes[1]
                    hit = jnp.logical_and(row > 0, wj > 0).astype(jnp.int32)
                    out += (jax.lax.dynamic_update_slice_in_dim(
                        hits, jax.lax.dynamic_slice_in_dim(hits, at, nd) + hit,
                        at, 0),)
                return out

            return jax.lax.fori_loop(0, used[q], add_slot, planes)

        return jax.lax.fori_loop(0, nq, add_query, tuple(planes))

    return step


def _resident_program(rs: DenseStore, nq: int, first: bool,
                      with_hits: bool):
    return obs_device.compiled(
        "bm25_resident", (rs.v_pad, rs.ndocs_pad, nq, first, with_hits),
        lambda: resident_body(nq, first, with_hits),
        donate_argnums=() if first else tuple(range(3, 5 if with_hits
                                                    else 4)))


def resident_program_keys(rungs: tuple[Rung, ...]) -> list[tuple]:
    """Every resident step a batch that fits one of `rungs` can
    dispatch: (queries, with_hits, first)."""
    return [(rung.nq, with_hits, first)
            for rung in rungs
            for with_hits in (False, True) for first in (True, False)]


def prebuild_resident_programs(rs: DenseStore,
                               rungs: tuple[Rung, ...]) -> int:
    """Build every program of `resident_program_keys` by running it once
    on an all-padding step — first step, then the next on the planes it
    gave. The (rung, conjunction) pairs compile side by side. Returns
    how many programs this call built."""
    from concurrent.futures import ThreadPoolExecutor
    pairs: dict = {}
    for nq, with_hits, first in resident_program_keys(rungs):
        pairs.setdefault((nq, with_hits), {})[first] = _resident_program(
            rs, nq, first, with_hits)
    todo = [(nq, pair) for (nq, _h), pair in pairs.items()
            if not all(p.called for p in pair.values())]

    def run(item):
        nq, pair = item
        tids = np.zeros((nq, DENSE_SLOTS), dtype=np.int32)
        w = np.zeros((nq, DENSE_SLOTS), dtype=np.float32)
        jax.block_until_ready(
            pair[False](rs.St, tids, w, *pair[True](rs.St, tids, w)))

    built = sum(not p.called for _, pair in todo for p in pair.values())
    if todo:
        with ThreadPoolExecutor(max_workers=len(todo)) as pool:
            list(pool.map(run, todo))
    return built


def pad_k(k: int) -> int:
    """Bucket k so jit caches stay small: 10 / 100 / 1000 / next pow2."""
    for bucket in (10, 100, 1000):
        if k <= bucket:
            return bucket
    return 1 << (k - 1).bit_length()
