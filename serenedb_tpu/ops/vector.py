"""Vector kernels: k-means training, IVF cluster-probe search, MaxSim.

Reference analog: libs/iresearch/formats/ivf/ (faiss-backed k-means
centroids, cluster posting lists, SQ8, nprobe/rerank knobs; SURVEY.md §2.7)
plus FLASH-MAXSIM's dimension-tiled late-interaction kernels.

TPU re-design: the seed's `ivf_topk` computed the full Q×N distance
matrix and only *masked* by probe bitmap — nprobe saved zero FLOPs and
zero HBM. The real pipeline here scales with probed clusters, not N:

- kmeans: assignment = argmin over  ||x||² − 2·X·Cᵀ + ||c||²  tiles;
  centroid update = one-hot(assign)ᵀ @ X (another matmul).
- probe: centroid distances (one small matmul-shaped reduce) pick the
  nprobe nearest lists; a scan walks the probed lists in fixed-size
  lane chunks, gathering candidate vectors from the paged HBM region
  through the slot map and exact-rescoring them with `dist_tail_expr`.
- selection: a running (distance, row) top-k carry merged per chunk
  with a two-key `lax.sort` — exact (score desc, doc asc) tie order by
  construction, no composite-key encoding (x64 stays off).

Bit-parity contract: probe, brute oracle and cold (pool-off) paths all
reduce identical `(Qp, MC, Dp)` gathered fragments through the same
`dist_tail_expr`, so per-(query,row) distance bits match and the exact
selection makes `nprobe=lists` bit-identical to host brute force.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import device as obs_device

#: row-id pad sentinel in sort keys: dead lanes carry (+inf, _PAD_ROW)
#: so they sort behind every live row; callers filter non-finite
#: distances (matches the posting-pool _PAD_DOC idiom)
_PAD_ROW = (1 << 31) - 1


def pad_rows(a: np.ndarray, multiple: int = 8) -> np.ndarray:
    pad = (-a.shape[0]) % multiple
    if pad:
        a = np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
    return a


def _pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


# -- distance expression (THE parity-bearing fragment) -----------------------


def _chain_sum(terms) -> jax.Array:
    """Left-to-right f32 add chain over an iterator of equal-shape
    arrays. The chain is explicit in the HLO graph, so XLA cannot
    reassociate it (the `_accumulate` idiom from the posting pool), and
    it fuses into one kernel vectorized across the batch lanes. One
    backend freedom remains: instruction selection may contract a
    product feeding an add into an fma (observed on XLA:CPU even with
    fast-math off and an optimization_barrier — the machine combiner
    fires below HLO). Contraction only SKIPS a rounding, so whenever
    the chain arithmetic is exact the bits are grouping-independent;
    see `host_dist` for how the parity contract uses that."""
    acc = None
    for t in terms:
        acc = t if acc is None else acc + t
    return acc


def dist_tail_expr(x: jax.Array, q: jax.Array, metric: str) -> jax.Array:
    """Distance over the LAST axis — elementwise ops + a sequential add
    chain, never the matmul identity. Every scoring path (probe
    rescore, brute oracle, cold fallback) funnels through this one
    expression, and `host_dist` mirrors it add-for-add in numpy. The
    association order is graph-fixed, so trailing zero-padded
    dimensions are exact no-ops and batch/padding shapes never move the
    bits — that is what makes `nprobe=lists` ≡ brute-force parity hold
    per-row instead of per-launch-shape. l2 = squared L2, ip = negative
    inner product (smaller = better), cos = cosine distance."""
    d = x.shape[-1]
    if metric == "l2":
        dv = x - q
        return _chain_sum(dv[..., j] * dv[..., j] for j in range(d))
    if metric == "ip":
        return -_chain_sum(x[..., j] * q[..., j] for j in range(d))
    nx = jnp.sqrt(_chain_sum(x[..., j] * x[..., j] for j in range(d)))
    nq = jnp.sqrt(_chain_sum(q[..., j] * q[..., j] for j in range(d)))
    dot = _chain_sum(x[..., j] * q[..., j] for j in range(d))
    return 1.0 - dot / jnp.maximum(nx * nq, 1e-9)


def host_dist(x: np.ndarray, q: np.ndarray, metric: str) -> np.ndarray:
    """Numpy mirror of `dist_tail_expr`: identical elementwise ops in
    the identical left-to-right order over the last axis, all f32.
    Subtract/multiply/add/sqrt/divide are correctly rounded in both
    numpy and XLA, so the only device freedom left is fma contraction
    inside the chain (see `_chain_sum`). Contraction skips a rounding,
    so the mirror is BIT-exact whenever the chain arithmetic is exact —
    in particular for grid-quantized vectors (entries k/2^g with
    products and partial sums under 2^24 ulps), which is what the
    parity suites and the bench parity leg use. On arbitrary real data
    the mirror is exact to ≤1 ulp per distance, and the top-k ROW order
    still matches except between rows whose distances collide within
    that ulp. The `+ 0.0` canonicalizes -0.0 like the device programs."""
    x = np.asarray(x, np.float32)
    q = np.asarray(q, np.float32)
    d = x.shape[-1]

    def chain(terms):
        acc = None
        for t in terms:
            acc = t if acc is None else acc + t
        return acc

    if metric == "l2":
        dv = x - q
        return chain(dv[..., j] * dv[..., j] for j in range(d)) + \
            np.float32(0.0)
    if metric == "ip":
        return -chain(x[..., j] * q[..., j] for j in range(d)) + \
            np.float32(0.0)
    nx = np.sqrt(chain(x[..., j] * x[..., j] for j in range(d)))
    nq = np.sqrt(chain(q[..., j] * q[..., j] for j in range(d)))
    dot = chain(x[..., j] * q[..., j] for j in range(d))
    return (np.float32(1.0) -
            dot / np.maximum(nx * nq, np.float32(1e-9))) + np.float32(0.0)


def _merge_topk(best_d, best_r, d, r, kk: int):
    """Merge one chunk's (distance, row) lanes into the running top-kk
    carry: two-key `lax.sort` on (f32 distance asc, i32 row asc) — the
    PR 11 exact tie order without any composite encode (int64 would
    silently truncate with x64 off). Rows are distinct across chunks,
    so the selection is exact and chunk-order independent."""
    cd = jnp.concatenate([best_d, d], axis=1)
    cr = jnp.concatenate([best_r, r], axis=1)
    sd, sr = jax.lax.sort((cd, cr), num_keys=2)
    return sd[:, :kk], sr[:, :kk]


# -- k-means (ledger-routed; matmul identity at the backend's default
#    precision is fine here — training and assignment only choose clusters
#    (recall at nprobe < lists); no parity contract binds them to the
#    scoring expression, and no returned distance comes from them) -----------


def _sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared L2 distances (N, K) via the matmul identity."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    return x2 - 2.0 * (x @ c.T) + c2


def _kmeans_program(k: int, iters: int):
    def run(x, init):
        def step(c, _):
            d = _sq_dists(x, c)
            assign = jnp.argmin(d, axis=1)
            oh = jax.nn.one_hot(assign, k, dtype=jnp.float32)   # (N, K)
            counts = oh.sum(axis=0)                              # (K,)
            sums = jnp.einsum("nk,nd->kd", oh, x)
            new_c = sums / jnp.maximum(counts[:, None], 1.0)
            # empty clusters keep their previous centroid
            new_c = jnp.where(counts[:, None] > 0, new_c, c)
            return new_c, None

        c, _ = jax.lax.scan(step, init, None, length=iters)
        return c

    return run


def kmeans_fit(x: jax.Array, init: jax.Array, k: int,
               iters: int) -> jax.Array:
    """Lloyd's k-means on device. x: (N, D) f32 (caller passes valid
    rows only, padded by repeating real rows). Returns (k, D)
    centroids."""
    prog = obs_device.compiled(
        "vector_kmeans", (x.shape[0], x.shape[1], k, iters),
        lambda: _kmeans_program(k, iters))
    return prog(x, init)


def _assign_program():
    def run(x, centroids):
        return jnp.argmin(_sq_dists(x, centroids), axis=1).astype(jnp.int32)

    return run


def assign_clusters(x: jax.Array, centroids: jax.Array) -> jax.Array:
    prog = obs_device.compiled(
        "vector_assign", (x.shape[0], x.shape[1], centroids.shape[0]),
        lambda: _assign_program())
    return prog(x, centroids)


# -- IVF probe / brute programs ----------------------------------------------


def probe_program(metric: str, dp: int, l_real: int, nprobe: int,
                  kk: int, mc: int):
    """Builder for the cluster-probe rescore program (one jitted
    dispatch per coalesced batch). Statics name the padded geometry;
    the caller's `obs_device.compiled` key adds the array shapes.

    Inputs: region (pages, PAGE_F32) or (npos_pad, dp) f32; slotmap
    (npos_pad,) i32 logical position → region row; offsets/counts (Lp,)
    i32 per-cluster logical extents; rowids (npos_pad,) i32 (pad =
    _PAD_ROW); cents (Lp, dp) f32; queries (Qp, dp) f32; tmap/jmap
    (nchunks, mc) i32 — the host-built flattening of the (nprobe, M)
    probe grid into mc-lane chunks (jmap pad = M → dead lane). Scan
    temps stay bounded at (Qp, mc, dp) regardless of N."""

    def run(region, slotmap, offsets, counts, rowids, cents, queries,
            tmap, jmap):
        rg = region.reshape(-1, dp)
        lp = cents.shape[0]
        qd = dist_tail_expr(queries[:, None, :], cents[None, :, :],
                            metric) + 0.0
        qd = jnp.where(jnp.arange(lp)[None, :] < l_real, qd, jnp.inf)
        # top-nprobe lists; top_k breaks distance ties by lower cluster
        # id — deterministic probe sets
        _, probe = jax.lax.top_k(-qd, nprobe)                 # (Q, nprobe)
        qp = queries.shape[0]

        def step(carry, chunk):
            best_d, best_r = carry
            tm, jm = chunk                                    # (mc,)
            cl = jnp.take(probe, tm, axis=1)                  # (Q, mc)
            base = jnp.take(offsets, cl)
            cnt = jnp.take(counts, cl)
            live = jm[None, :] < cnt
            pos = jnp.where(live, base + jm[None, :], 0)
            slot = jnp.take(slotmap, pos)
            x = jnp.take(rg, slot, axis=0)                    # (Q, mc, dp)
            d = dist_tail_expr(x, queries[:, None, :], metric) + 0.0
            row = jnp.take(rowids, pos)
            d = jnp.where(live, d, jnp.inf)
            row = jnp.where(live, row, _PAD_ROW)
            return _merge_topk(best_d, best_r, d, row, kk), None

        init = (jnp.full((qp, kk), jnp.inf, jnp.float32),
                jnp.full((qp, kk), _PAD_ROW, jnp.int32))
        (best_d, best_r), _ = jax.lax.scan(step, init, (tmap, jmap))
        return best_d, best_r

    return run


# -- flat scan: the exact index (ES `index_options.type: flat`) ---------------

#: queries per dispatch the flat program is built for — the closed set a
#: flat index prebuilds (what `ops/bm25.score_rungs` is to BM25); a
#: coalesced batch is fitted to the smallest rung that holds it and
#: split past the largest
FLAT_RUNGS = (1, 8, 32)

#: rows per tile of the scan: one `dot_general` and one top-k each
FLAT_TILE = 131072


def flat_rung(n_queries: int) -> int:
    return next((r for r in FLAT_RUNGS if r >= n_queries), FLAT_RUNGS[-1])


def flat_aux_program(metric: str):
    """Per-row factor the scan needs besides the dot product, computed
    ONCE when a segment is uploaded: cos → 1 / ||x|| (0 for a zero row),
    l2 → ||x||^2, ip → 0; NaN marks a dead (NULL) row."""
    def program(vecs, live):
        sq = jnp.sum(vecs * vecs, axis=1)
        if metric == "cos":
            aux = jnp.where(sq > 0, jax.lax.rsqrt(jnp.maximum(sq, 1e-30)),
                            0.0)
        elif metric == "l2":
            aux = sq
        else:
            aux = jnp.zeros_like(sq)
        return jnp.where(live, aux, jnp.nan).astype(jnp.float32)
    return program


def _flat_dist(dot, aux, q, metric: str):
    """(Q, T) distances from the tile's dot products: cos = 1 - cosine,
    l2 = squared L2, ip = negated inner product (smaller = nearer, the
    `vec_*` functions' own senses); +inf on dead rows."""
    if metric == "cos":
        qn = jnp.sqrt(jnp.sum(q * q, axis=1, keepdims=True))
        qinv = jnp.where(qn > 0, 1.0 / jnp.maximum(qn, 1e-30), 0.0)
        d = 1.0 - dot * aux[None, :] * qinv
    elif metric == "l2":
        qsq = jnp.sum(q * q, axis=1, keepdims=True)
        d = jnp.maximum(aux[None, :] - 2.0 * dot + qsq, 0.0)
    else:
        d = -dot + aux[None, :]
    # a dead row's NaN factor, and an overflow on the way, rank last
    return jnp.where(jnp.isnan(d), jnp.inf, d)


def _tile_topk(d, base, kk: int):
    """The kk nearest of one tile, (distance asc, row asc): `lax.top_k`
    puts the lower index first among equals."""
    neg, idx = jax.lax.top_k(-d, kk)
    return -neg, (base + idx).astype(jnp.int32)


def flat_scan_program(metric: str, n: int, dim: int, kk: int,
                      tile: int = FLAT_TILE):
    """Exact top-kk over EVERY row of one resident (n, dim) float32
    segment: per row tile one `dot_general((Q, dim), (tile, dim))` at
    `Precision.HIGHEST` (float32 products: the default would lower them
    to one bfloat16 pass), the metric's distance from the factors stored
    at upload, the tile's own top-kk, and a merge into the running
    top-kk — carry first, so equal distances keep the lower row. The
    rows are read once per DISPATCH whatever Q is. A tail shorter than a
    tile is read as the LAST `tile` rows with the overlap masked, so the
    array needs no padding in either axis.

    program(vecs (n, dim), aux (n,), q (Q, dim)) → (dist (Q, kk) f32
    ascending, row (Q, kk) i32); lanes past the live rows carry
    (+inf, _PAD_ROW)."""
    tile = min(tile, n)
    nt, rem = divmod(n, tile)
    dn = (((1,), (1,)), ((), ()))

    def scan_tile(vecs, aux, q, start, first_live):
        x = jax.lax.dynamic_slice(vecs, (start, 0), (tile, dim))
        a = jax.lax.dynamic_slice(aux, (start,), (tile,))
        dot = jax.lax.dot_general(
            q, x, dn, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        d = _flat_dist(dot, a, q, metric)
        if first_live is not None:
            d = jnp.where((jnp.arange(tile) < first_live)[None, :],
                          jnp.inf, d)
        if tile < kk:
            d = jnp.pad(d, ((0, 0), (0, kk - tile)),
                        constant_values=jnp.inf)
        return _tile_topk(d, start, kk)

    def merge(best_d, best_r, d, r):
        cd = jnp.concatenate([best_d, d], axis=1)
        cr = jnp.concatenate([best_r, r], axis=1)
        neg, idx = jax.lax.top_k(-cd, kk)
        return -neg, jnp.take_along_axis(cr, idx, axis=1)

    def program(vecs, aux, q):
        nq = q.shape[0]
        best = (jnp.full((nq, kk), jnp.inf, jnp.float32),
                jnp.full((nq, kk), _PAD_ROW, jnp.int32))

        def body(i, carry):
            d, r = scan_tile(vecs, aux, q, i * tile, None)
            return merge(*carry, d, r)

        best = jax.lax.fori_loop(0, nt, body, best)
        if rem:
            d, r = scan_tile(vecs, aux, q, n - tile, tile - rem)
            best = merge(*best, d, r)
        bd, br = best
        return bd, jnp.where(jnp.isfinite(bd), br, _PAD_ROW)
    return program


def chunk_maps(nprobe: int, m: int, mc: int) -> tuple[np.ndarray,
                                                      np.ndarray]:
    """Host-built flattening of the (nprobe, M) probe grid into mc-lane
    scan chunks: tmap = probe-slot index, jmap = within-cluster logical
    position (pad lanes get jmap = m, dead against every count)."""
    total = nprobe * m
    nchunks = max(1, -(-total // mc))
    tm = np.full(nchunks * mc, 0, np.int32)
    jm = np.full(nchunks * mc, m, np.int32)
    flat = np.arange(total, dtype=np.int64)
    tm[:total] = (flat // m).astype(np.int32)
    jm[:total] = (flat % m).astype(np.int32)
    return tm.reshape(nchunks, mc), jm.reshape(nchunks, mc)


# -- MaxSim late-interaction program -----------------------------------------


def maxsim_program(dp: int, tile: int, tmax: int, kk: int, dc: int):
    """Builder for the multi-vector MaxSim scorer (FLASH-MAXSIM shape):
    docs are the 'clusters' (one token matrix each), scanned in
    dc-doc chunks with tmax-token pads; the token×query-token similarity
    accumulates dimension-tiled (`tile` dims per einsum) so the
    (B, dc, tmax, S) similarity block is the only large temp. Query
    token rows padded with zeros add exactly 0.0 to every score (max
    over live tokens of zero dots is 0) — an exact no-op. Empty/pad
    docs score -inf → key +inf → filtered by the caller. Keys merge
    through the same two-key sort carry as the IVF probe, so the
    (score desc, doc asc) contract holds here too."""

    def run(region, slotmap, offsets, counts, rowids, queries,
            dmap):
        rg = region.reshape(-1, dp)
        b, s = queries.shape[0], queries.shape[1]

        def step(carry, dchunk):
            best_k, best_r = carry
            base = jnp.take(offsets, dchunk)                  # (dc,)
            cnt = jnp.take(counts, dchunk)
            t = jnp.arange(tmax, dtype=jnp.int32)
            live = t[None, :] < cnt[:, None]                  # (dc, tmax)
            pos = jnp.where(live, base[:, None] + t[None, :], 0)
            x = jnp.take(rg, jnp.take(slotmap, pos), axis=0)  # (dc,tmax,dp)
            sim = jnp.zeros((b, dc, tmax, s), jnp.float32)
            for i in range(0, dp, tile):
                # HIGHEST: the documented scorer is f32; at the MXU's
                # default precision this dot would multiply bf16-rounded
                # operands (k-means above only picks clusters and keeps
                # the default)
                sim = sim + jnp.einsum(
                    "dtx,bsx->bdts",
                    x[..., i:i + tile], queries[..., i:i + tile],
                    precision=jax.lax.Precision.HIGHEST)
            sim = jnp.where(live[None, :, :, None], sim, -jnp.inf)
            score = jnp.sum(jnp.max(sim, axis=2), axis=2)     # (B, dc)
            key = -score + 0.0
            row = jnp.broadcast_to(jnp.take(rowids, dchunk)[None, :],
                                   (b, dc))
            return _merge_topk(best_k, best_r, key, row, kk), None

        init = (jnp.full((b, kk), jnp.inf, jnp.float32),
                jnp.full((b, kk), _PAD_ROW, jnp.int32))
        (best_k, best_r), _ = jax.lax.scan(step, init, dmap)
        return best_k, best_r

    return run


# -- host helpers -------------------------------------------------------------


def init_centroids(x: np.ndarray, k: int, seed: int = 0) -> np.ndarray:
    """k-means++-lite init on host: random distinct samples."""
    rng = np.random.default_rng(seed)
    n = len(x)
    if n >= k:
        idx = rng.choice(n, k, replace=False)
    else:
        idx = rng.choice(max(n, 1), k, replace=True)
    return np.ascontiguousarray(x[idx], dtype=np.float32)


def sq8_quantize(x: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-dimension affine SQ8: x ≈ lo + (q/255)·(hi−lo), q ∈ uint8
    (reference: the IVF scalar quantizer, ivf_writer.hpp)."""
    lo = x.min(axis=0)
    hi = x.max(axis=0)
    scale = np.where(hi > lo, hi - lo, 1.0)
    q = np.clip(np.round((x - lo) / scale * 255.0), 0, 255).astype(np.uint8)
    return q, lo.astype(np.float32), scale.astype(np.float32)


def sq8_dequantize(q: np.ndarray, lo: np.ndarray,
                   scale: np.ndarray) -> np.ndarray:
    return (lo + q.astype(np.float32) / 255.0 * scale).astype(np.float32)


def sq8_roundtrip(x: np.ndarray) -> np.ndarray:
    """Quantize+dequantize: the f32 values the device will score with."""
    q, lo, scale = sq8_quantize(x)
    return sq8_dequantize(q, lo, scale)
