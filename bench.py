"""Benchmark shapes and a sequential harness.

    python bench.py [shape ...]      # all shapes, or the named ones

The parent never imports jax. It runs each shape in its own child
process (`python bench.py --shape <name>`), one after another — an
accelerator belongs to one process at a time — prints one JSON line per
shape and a final summary line, and exits non-zero when any shape
failed or when a device shape ran anywhere but a TPU. There is no
fallback: a shape that cannot run where it is meant to run is an error.

Three classes of shape:
- device shapes (default): their numbers are device numbers, so the
  child must report `platform: tpu`;
- HOST_SHAPES: never dispatch to a device; reported as `platform: host`;
- VIRTUAL_MESH_SHAPES: parity + dispatch counts of the in-program
  multi-chip combine on a 4-device virtual CPU mesh, by construction
  not a device timing.

Cold vs warm: for the analytics shapes (q1, hits) the headline number is
the COLD device run — first dispatch after data lands in the engine,
including host→HBM upload, tile compression and key factorization —
because BASELINE.md's ClickBench target says "cold". The persistent XLA
compilation cache (utils/backend.configure_compile_cache) keeps the
*binary* warm across processes, mirroring the reference's cold runs with
a prebuilt release build (scripts/perf/run_hits_perf.sh). Warm numbers
are reported alongside in detail.

`BENCH_LEDGER.json` is a frozen record of earlier host/CPU-backend runs;
nothing here reads or writes it.
"""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys
import threading
import time

METRIC = ("geomean device-vs-CPU speedup (ClickBench Q1 agg, ClickBench "
          "Q5-Q20 hash GROUP BY, BM25 top-10 QPS); result parity asserted")


# ---------------------------------------------------------------- shapes

def bench_q1() -> float:
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(0)
    n = 10_000_000
    db = Database()
    c = db.connect()
    batch = Batch.from_pydict({
        "adv": Column.from_numpy(
            rng.choice(np.array([0, 0, 0, 0, 1, 2, 3], dtype=np.int32), n)),
        "region": Column.from_numpy(rng.integers(0, 200, n).astype(np.int32)),
        "x": Column.from_numpy(
            rng.integers(0, 100000, n).astype(np.int32)),
    })
    db.schemas["main"].tables["hits"] = MemTable("hits", batch)
    queries = [
        "SELECT count(*) FROM hits WHERE adv <> 0",
        "SELECT count(*), sum(x) FROM hits WHERE adv <> 0 AND x < 90000",
        "SELECT region, count(*), sum(x) FROM hits GROUP BY region",
    ]

    def run_all():
        return [tuple(c.execute(q).rows()) for q in queries]

    c.execute("SET serene_device = 'cpu'")
    run_all()
    t0 = time.perf_counter()
    cpu_res = run_all()
    t_cpu = time.perf_counter() - t0

    c.execute("SET serene_device = 'tpu'")
    t0 = time.perf_counter()
    dev_cold = run_all()  # upload + (cached-)compile + first dispatch
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev_res = run_all()
    t_dev = time.perf_counter() - t0
    assert cpu_res == dev_res == dev_cold, \
        "device/CPU result mismatch in Q1 bench"
    _EXTRA["cold_s"] = round(t_cold, 3)
    _EXTRA["warm_s"] = round(t_dev, 3)
    _EXTRA["cpu_s"] = round(t_cpu, 3)
    _EXTRA["speedup_warm"] = round(t_cpu / t_dev, 3)
    return t_cpu / t_cold


def bench_hits() -> float:
    """ClickBench Q5–Q20-style hash GROUP BY aggregates over a faithful
    10M-row hits generator: full-range int64 UserID (zipf-skewed user
    activity), skewed RegionID, mostly-zero AdvEngineID, mostly-empty
    SearchPhrase, SearchEngineID. Exercises direct-coded, dictionary and
    host-factorized device GROUP BY paths. ORDER BY gets deterministic
    tie-breaks so result parity is assertable (reference harness:
    scripts/perf/run_hits_perf.sh)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(3)
    n = 10_000_000
    n_users = 500_000
    user_hashes = rng.integers(0, 1 << 62, n_users, dtype=np.int64)
    uid = user_hashes[rng.zipf(1.4, n).astype(np.int64) % n_users]
    region = (rng.zipf(1.5, n) % 9000).astype(np.int32)
    adv = np.where(rng.random(n) < 0.96, 0,
                   rng.integers(1, 64, n)).astype(np.int32)
    n_phrases = 100_000
    phrase_pool = np.asarray([""] + [f"phrase {i}" for i in range(n_phrases)],
                             dtype=object)
    pid = np.where(rng.random(n) < 0.7, 0,
                   1 + rng.zipf(1.3, n) % n_phrases).astype(np.int64)
    seid = (rng.zipf(1.6, n) % 100).astype(np.int32)
    width = rng.integers(0, 4000, n).astype(np.int32)

    db = Database()
    c = db.connect()
    batch = Batch.from_pydict({
        "UserID": Column.from_numpy(uid),
        "RegionID": Column.from_numpy(region),
        "AdvEngineID": Column.from_numpy(adv),
        "SearchPhrase": Column.from_numpy(phrase_pool[pid]),
        "SearchEngineID": Column.from_numpy(seid),
        "ResolutionWidth": Column.from_numpy(width),
    })
    db.schemas["main"].tables["hits"] = MemTable("hits", batch)
    queries = [
        # Q8: low-card direct-coded key
        "SELECT AdvEngineID, count(*) AS c FROM hits WHERE AdvEngineID <> 0 "
        "GROUP BY AdvEngineID ORDER BY c DESC, AdvEngineID",
        # Q10-shape (no distinct): region rollup
        "SELECT RegionID, sum(AdvEngineID), count(*) AS c, "
        "avg(ResolutionWidth) FROM hits GROUP BY RegionID "
        "ORDER BY c DESC, RegionID LIMIT 10",
        # Q13: dictionary string key
        "SELECT SearchPhrase, count(*) AS c FROM hits "
        "WHERE SearchPhrase <> '' GROUP BY SearchPhrase "
        "ORDER BY c DESC, SearchPhrase LIMIT 10",
        # Q15: composite key beyond the direct code space → factorize
        "SELECT SearchEngineID, SearchPhrase, count(*) AS c FROM hits "
        "WHERE SearchPhrase <> '' GROUP BY SearchEngineID, SearchPhrase "
        "ORDER BY c DESC, SearchEngineID, SearchPhrase LIMIT 10",
        # Q16: full-range int64 key → factorize
        "SELECT UserID, count(*) AS c FROM hits GROUP BY UserID "
        "ORDER BY c DESC, UserID LIMIT 10",
        # Q17: wide composite key → factorize
        "SELECT UserID, SearchPhrase, count(*) AS c FROM hits "
        "GROUP BY UserID, SearchPhrase ORDER BY c DESC, UserID, "
        "SearchPhrase LIMIT 10",
    ]

    def run_all():
        return [tuple(c.execute(q).rows()) for q in queries]

    c.execute("SET serene_device = 'cpu'")
    run_all()
    t0 = time.perf_counter()
    cpu_res = run_all()
    t_cpu = time.perf_counter() - t0

    c.execute("SET serene_device = 'tpu'")
    t0 = time.perf_counter()
    dev_cold = run_all()   # compile + upload + cold factorize — reported
    t_dev_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev_res = run_all()
    t_dev = time.perf_counter() - t0
    assert cpu_res == dev_res == dev_cold, \
        "device/CPU result mismatch in hits bench"
    # HBM working set after the run: compressed tiles (frame-of-reference
    # uint8/16) vs the raw-int32/f32 equivalent
    t = db.schemas["main"].tables["hits"]
    comp = raw = 0
    for cname in t.column_names:
        dc = t._device_cache.get(cname)
        if dc is None:
            continue
        dc = dc[1]
        comp += int(dc.data.size) * dc.data.dtype.itemsize
        raw += int(dc.data.size) * 4
    _EXTRA["hbm_bytes_compressed"] = comp
    _EXTRA["hbm_bytes_raw_equiv"] = raw
    _EXTRA["cold_s"] = round(t_dev_cold, 3)
    _EXTRA["warm_s"] = round(t_dev, 3)
    _EXTRA["cpu_s"] = round(t_cpu, 3)
    _EXTRA["speedup_warm"] = round(t_cpu / t_dev, 3)
    return t_cpu / t_dev_cold


def bench_bm25() -> float:
    import numpy as np

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import SegmentSearcher
    from serenedb_tpu.search.segment import build_field_index

    rng = np.random.default_rng(1)
    vocab = [f"w{i}" for i in range(2000)]
    zipf = rng.zipf(1.3, size=4_000_000) % len(vocab)
    n_docs = 100_000
    lens = rng.integers(8, 40, n_docs)
    docs = []
    pos = 0
    for ln in lens:
        docs.append(" ".join(vocab[z] for z in zipf[pos:pos + ln]))
        pos += ln
    an = get_analyzer("simple")
    fi = build_field_index(docs, an)
    searcher = SegmentSearcher(fi, an, n_docs)

    # benchmark-game-style query set: single terms across the frequency
    # spectrum, 2-term disjunctions pairing common with rare terms (the
    # shape WAND/MaxScore exists for), 2-term conjunctions (256 queries)
    idxs = [1 + 3 * i for i in range(128)]
    qterms = [vocab[i] for i in idxs]
    queries = ([parse_query(t, an) for t in qterms] +
               [parse_query(f"{a} | {b}", an)
                for a, b in zip(qterms[:64], qterms[64:][::-1])] +
               [parse_query(f"{a} & {b}", an)
                for a, b in zip(qterms[1::2], qterms[::2])])

    # warmup/compile — the QPS regime batches queries per dispatch
    out_dev = searcher.topk_batch(queries, 10)
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        searcher.topk_batch(queries, 10)
    t_dev = time.perf_counter() - t0
    qps_dev = reps * len(queries) / t_dev

    # CPU baseline: block-max WAND + MaxScore (cpu_topk_wand) — the same
    # optimization family the reference's CPU engine runs
    # (search/block_disjunction.hpp), NOT the exhaustive scorer, so the
    # reported ratio survives scrutiny. Warm pass first: plan/bucket
    # caches mirror the device path's compile+upload warmup.
    shapes = [searcher._query_shape(q) for q in queries]
    for (tids, req, _, _) in shapes:
        searcher.cpu_topk_wand(tids, 10, require_all=req)
    t0 = time.perf_counter()
    cpu_out = []
    for (tids, req, _, _) in shapes:
        cpu_out.append(searcher.cpu_topk_wand(tids, 10, require_all=req))
    t_cpu = time.perf_counter() - t0
    qps_cpu = len(queries) / t_cpu
    # top-10 parity device vs CPU on a spanning sample
    for si in range(0, len(queries), 7):
        dev_s, dev_d = out_dev[si]
        ref_s, ref_d = cpu_out[si]
        assert len(dev_s) == len(ref_s), \
            f"query {si}: {len(dev_s)} vs {len(ref_s)} results"
        np.testing.assert_allclose(dev_s, ref_s, rtol=2e-3, atol=1e-3)
        for j, (dd, rd) in enumerate(zip(dev_d.tolist(), ref_d.tolist())):
            if dd != rd:  # doc ids may differ only on score ties
                assert abs(float(ref_s[j]) - float(dev_s[j])) < 1e-3, \
                    f"query {si} rank {j}: doc {dd} != {rd}"
    return qps_dev / qps_cpu


def bench_bm25_1m() -> float:
    """BM25 top-10 at 1M docs (an MS-MARCO-scale step): the query batch
    auto-splits so the device accumulator never exceeds the HBM cap, and
    WAND/MaxScore pruning keeps per-dispatch work bounded. Measures QPS
    against the exhaustive CPU scorer on a query sample; asserts top-10
    parity."""
    import numpy as np

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import SegmentSearcher
    from serenedb_tpu.search.segment import build_field_index

    rng = np.random.default_rng(5)
    n_docs = 1_000_000
    vocab = np.asarray([f"w{i}" for i in range(30_000)], dtype=object)
    lens = rng.integers(8, 40, n_docs)
    zipf = rng.zipf(1.25, size=int(lens.sum())) % len(vocab)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    words = vocab[zipf]
    docs = [" ".join(words[bounds[i]:bounds[i + 1]])
            for i in range(n_docs)]
    an = get_analyzer("simple")
    fi = build_field_index(docs, an)
    del docs, words, zipf
    searcher = SegmentSearcher(fi, an, n_docs)

    idxs = [1 + 9 * i for i in range(64)]
    qterms = [f"w{i}" for i in idxs]
    queries = ([parse_query(t, an) for t in qterms] +
               [parse_query(f"{a} | {b}", an)
                for a, b in zip(qterms[:32], qterms[32:][::-1])] +
               [parse_query(f"{a} & {b}", an)
                for a, b in zip(qterms[1::2], qterms[::2])])

    out_dev = searcher.topk_batch(queries, 10)  # warmup/compile
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        searcher.topk_batch(queries, 10)
    qps_dev = reps * len(queries) / (time.perf_counter() - t0)

    # WAND/MaxScore CPU reference (warm) on a spanning sample + parity
    sample = list(range(0, len(queries), 8))
    shapes = [searcher._query_shape(queries[si]) for si in sample]
    for (tids, req, _, _) in shapes:
        searcher.cpu_topk_wand(tids, 10, require_all=req)
    t0 = time.perf_counter()
    cpu_out = [searcher.cpu_topk_wand(tids, 10, require_all=req)
               for (tids, req, _, _) in shapes]
    qps_cpu = len(sample) / (time.perf_counter() - t0)
    for pos, si in enumerate(sample):
        ref_s, ref_d = cpu_out[pos]
        dev_s, dev_d = out_dev[si]
        assert len(dev_s) == min(10, len(ref_s)), \
            f"query {si}: {len(dev_s)} results, expected {min(10, len(ref_s))}"
        np.testing.assert_allclose(dev_s, ref_s[:len(dev_s)],
                                   rtol=2e-3, atol=1e-3)
        # doc ids must agree except where scores tie at the boundary
        for j, (dd, rd) in enumerate(zip(dev_d.tolist(), ref_d.tolist())):
            if dd != rd:
                assert abs(float(ref_s[j]) - float(dev_s[j])) < 1e-3, \
                    f"query {si} rank {j}: doc {dd} != {rd}"
    return qps_dev / qps_cpu


def _synth_posting_index(n_docs: int, vocab: int, total_postings: int,
                         seed: int):
    """Build a FieldIndex directly from a synthetic posting distribution
    (vectorized — no string tokenization; this shape measures scoring QPS,
    not indexing). Term document-frequencies follow a zipf law, tfs are
    small-integer zipf, norms are the consistent per-doc tf sums."""
    import numpy as np

    from serenedb_tpu.search.segment import FieldIndex, _add_block_max

    rng = np.random.default_rng(seed)
    # zipf df profile scaled to the posting budget
    raw = 1.0 / np.arange(1, vocab + 1) ** 0.9
    df_target = np.maximum((raw / raw.sum() * total_postings), 1.0)
    df_target = np.minimum(df_target, n_docs * 0.8).astype(np.int64)
    terms_rep = np.repeat(np.arange(vocab, dtype=np.int64), df_target)
    docs_rnd = rng.integers(0, n_docs, len(terms_rep), dtype=np.int64)
    keys = terms_rep * n_docs + docs_rnd
    keys = np.unique(keys)   # sorted by (term, doc); drops dup samples
    post_terms = (keys // n_docs).astype(np.int64)
    post_docs = (keys % n_docs).astype(np.int32)
    post_tfs = np.minimum(rng.zipf(1.7, len(keys)), 64).astype(np.int32)
    doc_freq = np.bincount(post_terms, minlength=vocab).astype(np.int32)
    offsets = np.zeros(vocab + 1, dtype=np.int64)
    np.cumsum(doc_freq, out=offsets[1:])
    norms = np.bincount(post_docs, weights=post_tfs,
                        minlength=n_docs).astype(np.int32)
    fi = FieldIndex(
        terms=np.asarray([f"w{i:07d}" for i in range(vocab)], dtype=object),
        doc_freq=doc_freq,
        offsets=offsets,
        post_docs=post_docs,
        post_tfs=post_tfs,
        pos_offsets=np.zeros(len(post_docs) + 1, dtype=np.int64),
        positions=np.empty(0, dtype=np.int32),
        norms=norms,
        block_max_tf=np.empty(0, dtype=np.int32),
        block_offsets=np.zeros(vocab + 1, dtype=np.int64),
        total_tokens=int(post_tfs.sum()),
    )
    _add_block_max(fi)
    return fi


def bench_bm25_8m() -> float:
    """BM25 top-10 at 8M docs — MS-MARCO scale (8.8M passages). Proves the
    HBM-capped query splitting + WAND planning hold at target size; CPU
    baseline is the WAND/MaxScore host scorer; asserts top-10 parity."""
    import numpy as np

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import SegmentSearcher

    n_docs = 8_000_000
    vocab = 200_000
    fi = _synth_posting_index(n_docs, vocab, 120_000_000, seed=9)
    an = get_analyzer("simple")
    searcher = SegmentSearcher(fi, an, n_docs)

    idxs = [1 + 97 * i for i in range(48)]
    qterms = [f"w{i:07d}" for i in idxs]
    queries = ([parse_query(t, an) for t in qterms] +
               [parse_query(f"{a} | {b}", an)
                for a, b in zip(qterms[:24], qterms[24:][::-1])] +
               [parse_query(f"{a} & {b}", an)
                for a, b in zip(qterms[1::2], qterms[::2])])

    out_dev = searcher.topk_batch(queries, 10)  # warmup/compile
    store = searcher._device_store()
    _EXTRA["hbm_tiles_mb"] = round(store.hbm_bytes / (1 << 20), 1)
    _EXTRA["hbm_raw_equiv_mb"] = round(
        store.hbm_bytes_raw_equiv / (1 << 20), 1)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        searcher.topk_batch(queries, 10)
    qps_dev = reps * len(queries) / (time.perf_counter() - t0)

    sample = list(range(0, len(queries), 6))
    shapes = [searcher._query_shape(queries[si]) for si in sample]
    for (tids, req, _, _) in shapes:
        searcher.cpu_topk_wand(tids, 10, require_all=req)
    t0 = time.perf_counter()
    cpu_out = [searcher.cpu_topk_wand(tids, 10, require_all=req)
               for (tids, req, _, _) in shapes]
    qps_cpu = len(sample) / (time.perf_counter() - t0)
    for pos, si in enumerate(sample):
        ref_s, ref_d = cpu_out[pos]
        dev_s, dev_d = out_dev[si]
        assert len(dev_s) == min(10, len(ref_s)), \
            f"query {si}: {len(dev_s)} results, expected {min(10, len(ref_s))}"
        np.testing.assert_allclose(dev_s, ref_s[:len(dev_s)],
                                   rtol=2e-3, atol=1e-3)
        for j, (dd, rd) in enumerate(zip(dev_d.tolist(), ref_d.tolist())):
            if dd != rd:
                assert abs(float(ref_s[j]) - float(dev_s[j])) < 1e-3, \
                    f"query {si} rank {j}: doc {dd} != {rd}"
    return qps_dev / qps_cpu


def bench_ingest() -> float:
    """Production streaming-ingest shape (ISSUE 18): (a) raw parallel
    analysis MB/s vs the serial oracle, bit-identity asserted; (b)
    sustained END-TO-END engine ingest — MB/s + docs/s under 1/4/8
    concurrent writers WITH concurrent readers against a durable db
    (WAL group commit + the maintenance ticker live), read p99 during
    ingest recorded per writer count; (c) read p99 under background vs
    foreground segment maintenance — the headline HTAP number; (d)
    relational + search results bit-identical with parallel ingest
    on/off. Returns the raw-analysis speedup (parallel/serial); the
    scaling assert fires only on multi-core hosts (the PR 5/10 noise
    lesson), everything else is recorded in extras."""
    import tempfile
    import threading

    import numpy as np

    from serenedb_tpu.engine import Database
    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.segment import (build_field_index,
                                             build_field_index_auto)
    from serenedb_tpu.utils.config import REGISTRY

    n_cores = os.cpu_count() or 1
    _EXTRA["threads"] = n_cores

    # ---- (a) raw parallel analysis vs the serial oracle --------------
    rng = np.random.default_rng(7)
    vocab = np.asarray([f"w{i}" for i in range(50_000)], dtype=object)
    n_docs = 60_000
    lens = rng.integers(40, 160, n_docs)
    zipf = rng.zipf(1.2, size=int(lens.sum())) % len(vocab)
    bounds = np.concatenate([[0], np.cumsum(lens)])
    words = vocab[zipf]
    docs = [" ".join(words[bounds[i]:bounds[i + 1]]) for i in range(n_docs)]
    del words, zipf
    mb = sum(len(d) for d in docs) / (1 << 20)
    an = get_analyzer("simple")

    REGISTRY.set_global("serene_parallel_ingest", False)
    t0 = time.perf_counter()
    fi_ser = build_field_index(list(docs), an)
    t_ser = time.perf_counter() - t0
    REGISTRY.set_global("serene_parallel_ingest", True)
    REGISTRY.set_global("serene_workers", n_cores)
    t0 = time.perf_counter()
    fi_par = build_field_index_auto(list(docs), an)
    t_par = time.perf_counter() - t0
    # bit-identity: the deterministic merge must reproduce the serial
    # build exactly, not just approximately
    import numpy.testing as npt
    assert [str(t) for t in fi_ser.terms] == [str(t) for t in fi_par.terms]
    for f in ("doc_freq", "offsets", "post_docs", "post_tfs",
              "pos_offsets", "positions", "norms", "block_max_tf",
              "block_offsets"):
        npt.assert_array_equal(getattr(fi_ser, f), getattr(fi_par, f), f)
    assert fi_ser.total_tokens == fi_par.total_tokens
    _EXTRA["mb"] = round(mb, 1)
    _EXTRA["mbps_1t"] = round(mb / t_ser, 1)
    _EXTRA["mbps_mt"] = round(mb / t_par, 1)
    del fi_ser, fi_par

    # ---- (b) end-to-end writers × readers against a durable db -------
    body = [" ".join(f"w{int(x)}" for x in rng.integers(0, 3000, 14))
            for _ in range(400)]

    def _stream(db, n_writers, total_docs, batch=50):
        """Insert total_docs across n_writers threads while 2 readers
        hammer search queries; returns (seconds, read latencies ms)."""
        stmts = []
        for s in range(0, total_docs, batch):
            vals = ", ".join(
                f"({s + j}, '{body[(s + j) % len(body)]}')"
                for j in range(min(batch, total_docs - s)))
            stmts.append(f"INSERT INTO docs VALUES {vals}")
        nbytes = sum(len(body[i % len(body)]) for i in range(total_docs))
        cursor = {"i": 0}
        lock = threading.Lock()
        stop = threading.Event()
        lat_ms, errs = [], []

        def writer():
            c = db.connect()
            try:
                while True:
                    with lock:
                        i = cursor["i"]
                        cursor["i"] += 1
                    if i >= len(stmts):
                        return
                    c.execute(stmts[i])
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def reader():
            c = db.connect()
            while not stop.is_set():
                t0 = time.perf_counter()
                c.execute("SELECT count(*) FROM docs WHERE body @@ 'w1'")
                c.execute("SELECT id, bm25(body) AS s FROM docs "
                          "WHERE body @@ 'w7' ORDER BY s DESC, id LIMIT 10")
                lat_ms.append((time.perf_counter() - t0) * 1e3)

        rs = [threading.Thread(target=reader, daemon=True)
              for _ in range(2)]
        ws = [threading.Thread(target=writer) for _ in range(n_writers)]
        t0 = time.perf_counter()
        for t in rs + ws:
            t.start()
        for t in ws:
            t.join()
        dt = time.perf_counter() - t0
        stop.set()
        for t in rs:
            t.join(timeout=30)
        if errs:
            raise errs[0]
        return dt, nbytes, lat_ms

    def _fresh_db(tmp, tag):
        d = Database(os.path.join(tmp, tag))
        c = d.connect()
        c.execute("CREATE TABLE docs (id INT, body TEXT)")
        c.execute(f"INSERT INTO docs VALUES (-1, '{body[0]}')")
        c.execute("CREATE INDEX ON docs USING inverted (body)")
        return d

    curve = {}
    with tempfile.TemporaryDirectory() as tmp:
        for w in (1, 4, 8):
            db = _fresh_db(tmp, f"w{w}")
            dt, nbytes, lat = _stream(db, w, 3000)
            curve[str(w)] = {
                "docs_per_s": round(3000 / dt, 1),
                "mbps": round(nbytes / (1 << 20) / dt, 2),
                "read_p99_ms": round(float(np.percentile(lat, 99)), 2)
                if lat else None,
                "reads": len(lat)}
            db.close()

        # ---- (c) read p99: background vs foreground maintenance ------
        p99 = {}
        for mode, bg in (("bg", True), ("fg", False)):
            REGISTRY.set_global("serene_background_merge", bg)
            REGISTRY.set_global("serene_max_segments", 4)
            db = _fresh_db(tmp, mode)
            _, _, lat = _stream(db, 4, 3000)
            p99[mode] = round(float(np.percentile(lat, 99)), 2) \
                if lat else None
            db.close()
        REGISTRY.set_global("serene_background_merge", True)
        REGISTRY.set_global("serene_max_segments", 8)
    _EXTRA["writers_curve"] = curve
    _EXTRA["read_p99_bg_ms"] = p99["bg"]
    _EXTRA["read_p99_fg_ms"] = p99["fg"]

    # ---- (d) end-to-end parity: parallel ingest on vs off ------------
    REGISTRY.set_global("serene_ingest_chunk_docs", 64)
    states = {}
    for on in (False, True):
        REGISTRY.set_global("serene_parallel_ingest", on)
        db = Database()
        c = db.connect()
        c.execute("CREATE TABLE docs (id INT, body TEXT)")
        for s in range(0, 2000, 100):
            vals = ", ".join(f"({s + j}, '{body[(s + j) % len(body)]}')"
                             for j in range(100))
            c.execute(f"INSERT INTO docs VALUES {vals}")
        c.execute("CREATE INDEX ON docs USING inverted (body)")
        states[on] = (
            c.execute("SELECT count(*) FROM docs WHERE body @@ 'w1'"
                      ).scalar(),
            c.execute("SELECT id, bm25(body) AS s FROM docs "
                      "WHERE body @@ 'w7' ORDER BY s DESC, id LIMIT 20"
                      ).rows(),
            c.execute("SELECT id % 7, count(*) FROM docs "
                      "WHERE body @@ 'w2 | w3' GROUP BY id % 7 "
                      "ORDER BY 1").rows())
    assert states[False] == states[True], "parallel-ingest parity broke"
    REGISTRY.set_global("serene_ingest_chunk_docs", 4096)

    ratio = t_ser / t_par
    if n_cores >= 2:
        assert ratio > 1.3, \
            f"parallel ingest does not scale: {ratio:.2f}x on {n_cores} cores"
    return ratio


def bench_host_agg() -> float:
    """Host morsel-parallel hash-GROUP-BY scaling (reference: DuckDB's
    morsel-driven pipeline workers; ISSUE 1 tentpole): one
    Scan→Filter→GroupBy shape through the engine with the device path
    disabled, at serene_workers=1 vs all cores. Returns the scaling
    ratio t_1t/t_mt; extras carry the full worker→seconds curve so the
    ledger shows the curve, not a flat 1t≈mt. Results must be
    bit-identical across worker counts (asserted)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    n_cores = os.cpu_count() or 1
    rng = np.random.default_rng(13)
    n = 6_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE hits (k INT, v BIGINT, f DOUBLE)")
    batch = Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 1000, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64)),
        "f": Column.from_numpy(rng.normal(size=n)),
    })
    db.schemas["main"].tables["hits"] = MemTable("hits", batch)
    c.execute("SET serene_device = 'cpu'")
    q = ("SELECT k, count(*), sum(v), min(f), max(f), avg(f), stddev(f) "
         "FROM hits WHERE v % 7 <> 0 GROUP BY k")

    workers = sorted({1, 2, n_cores} - {0})
    workers = [w for w in workers if w <= n_cores]
    curve: dict[str, float] = {}
    results: dict[int, list] = {}
    for w in workers:
        c.execute(f"SET serene_workers = {w}")
        results[w] = c.execute(q).rows()      # warm + correctness capture
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            c.execute(q)
        curve[str(w)] = round((time.perf_counter() - t0) / reps, 4)
    for w in workers[1:]:
        assert results[w] == results[workers[0]], \
            f"workers={w} diverged from workers=1"
    _EXTRA["rows"] = n
    _EXTRA["threads"] = n_cores
    _EXTRA["curve_s"] = curve
    _EXTRA["t_1t_s"] = curve[str(workers[0])]
    _EXTRA["t_mt_s"] = curve[str(workers[-1])]
    return curve[str(workers[0])] / curve[str(workers[-1])]


def bench_filter_scan() -> float:
    """Zone-map skip-scan (ISSUE 2 tentpole): one selective-filter
    aggregate over a position-clustered column at selectivities 100%,
    10%, 1%, 0.1% with `serene_zonemap` on vs off. Returns the off/on
    speedup at 1% selectivity; extras carry the full
    selectivity→seconds curve for both settings. Results must be
    bit-identical on/off (asserted), and 100% selectivity must not
    regress (all-match blocks skip predicate evaluation, so the on path
    is never slower than off)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(17)
    n = 6_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE fs (ts BIGINT, v BIGINT, f DOUBLE)")
    batch = Batch.from_pydict({
        # clustered scan axis (ingest order / time): the realistic shape
        # zone maps exist for
        "ts": Column.from_numpy(np.arange(n, dtype=np.int64)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64)),
        "f": Column.from_numpy(rng.normal(size=n)),
    })
    db.schemas["main"].tables["fs"] = MemTable("fs", batch)
    c.execute("SET serene_device = 'cpu'")
    c.execute("SET serene_morsel_rows = 65536")   # ~92 prunable blocks
    selectivities = [1.0, 0.1, 0.01, 0.001]
    curve: dict[str, dict[str, float]] = {}
    reps = 3
    for sel in selectivities:
        cut = int(n * sel)
        q = (f"SELECT count(*), sum(v), max(f) FROM fs "
             f"WHERE ts < {cut}")
        entry: dict[str, float] = {}
        rows = {}
        for zm in ("on", "off"):
            c.execute(f"SET serene_zonemap = {zm}")
            rows[zm] = repr(c.execute(q).rows())    # warm + correctness
            t0 = time.perf_counter()
            for _ in range(reps):
                c.execute(q)
            entry[zm] = round((time.perf_counter() - t0) / reps, 5)
        assert rows["on"] == rows["off"], f"zonemap diverged at sel={sel}"
        curve[str(sel)] = entry
    _EXTRA["rows"] = n
    _EXTRA["curve_s"] = curve
    speedup_1pct = curve["0.01"]["off"] / curve["0.01"]["on"]
    _EXTRA["speedup_0.1pct"] = round(
        curve["0.001"]["off"] / curve["0.001"]["on"], 2)
    _EXTRA["full_scan_ratio"] = round(
        curve["1.0"]["on"] / curve["1.0"]["off"], 3)
    assert speedup_1pct >= 3.0, \
        f"zone maps under-deliver: {speedup_1pct:.2f}x at 1% selectivity"
    assert curve["1.0"]["on"] <= curve["1.0"]["off"] * 1.25, \
        "zone maps regress the 100%-selectivity scan"
    return speedup_1pct


def bench_join() -> float:
    """Vectorized parallel hash join vs the legacy row-tuple join
    (ISSUE 3 tentpole): one inner equi-join aggregate through the engine
    at build×probe shapes (100k×100k, 1M×1M) × probe-hit selectivity
    (100%, 10%, 1%), `serene_join_vectorized` on vs off. Build keys are
    a permutation of [0, nb) and probe keys draw uniformly from
    [0, nb/sel), so a `sel` fraction of probe rows finds exactly one
    partner and the probe side is unclustered (zone maps can't prune —
    this measures the matching tier, not the join filter). Returns the
    legacy/vectorized speedup at 1M×1M 10% selectivity; extras carry the
    whole curve. Results must be bit-identical (asserted)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(23)
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE p (k BIGINT, v BIGINT)")
    c.execute("CREATE TABLE b (k BIGINT, w BIGINT)")
    c.execute("SET serene_device = 'cpu'")
    q = "SELECT count(*), sum(v+w) FROM p JOIN b ON p.k = b.k"
    curve: dict[str, dict[str, float]] = {}
    headline = None
    for nb, npr in ((100_000, 100_000), (1_000_000, 1_000_000)):
        for sel in (1.0, 0.1, 0.01):
            keyspace = int(nb / sel)
            db.schemas["main"].tables["b"] = MemTable("b", Batch.from_pydict({
                "k": Column.from_numpy(
                    rng.permutation(np.arange(nb, dtype=np.int64))),
                "w": Column.from_numpy(
                    rng.integers(0, 100, nb, dtype=np.int64))}))
            db.schemas["main"].tables["p"] = MemTable("p", Batch.from_pydict({
                "k": Column.from_numpy(
                    rng.integers(0, keyspace, npr, dtype=np.int64)),
                "v": Column.from_numpy(
                    rng.integers(0, 100, npr, dtype=np.int64))}))
            c.execute("SET serene_join_vectorized = on")
            rows_vec = c.execute(q).rows()     # warm + correctness capture
            reps = 2
            t0 = time.perf_counter()
            for _ in range(reps):
                c.execute(q)
            t_vec = (time.perf_counter() - t0) / reps
            c.execute("SET serene_join_vectorized = off")
            t0 = time.perf_counter()
            rows_leg = c.execute(q).rows()     # legacy is slow: 1 reps
            t_leg = time.perf_counter() - t0
            assert rows_vec == rows_leg, \
                f"vectorized join diverged at {nb}x{npr} sel={sel}"
            entry = {"vec": round(t_vec, 4), "legacy": round(t_leg, 4),
                     "speedup": round(t_leg / t_vec, 2)}
            curve[f"{nb}x{npr}@{sel}"] = entry
            if (nb, npr, sel) == (1_000_000, 1_000_000, 0.1):
                headline = t_leg / t_vec
    _EXTRA["curve"] = curve
    _EXTRA["speedup_1m_100pct"] = curve["1000000x1000000@1.0"]["speedup"]
    _EXTRA["speedup_1m_1pct"] = curve["1000000x1000000@0.01"]["speedup"]
    assert headline >= 5.0, \
        f"vectorized join under-delivers: {headline:.2f}x at 1Mx1M"
    return headline


def bench_profile_overhead() -> float:
    """Profiler overhead budget (ISSUE 4, <3%): the host_agg filtered
    parallel aggregate plus the vectorized join at 1M rows, with
    `serene_profile` on vs off. Per-batch span stamps and morsel stage
    clocks are the only difference; results are asserted bit-identical.
    Returns t_off/t_on (≈1.0; 0.97 ⇔ 3% overhead) so the ledger's
    "faster is better" convention holds; extras carry the measured
    overhead percentage per query shape. Single-digit-percent deltas
    drown in scheduler noise under naive A/B timing, so executions
    alternate on/off pairwise and the overhead is a ratio of per-mode
    MEDIANS — order and drift hit both modes equally."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(31)
    n = 1_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE po (k INT, v BIGINT)")
    c.execute("CREATE TABLE pb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["po"] = MemTable("po", Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 1000, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64))}))
    db.schemas["main"].tables["pb"] = MemTable("pb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(n, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, n, dtype=np.int64))}))
    c.execute("SET serene_device = 'cpu'")
    queries = {
        "host_agg": ("SELECT k, count(*), sum(v) FROM po "
                     "WHERE v % 7 <> 0 GROUP BY k"),
        "join": ("SELECT count(*), sum(v + w) FROM po "
                 "JOIN pb ON po.v = pb.k"),
    }
    import statistics
    pairs = 7
    detail: dict[str, dict] = {}
    t_on_total = t_off_total = 0.0
    for name, q in queries.items():
        rows = {}
        samples: dict[str, list[float]] = {"on": [], "off": []}
        for prof in ("on", "off"):          # warm both paths + capture
            c.execute(f"SET serene_profile = {prof}")
            rows[prof] = c.execute(q).rows()
        assert rows["on"] == rows["off"], f"profiling perturbed {name}"
        for _ in range(pairs):
            for prof in ("off", "on"):
                c.execute(f"SET serene_profile = {prof}")
                t0 = time.perf_counter()
                c.execute(q)
                samples[prof].append(time.perf_counter() - t0)
        med = {p: statistics.median(s) for p, s in samples.items()}
        overhead = med["on"] / med["off"] - 1.0
        detail[name] = {"on_s": round(med["on"], 5),
                        "off_s": round(med["off"], 5),
                        "overhead_pct": round(overhead * 100, 2)}
        t_on_total += med["on"]
        t_off_total += med["off"]
    _EXTRA["rows"] = n
    _EXTRA["detail"] = detail
    overall = t_on_total / t_off_total - 1.0
    _EXTRA["overhead_pct"] = round(overall * 100, 2)
    assert overall < 0.03, \
        f"profiler overhead over budget: {overall * 100:.2f}% (>3%)"
    return t_off_total / t_on_total


def bench_trace_overhead() -> float:
    """Timeline-tracing overhead budget (ISSUE 10, <3%): the host_agg
    filtered parallel aggregate plus the vectorized join at 1M rows,
    with `serene_trace` on vs off (profiling stays at its default in
    both modes — this isolates the TRACING delta: per-statement trace
    setup, per-pool-task span stamps, flight-recorder finalize).
    Results are asserted bit-identical and the end-to-end
    alternating-pairs medians are recorded per shape — but like the
    result_cache miss-overhead leg, a single-digit-percent delta drowns
    in this host's ±10%+ serial drift end to end, so the ASSERTED
    number is a direct decomposition: the measured cost of one traced
    statement's actual span traffic (trace setup + 4x the observed span
    count + ring merge + flight record), divided by the query's off-mode
    median. Returns t_off/t_on (≈1.0; 0.97 ⇔ 3% overhead)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(31)
    n = 1_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE po (k INT, v BIGINT)")
    c.execute("CREATE TABLE pb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["po"] = MemTable("po", Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 1000, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64))}))
    db.schemas["main"].tables["pb"] = MemTable("pb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(n, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, n, dtype=np.int64))}))
    c.execute("SET serene_device = 'cpu'")
    queries = {
        "host_agg": ("SELECT k, count(*), sum(v) FROM po "
                     "WHERE v % 7 <> 0 GROUP BY k"),
        "join": ("SELECT count(*), sum(v + w) FROM po "
                 "JOIN pb ON po.v = pb.k"),
    }
    import statistics

    from serenedb_tpu.obs.trace import FLIGHT, QueryTrace
    pairs = 7
    detail: dict[str, dict] = {}
    t_on_total = t_off_total = 0.0
    max_spans = 1
    for name, q in queries.items():
        rows = {}
        samples: dict[str, list[float]] = {"on": [], "off": []}
        for tr in ("on", "off"):            # warm both paths + capture
            c.execute(f"SET serene_trace = {tr}")
            rows[tr] = c.execute(q).rows()
        assert rows["on"] == rows["off"], f"tracing perturbed {name}"
        for _ in range(pairs):
            for tr in ("off", "on"):
                c.execute(f"SET serene_trace = {tr}")
                t0 = time.perf_counter()
                c.execute(q)
                samples[tr].append(time.perf_counter() - t0)
        # the query's REAL span count (its last traced run is the
        # newest flight entry) feeds the direct probe below
        spans = len(FLIGHT.last()["spans"])
        max_spans = max(max_spans, spans)
        med = {p: statistics.median(s) for p, s in samples.items()}
        overhead = med["on"] / med["off"] - 1.0
        detail[name] = {"on_s": round(med["on"], 5),
                        "off_s": round(med["off"], 5),
                        "spans": spans,
                        "e2e_overhead_pct": round(overhead * 100, 2)}
        t_on_total += med["on"]
        t_off_total += med["off"]
    # direct decomposition: one traced statement costs (setup + span
    # stamps + ring merge + flight record); probe it at 4x the widest
    # observed span count and charge it against the FASTEST query's
    # off-mode median (the worst case for a fixed per-statement cost)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        qt = QueryTrace("bench probe")
        now = qt.t0_ns
        for i in range(4 * max_spans):
            qt.add("probe_span", "bench", now + i, now + i + 100, k=i)
        FLIGHT.record(qt.finish())
    per_stmt_s = (time.perf_counter() - t0) / reps
    fastest_off = min(d["off_s"] for d in detail.values())
    direct = per_stmt_s / fastest_off
    _EXTRA["rows"] = n
    _EXTRA["detail"] = detail
    _EXTRA["per_statement_trace_ms"] = round(per_stmt_s * 1e3, 4)
    _EXTRA["probe_spans"] = 4 * max_spans
    _EXTRA["overhead_pct"] = round(direct * 100, 3)
    _EXTRA["e2e_overhead_pct"] = round(
        (t_on_total / t_off_total - 1.0) * 100, 2)
    assert direct < 0.03, \
        f"tracing overhead over budget: {direct * 100:.2f}% (>3%)"
    return t_off_total / t_on_total


def bench_mem_overhead() -> float:
    """Memory-accounting overhead budget (ISSUE 13, <3%): the host_agg
    filtered parallel aggregate plus the vectorized join at 1M rows,
    with `serene_mem_account` on vs off (profile/trace stay at their
    defaults in both modes — this isolates the ACCOUNTING delta:
    per-statement accountant setup + ACTIVE registration, per-batch /
    per-morsel charge+release pairs, statement-end totals). Results are
    asserted bit-identical and the end-to-end alternating-pairs medians
    are recorded per shape — but like trace_overhead (the PR 5/PR 10
    noise lesson), a single-digit-percent delta drowns in this host's
    serial drift end to end, so the ASSERTED number is a direct
    decomposition: the measured cost of one accounted statement's
    actual charge/release traffic (setup + register + 4x the observed
    event count + merge/totals + retire), divided by the query's
    off-mode median. Returns t_off/t_on (≈1.0; 0.97 ⇔ 3% overhead)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(31)
    n = 1_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE po (k INT, v BIGINT)")
    c.execute("CREATE TABLE pb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["po"] = MemTable("po", Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 1000, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64))}))
    db.schemas["main"].tables["pb"] = MemTable("pb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(n, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, n, dtype=np.int64))}))
    c.execute("SET serene_device = 'cpu'")
    queries = {
        "host_agg": ("SELECT k, count(*), sum(v) FROM po "
                     "WHERE v % 7 <> 0 GROUP BY k"),
        "join": ("SELECT count(*), sum(v + w) FROM po "
                 "JOIN pb ON po.v = pb.k"),
    }
    import statistics

    from serenedb_tpu.obs.resources import ACTIVE, MemoryAccountant
    from serenedb_tpu.utils import metrics as _metrics
    pairs = 7
    detail: dict[str, dict] = {}
    t_on_total = t_off_total = 0.0
    max_events = 1
    for name, q in queries.items():
        rows = {}
        samples: dict[str, list[float]] = {"on": [], "off": []}
        for mode in ("on", "off"):          # warm both paths + capture
            c.execute(f"SET serene_mem_account = {mode}")
            ev0 = _metrics.MEM_ACCOUNT_EVENTS.value
            rows[mode] = c.execute(q).rows()
            if mode == "on":
                # the query's REAL charge/release traffic feeds the
                # direct probe below
                events = _metrics.MEM_ACCOUNT_EVENTS.delta(ev0)
                max_events = max(max_events, events)
        assert rows["on"] == rows["off"], f"accounting perturbed {name}"
        for _ in range(pairs):
            for mode in ("off", "on"):
                c.execute(f"SET serene_mem_account = {mode}")
                t0 = time.perf_counter()
                c.execute(q)
                samples[mode].append(time.perf_counter() - t0)
        med = {p: statistics.median(s) for p, s in samples.items()}
        overhead = med["on"] / med["off"] - 1.0
        detail[name] = {"on_s": round(med["on"], 5),
                        "off_s": round(med["off"], 5),
                        "e2e_overhead_pct": round(overhead * 100, 2)}
        t_on_total += med["on"]
        t_off_total += med["off"]
    # direct decomposition: one accounted statement costs (accountant
    # setup + ACTIVE register + charge/release traffic + merge/totals +
    # retire); probe it at 4x the widest observed event count and
    # charge it against the FASTEST query's off-mode median (the worst
    # case for a fixed per-statement cost)
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        acct = MemoryAccountant("bench probe", pid=0)
        ACTIVE.register(acct)
        for i in range(2 * max_events):     # 2x charge+release = 4x events
            acct.charge(i & 15, 4096)
            acct.release(i & 15, 4096)
        acct.add_progress(rows=1024, nbytes=8192, morsels=1)
        acct.merged()
        acct.totals()
        acct.event_count()
        ACTIVE.retire(acct)
    per_stmt_s = (time.perf_counter() - t0) / reps
    fastest_off = min(d["off_s"] for d in detail.values())
    direct = per_stmt_s / fastest_off
    _EXTRA["rows"] = n
    _EXTRA["detail"] = detail
    _EXTRA["per_statement_account_ms"] = round(per_stmt_s * 1e3, 4)
    _EXTRA["probe_events"] = 4 * max_events
    _EXTRA["overhead_pct"] = round(direct * 100, 3)
    _EXTRA["e2e_overhead_pct"] = round(
        (t_on_total / t_off_total - 1.0) * 100, 2)
    assert direct < 0.03, \
        f"accounting overhead over budget: {direct * 100:.2f}% (>3%)"
    return t_off_total / t_on_total


def bench_concurrency() -> float:
    """Workload governor (ISSUE 14): p50/p99 latency of SMALL dashboard
    queries while heavy scans run, fair-share + admission off vs on.

    Three heavy aggregate statements loop continuously over 2M rows
    (each keeps its map_ordered window of morsel tasks in the shared
    pool queue) while a fourth session runs 30 small aggregates; per
    small query the flight-recorder timeline yields its WIDEST pool
    queue-wait span. ASSERTED (the PR 5/PR 10 noise discipline: claim
    the decomposition, record the end to end): results bit-identical
    off vs on, and the small queries' p99 queue-wait DROPS with fair
    share on — under FIFO a small morsel provably waits behind every
    heavy morsel already queued, under stride picking it overtakes
    them. End-to-end p50/p99 latencies are recorded in the extra
    payload, not asserted. Returns wait_p99_off / wait_p99_on."""
    import statistics

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.obs.trace import FLIGHT
    from serenedb_tpu.utils.config import REGISTRY

    rng = np.random.default_rng(23)
    n_heavy, n_small = 2_000_000, 30_000
    # an 8-worker pool regardless of host cores (set BEFORE first
    # get_pool()): the fair-share story is about deep per-statement
    # backlogs, and map_ordered windows in-flight tasks at
    # min(serene_workers, pool size) — a 2-worker floor pool on a
    # small box would cap every heavy statement at 2 queued morsels
    # and hide the starvation this shape measures
    REGISTRY.set_global("serene_workers", 8)
    db = Database()
    boot = db.connect()
    boot.execute("CREATE TABLE hv (k INT, v BIGINT)")
    boot.execute("CREATE TABLE sm (k INT, v BIGINT)")
    db.schemas["main"].tables["hv"] = MemTable("hv", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.integers(0, 1000, n_heavy).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(0, n_heavy, n_heavy, dtype=np.int64))}))
    db.schemas["main"].tables["sm"] = MemTable("sm", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.integers(0, 50, n_small).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(0, n_small, n_small, dtype=np.int64))}))

    HEAVY_Q = ("SELECT k, count(*), sum(v) FROM hv WHERE v % 7 <> 0 "
               "GROUP BY k")
    SMALL_Q = ("SELECT k, count(*), sum(v) FROM sm WHERE v % 3 <> 0 "
               "GROUP BY k ORDER BY k")

    def connect(morsel_rows):
        cc = db.connect()
        cc.execute("SET serene_device = 'cpu'")
        cc.execute(f"SET serene_morsel_rows = {morsel_rows}")
        cc.execute("SET serene_parallel_min_rows = 1024")
        cc.execute("SET serene_workers = 8")
        return cc

    quiet = connect(4096)
    oracle_small = quiet.execute(SMALL_Q).rows()
    oracle_heavy = quiet.execute(HEAVY_Q).rows()

    samples = 30

    def measure(governor_on: bool, mode: str):
        REGISTRY.set_global("serene_fair_share", governor_on)
        REGISTRY.set_global("serene_max_concurrent_statements",
                            8 if governor_on else 0)
        stop = threading.Event()
        heavy_rows = []
        heavy_errs = []

        def heavy_loop():
            # a dead heavy thread would let the A/B measure ZERO
            # contention and ledger a vacuous ratio — surface the
            # first failure instead of letting the excepthook eat it
            try:
                hc = connect(65536)     # ~30 multi-ms morsels per pass
                while not stop.is_set():
                    heavy_rows.append(hc.execute(HEAVY_Q).rows())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                heavy_errs.append(e)

        threads = [threading.Thread(target=heavy_loop) for _ in range(3)]
        for t in threads:
            t.start()
        sc = connect(4096)
        sc.execute("SET serene_trace = on")
        # the dashboard session rides a high fair-share weight: its
        # morsels take ~10 picks per heavy-tag pick instead of an
        # equal 1-in-4 share (3 heavy tags dilute equal weights); a
        # no-op under FIFO, which is exactly the A/B this shape runs
        sc.execute("SET serene_priority = 1000")
        lat, waits = [], []
        rows = None
        try:
            time.sleep(0.2)             # heavy loops reach steady state
            for i in range(samples):
                marker = f"conc_{mode}_{i}"
                t0 = time.perf_counter()
                rows = sc.execute(
                    SMALL_Q.replace("GROUP BY",
                                    f"/* {marker} */ GROUP BY")).rows()
                lat.append(time.perf_counter() - t0)
                entry = next(e for e in reversed(FLIGHT.snapshot())
                             if marker in e["query"])
                spans = [s["end_ns"] - s["begin_ns"]
                         for s in entry["spans"]
                         if s["name"] == "queue_wait" and
                         s["cat"] == "pool"]
                waits.append(max(spans) / 1e9 if spans else 0.0)
        finally:
            stop.set()
            for t in threads:
                t.join()
        if heavy_errs:
            raise heavy_errs[0]
        assert heavy_rows, f"no heavy statements completed ({mode})"
        assert rows == oracle_small, f"small-query parity broke ({mode})"
        assert all(r == oracle_heavy for r in heavy_rows), \
            f"heavy-query parity broke ({mode})"
        return lat, waits, len(heavy_rows)

    def pcts(xs):
        s = sorted(xs)
        return (statistics.median(s), s[min(len(s) - 1,
                                            int(0.99 * len(s)))])

    try:
        lat_off, wait_off, heavy_off = measure(False, "off")
        lat_on, wait_on, heavy_on = measure(True, "on")
    finally:
        REGISTRY.set_global("serene_fair_share", True)
        REGISTRY.set_global("serene_max_concurrent_statements", 0)
    lat_p50_off, lat_p99_off = pcts(lat_off)
    lat_p50_on, lat_p99_on = pcts(lat_on)
    wait_p50_off, wait_p99_off = pcts(wait_off)
    wait_p50_on, wait_p99_on = pcts(wait_on)
    _EXTRA["heavy_rows"] = n_heavy
    _EXTRA["small_rows"] = n_small
    _EXTRA["samples"] = samples
    _EXTRA["heavy_statements"] = {"off": heavy_off, "on": heavy_on}
    _EXTRA["small_latency_ms"] = {
        "off": {"p50": round(lat_p50_off * 1e3, 2),
                "p99": round(lat_p99_off * 1e3, 2)},
        "on": {"p50": round(lat_p50_on * 1e3, 2),
               "p99": round(lat_p99_on * 1e3, 2)}}
    _EXTRA["small_queue_wait_ms"] = {
        "off": {"p50": round(wait_p50_off * 1e3, 2),
                "p99": round(wait_p99_off * 1e3, 2)},
        "on": {"p50": round(wait_p50_on * 1e3, 2),
               "p99": round(wait_p99_on * 1e3, 2)}}
    _EXTRA["parity"] = "identical"
    # the asserted decomposition: fair share bounds the widest wait
    assert wait_p99_on < wait_p99_off, \
        f"p99 queue wait did not drop: off={wait_p99_off:.4f}s " \
        f"on={wait_p99_on:.4f}s"
    return wait_p99_off / max(wait_p99_on, 1e-9)


def bench_result_cache() -> float:
    """Multi-tier query cache (ISSUE 5 tentpole): the host_agg filtered
    aggregate and the vectorized join at 1M rows through the engine with
    the result cache on. Measures the three latencies a cache story is
    made of — cold (first execution, stores), warm (served from cache),
    invalidated (a write bumped the publication, full re-execution) —
    plus the miss-path overhead: cache ON but invalidated-every-run vs
    cache OFF, alternating pairwise with per-mode medians (the
    profile_overhead methodology: single-digit deltas drown in scheduler
    drift under naive A/B). Returns the cold/warm speedup at the
    host_agg shape (≥10x asserted); extras carry per-shape latencies and
    the measured overhead (<3% asserted). Warm results are asserted
    bit-identical to cold ones."""
    import statistics

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(41)
    n = 1_000_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE co (k INT, v BIGINT)")
    c.execute("CREATE TABLE cb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["co"] = MemTable("co", Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 1000, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-(10 ** 6), 10 ** 6, n, dtype=np.int64))}))
    db.schemas["main"].tables["cb"] = MemTable("cb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(n, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, n, dtype=np.int64))}))
    c.execute("SET serene_device = 'cpu'")
    c.execute("SET serene_result_cache = on")
    queries = {
        "host_agg": ("SELECT k, count(*), sum(v) FROM co "
                     "WHERE v % 7 <> 0 GROUP BY k"),
        "join": ("SELECT count(*), sum(v + w) FROM co "
                 "JOIN cb ON co.v = cb.k"),
    }
    detail: dict[str, dict] = {}
    headline = None
    for name, q in queries.items():
        t0 = time.perf_counter()
        cold_rows = c.execute(q).rows()
        t_cold = time.perf_counter() - t0
        warm_samples = []
        for _ in range(9):
            t0 = time.perf_counter()
            rows = c.execute(q).rows()
            warm_samples.append(time.perf_counter() - t0)
            assert rows == cold_rows, f"warm hit diverged on {name}"
        t_warm = statistics.median(warm_samples)
        # invalidated: a write bumps the publication tuple → full rerun
        c.execute("INSERT INTO co VALUES (0, 1)")
        t0 = time.perf_counter()
        c.execute(q)
        t_inval = time.perf_counter() - t0
        detail[name] = {"cold_s": round(t_cold, 5),
                        "warm_s": round(t_warm, 6),
                        "invalidated_s": round(t_inval, 5),
                        "warm_speedup": round(t_cold / t_warm, 1)}
        if name == "host_agg":
            headline = t_cold / t_warm
    # miss-path overhead, measured by DIRECT DECOMPOSITION: on a miss
    # the cache adds exactly its probe legs (begin -> fast_lookup ->
    # prepare -> lookup -> store) around an otherwise unchanged
    # execution, so time those legs explicitly and ratio them against
    # the statement's own serial execution time. An end-to-end A/B
    # cannot resolve a 3% budget on this host: the SAME serial query
    # with the cache fully off swings +/-10% run to run (scheduler/
    # frequency drift), while the probe legs are deterministic
    # sub-millisecond work. Distinct tautology literals force every
    # probe through the full miss path (parse/plan excluded from the
    # timed region -- both arms pay those identically).
    import statistics as _stats

    from serenedb_tpu.cache.result import RESULT_CACHE
    from serenedb_tpu.sql import parser as _parser
    c.execute("SET serene_workers = 1")
    c.execute("SET serene_result_cache = off")
    qtext = ("SELECT k, count(*), sum(v) FROM co "
             "WHERE v % 7 <> 0 AND 424242 = 424242 GROUP BY k")
    exec_samples = []
    for i in range(7):
        t0 = time.perf_counter()
        res = c.execute(qtext.replace("424242", str(10 ** 6 + i)))
        exec_samples.append(time.perf_counter() - t0)
    exec_s = _stats.median(exec_samples)
    batch = res.batch
    c.execute("SET serene_result_cache = on")
    st0 = _parser.parse(qtext)[0]
    plan = c._plan(st0, [])
    reps = 50
    variants = [_parser.parse(qtext.replace("424242",
                                            str(2 * 10 ** 6 + r)))[0]
                for r in range(reps)]
    t0 = time.perf_counter()
    for stv in variants:
        probe = RESULT_CACHE.begin(c, stv, [], qtext)
        probe.fast_lookup()
        probe.prepare(plan)
        probe.lookup()
        probe.store(batch)
    probe_s = (time.perf_counter() - t0) / reps
    overhead = probe_s / exec_s
    _EXTRA["probe_ms"] = round(probe_s * 1000, 3)
    _EXTRA["miss_exec_ms"] = round(exec_s * 1000, 2)
    _EXTRA["rows"] = n
    _EXTRA["detail"] = detail
    _EXTRA["miss_overhead_pct"] = round(overhead * 100, 2)
    assert overhead < 0.03, \
        f"result-cache miss-path overhead over budget: " \
        f"{overhead * 100:.2f}% (>3%)"
    assert headline >= 10.0, \
        f"warm hits under-deliver: {headline:.1f}x (<10x) on host_agg"
    return headline


def bench_device_pipeline() -> float:
    """Fused device relational pipeline (ISSUE 7 tentpole): a 1M-row
    filter→join→agg chain through the engine, three ways — host oracle
    (`serene_device_fused = off`), cold fused dispatch (data caches
    cleared: key factorize + host→device upload + one dispatch), and
    device-cached repeat (publication-keyed columns resident: one
    dispatch, zero transfer). The build side is 200k permuted keys and
    the probe draws from a 2x keyspace (~50% hit rate, unclustered so
    zone maps can't prune — this measures the fused matching tier).
    Returns the host/device-cached speedup (>1x asserted: the cached
    repeat dispatch must beat the host path); extras carry all three
    latencies. Results are asserted bit-identical to the host oracle."""
    import statistics

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec import device_pipeline as dp
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.utils import metrics as _metrics

    rng = np.random.default_rng(53)
    npr, nb, keyspace = 1_000_000, 200_000, 400_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE dpp (jk BIGINT, g INT, v BIGINT)")
    c.execute("CREATE TABLE dpb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["dpp"] = MemTable("dpp", Batch.from_pydict({
        "jk": Column.from_numpy(
            rng.integers(0, keyspace, npr, dtype=np.int64)),
        "g": Column.from_numpy(rng.integers(0, 16, npr).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, npr, dtype=np.int64))}))
    db.schemas["main"].tables["dpb"] = MemTable("dpb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(nb, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, nb, dtype=np.int64))}))
    q = ("SELECT g, count(*), sum(v), sum(w) FROM dpp "
         "JOIN dpb ON dpp.jk = dpb.k WHERE v > 0 GROUP BY g ORDER BY g")

    c.execute("SET serene_device = 'cpu'")
    c.execute("SET serene_device_fused = off")
    host_rows = c.execute(q).rows()
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        c.execute(q)
        samples.append(time.perf_counter() - t0)
    host_s = statistics.median(samples)

    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    off0 = _metrics.DEVICE_OFFLOADS.value
    fused_rows = c.execute(q).rows()          # compile warm-up + parity
    assert _metrics.DEVICE_OFFLOADS.value > off0, "fused path did not fire"
    assert fused_rows == host_rows, "fused pipeline diverged from host"
    # cold = DATA cold: publication-keyed device cache and the host-side
    # factorize cache cleared; the compiled program persists (the same
    # policy as device shapes: cold means upload, not recompile)
    dp.DEVICE_CACHE.clear()
    dp.clear_codes_cache()
    t0 = time.perf_counter()
    c.execute(q)
    cold_s = time.perf_counter() - t0
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        c.execute(q)
        samples.append(time.perf_counter() - t0)
    cached_s = statistics.median(samples)

    _EXTRA["rows"] = npr
    _EXTRA["host_s"] = round(host_s, 4)
    _EXTRA["cold_transfer_s"] = round(cold_s, 4)
    _EXTRA["device_cached_s"] = round(cached_s, 4)
    _EXTRA["cold_vs_cached"] = round(cold_s / cached_s, 2)
    headline = host_s / cached_s
    # the "one dispatch beats N host kernels" claim is a DEVICE claim:
    # on the CPU jit backend (tier-1's platform) a scatter-heavy XLA
    # program can legitimately trail the optimized numpy host path, so
    # record the honest ratio instead of failing
    import jax
    if jax.default_backend() != "cpu":
        assert headline > 1.0, \
            f"device-cached dispatch loses to host: {headline:.2f}x"
    return headline


def bench_fused_admission() -> float:
    """Fused-tier admission widening (ISSUE 17 tentpole): the
    join-bearing slice of the sqllogic corpus runs twice — with the
    PR-7 admission walls restored (`serene_device_fused_ext = off`)
    and with extended admission on (string/FILTER/DISTINCT aggregates,
    outer joins, residual join predicates, chained agg→top-N) — and
    the admitted fraction of fused-eligible join→agg plans is read
    from the compile ledger's `fused`/`fused_chain` lookups vs the
    per-reason decline counters (the same numbers `sdb_device()`
    serves). Parity is implicit: every corpus file's expected output
    IS the host oracle's. A chained leg then proves whole-query
    residency: the warm repeat of ORDER BY count(*) LIMIT over a fused
    aggregate must move ZERO host→device bytes — the stage-1
    accumulators hand off to the top-N program inside HBM. Returns
    admitted_after / admitted_before (>1 ⇔ walls demolished)."""
    import glob as _glob

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.obs import device as obs_device
    from serenedb_tpu.utils import metrics as _metrics

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    from tests.sqllogic_runner import run_test_file

    root = os.path.join(here, "tests", "sqllogic")
    files = sorted(
        _glob.glob(os.path.join(root, "*.test"))
        + _glob.glob(os.path.join(root, "any", "**", "*.test"),
                     recursive=True)
        + _glob.glob(os.path.join(root, "sdb", "**", "*.test"),
                     recursive=True))
    corpus = []
    for path in files:
        with open(path) as f:
            if "JOIN" in f.read():
                corpus.append(path)

    def counts() -> tuple[int, int]:
        fams = {p["family"]: p
                for p in obs_device.stats_section()["programs"]}
        admits = 0
        for fam in ("fused", "fused_chain"):
            f = fams.get(fam, {})
            admits += int(f.get("hits", 0)) + int(f.get("misses", 0))
        return admits, sum(obs_device.fused_declines().values())

    def run_corpus(ext_on: bool) -> tuple[int, int, float, int]:
        import tempfile
        a0, d0 = counts()
        fails = 0
        cwd = os.getcwd()
        for path in corpus:
            db = Database()
            try:
                with tempfile.TemporaryDirectory() as tmp:
                    os.chdir(tmp)   # relative COPY paths land here
                    conn = db.connect()
                    conn.execute("SET serene_device = 'tpu'")
                    conn.execute("SET serene_device_fused = on")
                    conn.execute("SET serene_device_fused_ext = "
                                 + ("on" if ext_on else "off"))
                    fails += len(run_test_file(conn, path, tmpdir=tmp))
            finally:
                os.chdir(cwd)
                db.close()
        a1, d1 = counts()
        admits, declines = a1 - a0, d1 - d0
        return admits, declines, admits / max(1, admits + declines), fails

    adm_b, dec_b, frac_b, fail_b = run_corpus(ext_on=False)
    adm_a, dec_a, frac_a, fail_a = run_corpus(ext_on=True)
    assert fail_b == 0 and fail_a == 0, \
        f"sqllogic corpus diverged under fused tier: {fail_b}/{fail_a}"
    assert adm_a > adm_b, \
        f"extended admission did not widen the tier: {adm_b} → {adm_a}"

    # chained leg: fused agg → top-N with the handoff in HBM
    rng = np.random.default_rng(71)
    npr, nb, keyspace = 200_000, 50_000, 100_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE fap (jk BIGINT, g INT, v BIGINT)")
    c.execute("CREATE TABLE fab (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["fap"] = MemTable("fap", Batch.from_pydict({
        "jk": Column.from_numpy(
            rng.integers(0, keyspace, npr, dtype=np.int64)),
        "g": Column.from_numpy(rng.integers(0, 64, npr).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, npr, dtype=np.int64))}))
    db.schemas["main"].tables["fab"] = MemTable("fab", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(nb, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, nb, dtype=np.int64))}))
    q = ("SELECT g, count(*) AS n, sum(v) FROM fap JOIN fab "
         "ON fap.jk = fab.k GROUP BY g ORDER BY n DESC LIMIT 5")
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    chain0 = _metrics.REGISTRY.gauge("DeviceChainedStages").value
    c.execute("SET serene_device_fused = off")
    host = c.execute(q).rows()
    c.execute("SET serene_device_fused = on")
    dev = c.execute(q).rows()             # cold: uploads + two compiles
    assert dev == host, "chained agg→top-N diverged from host"
    assert _metrics.REGISTRY.gauge("DeviceChainedStages").value > chain0, \
        "chained device path did not fire"
    ups0 = _metrics.DEVICE_TRANSFERS_UP.value
    t0 = time.perf_counter()
    warm = c.execute(q).rows()            # warm: both stages in HBM
    warm_s = time.perf_counter() - t0
    assert warm == host
    ups1 = _metrics.DEVICE_TRANSFERS_UP.value
    assert ups1 == ups0, \
        f"warm chained repeat moved host→device bytes ({ups1 - ups0})"
    db.close()

    _EXTRA["corpus_files"] = len(corpus)
    _EXTRA["admitted_before"] = adm_b
    _EXTRA["declined_before"] = dec_b
    _EXTRA["admitted_frac_before"] = round(frac_b, 4)
    _EXTRA["admitted_after"] = adm_a
    _EXTRA["declined_after"] = dec_a
    _EXTRA["admitted_frac_after"] = round(frac_a, 4)
    _EXTRA["chained_warm_s"] = round(warm_s, 4)
    _EXTRA["chained_warm_uploads"] = int(ups1 - ups0)
    _EXTRA["parity"] = "identical"
    return frac_a / max(frac_b, 1e-9) if frac_b else float(adm_a)


def bench_search_batch() -> float:
    """Batched ragged search serving (ISSUE 8 tentpole): aggregate QPS of
    concurrent 2-term top-10 searches over the 1M-doc synthetic corpus,
    batched (`serene_search_batch = on`: concurrent queries coalesce
    through search/batcher.py into shared ragged scoring dispatches) vs
    serial dispatch (`= off`, the parity oracle), at 1/8/64 concurrent
    submitters. Per-query results are asserted BIT-identical between the
    modes (scores, doc ids, tie order). Returns the 64-concurrency QPS
    ratio (≥5x asserted on the host backend, where the ragged numpy
    accumulate replaces per-query score planes; on a real device the
    ratio reflects per-dispatch cost amortization and is recorded
    honestly)."""
    import threading as _threading

    import jax
    import numpy as np

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.batcher import batched_topk
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
    from serenedb_tpu.utils import metrics as _metrics
    from serenedb_tpu.utils.config import REGISTRY as _settings

    an = get_analyzer("simple")
    n_docs = 1_000_000
    fi = _synth_posting_index(n_docs, 30_000, 12_000_000, 7)
    ms = MultiSearcher(an)
    ms.add_segment(SegmentSearcher(fi, an, n_docs), 0)
    terms = [f"w{100 + 13 * i:07d}" for i in range(128)]
    nodes = [parse_query(f"{terms[2 * i]} | {terms[2 * i + 1]}", an)
             for i in range(64)]

    def run_level(conc: int, on: bool, reps: int):
        _settings.set_global("serene_search_batch", on)
        results = [None] * len(nodes)
        bar = _threading.Barrier(conc)

        def worker(wi):
            bar.wait()
            for r in range(reps):
                for qi in range(wi, len(nodes), conc):
                    out, _ = batched_topk(ms, nodes[qi], 10, "bm25", 0,
                                          None)
                    if r == 0:
                        results[qi] = out

        ts = [_threading.Thread(target=worker, args=(i,))
              for i in range(conc)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        return reps * len(nodes) / dt, results

    import statistics as _stats

    # warm every compile bucket both modes will touch: serial per-query
    # shapes, the ragged contrib kernel's entry-count buckets, and the
    # coalesced batch sizes the 64-thread level produces
    run_level(1, False, 1)
    run_level(8, True, 1)
    run_level(64, True, 1)
    detail: dict[str, dict] = {}
    headline = None
    d0, q0 = (_metrics.SEARCH_BATCH_DISPATCHES.value,
              _metrics.SEARCH_BATCH_QUERIES.value)
    for conc in (1, 8, 64):
        # alternating pairs + per-mode medians (the profile_overhead
        # methodology): 64 GIL-thrashing threads swing a single serial
        # leg run-to-run far more than the batching effect under test
        pairs = 3 if conc == 64 else 2
        reps = 2 if conc >= 8 else 1
        on_s, off_s = [], []
        res_on = res_off = None
        for _ in range(pairs):
            qps_on, res_on = run_level(conc, True, reps)
            qps_off, res_off = run_level(conc, False, reps)
            on_s.append(qps_on)
            off_s.append(qps_off)
        for qi, (a, b) in enumerate(zip(res_on, res_off)):
            assert np.array_equal(a[0].view(np.uint32),
                                  b[0].view(np.uint32)) and \
                np.array_equal(a[1], b[1]), \
                f"batched result diverged from serial at conc={conc} " \
                f"query={qi}"
        qps_on = _stats.median(on_s)
        qps_off = _stats.median(off_s)
        detail[str(conc)] = {"qps_batched": round(qps_on, 1),
                             "qps_serial": round(qps_off, 1),
                             "ratio": round(qps_on / qps_off, 2)}
        if conc == 64:
            headline = qps_on / qps_off
    dn = _metrics.SEARCH_BATCH_DISPATCHES.value - d0
    _EXTRA["detail"] = detail
    _EXTRA["rows"] = n_docs
    _EXTRA["mean_batch"] = round(
        (_metrics.SEARCH_BATCH_QUERIES.value - q0) / max(dn, 1), 1)
    if jax.default_backend() == "cpu":
        assert headline >= 5.0, \
            f"batched serving under-delivers: {headline:.2f}x (<5x) at " \
            f"64 concurrent"
    return headline


def bench_paged_search() -> float:
    """Device-resident paged postings (ISSUE 16 tentpole): QPS of
    repeated coalesced ragged top-10 dispatches over the 1M-doc
    synthetic corpus at 1/8/64 queries per coalesced batch, page-
    resident (`serene_posting_pool = on`: warm batches score as ONE
    jitted gather-and-accumulate program over the pool's HBM page
    tables, uploading zero posting bytes) vs the host ragged path
    (`= off`, the parity oracle). Per-query results are asserted
    BIT-identical between the modes. Returns the 64-batch QPS ratio —
    recorded honestly on the CPU backend (the jitted gather competes
    with a numpy accumulate over host RAM there); on a real device the
    resident path must win (>1x asserted), because the oracle re-reads
    every posting from host memory per dispatch."""
    import statistics as _stats

    import jax
    import numpy as np

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.posting_pool import POOL
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import SegmentSearcher
    from serenedb_tpu.utils import metrics as _metrics
    from serenedb_tpu.utils.config import REGISTRY as _settings

    an = get_analyzer("simple")
    n_docs = 1_000_000
    fi = _synth_posting_index(n_docs, 30_000, 12_000_000, 7)
    seg = SegmentSearcher(fi, an, n_docs)
    terms = [f"w{100 + 13 * i:07d}" for i in range(128)]
    nodes = [parse_query(f"{terms[2 * i]} | {terms[2 * i + 1]}", an)
             for i in range(64)]

    def run_level(batch: int, on: bool, reps: int):
        _settings.set_global("serene_posting_pool", on)
        results = []
        t0 = time.perf_counter()
        for _ in range(reps):
            results = []
            for i in range(0, len(nodes), batch):
                results.extend(seg.topk_batch(nodes[i:i + batch], 10,
                                              ragged=True))
        dt = time.perf_counter() - t0
        return reps * len(nodes) / dt, results

    old = _settings.get_global("serene_posting_pool")
    try:
        # warm every bucket both modes touch: pool page residency +
        # batch descriptor memos + program compiles per batch size
        for batch in (1, 8, 64):
            run_level(batch, True, 1)
            run_level(batch, False, 1)
        d0 = _metrics.POSTING_POOL_DEVICE_QUERIES.value
        detail: dict[str, dict] = {}
        headline = None
        for batch in (1, 8, 64):
            on_s, off_s = [], []
            res_on = res_off = None
            for _ in range(2):    # alternating pairs + medians
                qps_on, res_on = run_level(batch, True, 1)
                qps_off, res_off = run_level(batch, False, 1)
                on_s.append(qps_on)
                off_s.append(qps_off)
            for qi, (a, b) in enumerate(zip(res_on, res_off)):
                assert np.array_equal(a[0].view(np.uint32),
                                      b[0].view(np.uint32)) and \
                    np.array_equal(a[1], b[1]), \
                    f"pool result diverged from host ragged at " \
                    f"batch={batch} query={qi}"
            qps_on = _stats.median(on_s)
            qps_off = _stats.median(off_s)
            detail[str(batch)] = {"qps_resident": round(qps_on, 1),
                                  "qps_host": round(qps_off, 1),
                                  "ratio": round(qps_on / qps_off, 2)}
            if batch == 64:
                headline = qps_on / qps_off
        assert _metrics.POSTING_POOL_DEVICE_QUERIES.value > d0, \
            "pool tier never engaged — bench measured host vs host"
        _EXTRA["detail"] = detail
        _EXTRA["rows"] = n_docs
        _EXTRA["pool"] = POOL.stats()
    finally:
        _settings.set_global("serene_posting_pool", old)
    if jax.default_backend() != "cpu":
        assert headline > 1.0, \
            f"resident paged scoring loses to host ragged: {headline:.2f}x"
    return headline


def bench_vector_search() -> float:
    """Vector retrieval subsystem (ISSUE 19 tentpole): knn top-10 QPS
    over a 100k x 256-d clustered corpus at 1/8/64 queries per
    coalesced dispatch — IVF cluster-probe (`nprobe = 8` of 64 lists:
    one jitted program gathers only the probed clusters' pages from the
    HBM region and exact-rescores the candidates) vs the device
    brute-force oracle (same program body, one all-rows list). The
    corpus is grid-quantized (entries k/16 with every squared-distance
    chain exact in f32 — see ops/vector.host_dist), so the probe path
    at `nprobe = lists` is asserted BIT-identical to the oracle: the
    probe tier is the exact path restricted to a candidate set, not an
    approximation of it. Returns the 64-batch probe/brute QPS ratio
    (work scales with probed clusters, so the probe path must win) and
    records recall@10 at the production nprobe in the detail."""
    import statistics as _stats

    import jax
    import jax.numpy as jnp
    import numpy as np

    from serenedb_tpu.ops import vector as vops
    from serenedb_tpu.search.ivf import IvfIndex, VecSegment
    from serenedb_tpu.search.vector_store import VPOOL
    from serenedb_tpu.utils import metrics as _metrics
    from serenedb_tpu.utils.config import REGISTRY as _settings

    rng = np.random.default_rng(7)
    n, dim, lists, nprobe, kk = 100_000, 256, 64, 8, 10
    # clustered grid corpus: centers k/16 (|k|<48) + noise k/16 (|k|<16)
    # keeps every coordinate a multiple of 2^-4 with |v| < 4 — products
    # are multiples of 2^-8 bounded by 16, and 256-dim sums stay far
    # under 2^24 such units, so device and host distance bits agree
    # regardless of FMA grouping
    centers = rng.integers(-48, 48, (lists, dim)).astype(np.float32)
    noise = rng.integers(-16, 16, (n, dim)).astype(np.float32)
    mat = (centers[rng.integers(0, lists, n)] + noise) / np.float32(16.0)
    # build the index straight from the matrix (100k INSERTs would
    # bench the ingest path, not the probe path)
    init = vops.init_centroids(mat, lists)
    cents = np.asarray(vops.kmeans_fit(
        jnp.asarray(vops.pad_rows(mat)), jnp.asarray(init), lists, 4))
    codes = np.asarray(vops.assign_clusters(
        jnp.asarray(vops.pad_rows(mat)), jnp.asarray(cents)))[:n]
    idx = IvfIndex(
        column="v", dim=dim, lists=lists, metric="l2", centroids=cents,
        segs=[VecSegment(mat, np.arange(n, dtype=np.int64), codes, lists)],
        num_rows=n, data_version=1)
    queries = (centers[rng.integers(0, lists, 64)]
               + rng.integers(-16, 16, (64, dim))) / np.float32(16.0)

    def run_level(batch: int, probe, reps: int):
        outs = []
        t0 = time.perf_counter()
        for _ in range(reps):
            outs = []
            for i in range(0, len(queries), batch):
                qs = queries[i:i + batch]
                if probe is None:
                    outs.append(idx.brute_search(qs, kk))
                else:
                    outs.append(idx.search(qs, kk, probe))
        dt = time.perf_counter() - t0
        return reps * len(queries) / dt, outs

    headline = None
    detail: dict[str, dict] = {}
    d0 = _metrics.VECTOR_SEARCH_DISPATCHES.value
    # 100k x 256-d = 6250 pages: widen the page budget past the 64 MiB
    # default so the probe path measures HBM-resident, not cold-upload
    old_pages = _settings.get_global("serene_vector_pages")
    _settings.set_global("serene_vector_pages", 8192)
    # full-probe parity gate: nprobe=lists probes every cluster, so the
    # probe program and the brute oracle must agree to the bit
    dq, rq = idx.search(queries, kk, lists)
    db, rb = idx.brute_search(queries, kk)
    assert np.array_equal(dq.view(np.uint32), db.view(np.uint32)) and \
        np.array_equal(rq, rb.astype(np.int64)), \
        "nprobe=lists diverged from the device brute-force oracle"
    brute_top = [set(rb[i][np.isfinite(db[i])].tolist())
                 for i in range(len(queries))]
    d8, r8 = idx.search(queries, kk, nprobe)
    got = sum(len(set(r8[i][np.isfinite(d8[i])].tolist()) & brute_top[i])
              for i in range(len(queries)))
    recall = got / max(sum(len(s) for s in brute_top), 1)
    assert recall >= 0.3, f"recall@10 collapsed: {recall:.2f}"
    for batch in (1, 8, 64):
        run_level(batch, nprobe, 1)    # warm compiles per batch size
        run_level(batch, None, 1)
        probe_s, brute_s = [], []
        for _ in range(2):    # alternating pairs + medians
            qps_p, _ = run_level(batch, nprobe, 1)
            qps_b, _ = run_level(batch, None, 1)
            probe_s.append(qps_p)
            brute_s.append(qps_b)
        qps_p = _stats.median(probe_s)
        qps_b = _stats.median(brute_s)
        detail[str(batch)] = {"qps_probe": round(qps_p, 1),
                              "qps_brute": round(qps_b, 1),
                              "ratio": round(qps_p / qps_b, 2)}
        if batch == 64:
            headline = qps_p / qps_b
    assert _metrics.VECTOR_SEARCH_DISPATCHES.value > d0, \
        "vector tier never dispatched — bench measured nothing"
    _EXTRA["detail"] = detail
    _EXTRA["rows"] = n
    _EXTRA["recall_at_10"] = round(recall, 4)
    _EXTRA["pool"] = VPOOL.stats()
    assert _EXTRA["pool"]["pages_used"] > 0, \
        "corpus never went HBM-resident — bench measured the cold path"
    _settings.set_global("serene_vector_pages", old_pages)
    VPOOL.clear()
    assert headline > 1.0, \
        f"cluster probe loses to brute force: {headline:.2f}x"
    return headline


def bench_shard_exec() -> float:
    """Sharded execution tier (ISSUE 9 tentpole): the 1M-row
    filter→join→agg chain through the engine at `serene_shards` 1/2/4 —
    shards=1 is the single fused dispatch (the parity oracle), shards=N
    runs the SAME fused program once per round-robin probe shard as
    concurrent pool tasks pinned across jax.devices(), with the build
    phase publication-cached and the exact integer cross-shard combine
    on host. Plus a search leg: 2-term top-10 WAND over a 1M-doc
    4-segment index with the segment set sharded. Every leg asserts
    results BIT-identical to shards=1; timing uses alternating pairs +
    medians (the profile_overhead methodology — this 2-core box swings
    serial legs run-to-run). Returns the best relational-leg speedup
    (≥1.5x asserted on the CPU backend: the shard fan-out must beat the
    single dispatch on at least one shard count)."""
    import statistics

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    rng = np.random.default_rng(53)
    npr, nb, keyspace = 1_000_000, 200_000, 400_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE sp (jk BIGINT, g INT, v BIGINT)")
    c.execute("CREATE TABLE sb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["sp"] = MemTable("sp", Batch.from_pydict({
        "jk": Column.from_numpy(
            rng.integers(0, keyspace, npr, dtype=np.int64)),
        "g": Column.from_numpy(rng.integers(0, 16, npr).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, npr, dtype=np.int64))}))
    db.schemas["main"].tables["sb"] = MemTable("sb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(nb, dtype=np.int64))),
        "w": Column.from_numpy(rng.integers(0, 100, nb, dtype=np.int64))}))
    q = ("SELECT g, count(*), sum(v), sum(w) FROM sp "
         "JOIN sb ON sp.jk = sb.k WHERE v > 0 GROUP BY g ORDER BY g")
    c.execute("SET serene_result_cache = off")
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    c.execute("SET serene_morsel_rows = 131072")   # 8 probe blocks
    c.execute("SET serene_workers = 4")

    ref = None
    for sh in (1, 2, 4):                  # warm compiles + upload caches
        c.execute(f"SET serene_shards = {sh}")
        rows = c.execute(q).rows()
        if ref is None:
            ref = rows
        assert rows == ref, f"shards={sh} diverged from the oracle"
        c.execute(q)

    def once(sh):
        c.execute(f"SET serene_shards = {sh}")
        t0 = time.perf_counter()
        c.execute(q)
        return time.perf_counter() - t0

    detail: dict[str, dict] = {}
    best = 0.0
    for target in (2, 4):
        base_s, shard_s = [], []
        for _ in range(6):                # alternating pairs
            base_s.append(once(1))
            shard_s.append(once(target))
        b = statistics.median(base_s)
        s = statistics.median(shard_s)
        detail[f"join_agg_shards_{target}"] = {
            "single_s": round(b, 4), "sharded_s": round(s, 4),
            "speedup": round(b / s, 2)}
        best = max(best, b / s)
    c.execute("SET serene_shards = 1")

    # -- search leg: sharded segment sets, bit-exact merge ---------------
    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
    from serenedb_tpu.utils.config import REGISTRY as _settings

    an = get_analyzer("simple")
    seg_docs = 250_000
    ms = MultiSearcher(an)
    for si in range(4):
        fi = _synth_posting_index(seg_docs, 20_000, 3_000_000, 11 + si)
        ms.add_segment(SegmentSearcher(fi, an, seg_docs), si * seg_docs)
    terms = [f"w{100 + 13 * i:07d}" for i in range(96)]
    nodes = [parse_query(f"{terms[2 * i]} | {terms[2 * i + 1]}", an)
             for i in range(48)]

    def run_search(sh, offset):
        _settings.set_global("serene_shards", sh)
        out = []
        t0 = time.perf_counter()
        for node in nodes[offset:offset + 16]:
            out.append(ms.cpu_topk(node, 10))
        return time.perf_counter() - t0, out

    # fragment tier OFF for the whole leg (it gates on the
    # serene_result_cache global): the parity loop runs every query at
    # every shard count, so with fragments on the timed passes would
    # measure cached-merge overhead instead of sharded WAND scoring
    rc_prior = _settings.get_global("serene_result_cache")
    _settings.set_global("serene_result_cache", False)
    try:
        # parity first: every query, shards 1 vs 2 vs 4
        _settings.set_global("serene_shards", 1)
        refs = [ms.cpu_topk(n, 10) for n in nodes]
        for sh in (2, 4):
            _settings.set_global("serene_shards", sh)
            for node, (rs, rd) in zip(nodes, refs):
                s2, d2 = ms.cpu_topk(node, 10)
                assert np.array_equal(s2.view(np.uint32),
                                      rs.view(np.uint32)) and \
                    np.array_equal(d2, rd), "sharded search diverged"
        # same slice both modes (fragments are off, so repeats re-score
        # fully), alternating pairs + medians like the relational leg
        t1s, t4s = [], []
        for _ in range(3):
            t1s.append(run_search(1, 0)[0])
            t4s.append(run_search(4, 0)[0])
        t1, t4 = statistics.median(t1s), statistics.median(t4s)
        detail["search_topk_shards_4"] = {
            "single_s": round(t1, 4), "sharded_s": round(t4, 4),
            "ratio": round(t1 / t4, 2)}
    finally:
        _settings.set_global("serene_shards", 1)
        _settings.set_global("serene_result_cache", rc_prior)

    _EXTRA["rows"] = npr
    _EXTRA["detail"] = detail
    _EXTRA["search_docs"] = 4 * seg_docs
    import jax
    if jax.default_backend() == "cpu" and (os.cpu_count() or 1) >= 2:
        # thread fan-out cannot beat serial on a single core — the
        # bar applies only where the host can actually overlap shards
        # (the test_parallel_exec single-worker-host skip idiom)
        assert best >= 1.5, \
            f"shard fan-out under-delivers: best {best:.2f}x (<1.5x)"
    return best


def bench_multichip() -> float:
    """In-program multi-chip combine (ISSUE 12 tentpole): the 1M-row
    filter→join→agg chain and a 1M-doc 4-segment search at
    `serene_shards` 1/2/4 over a 4-device virtual CPU mesh
    (xla_force_host_platform_device_count, armed by the harness for
    this shape), A/B-ing `serene_shard_combine=host` (PR 9's build +
    N probe dispatches + numpy combine) against `=device` (ONE
    shard_map-partitioned dispatch with psum/pmin/pmax reducing the
    integer accumulators in HBM; search merges with an in-program
    per-shard top-k + one all_gather hop). Every cell asserts results
    BIT-identical to shards=1; timing uses alternating pairs + medians
    (the profile_overhead methodology). The asserted facts follow the
    PR 5/PR 10 lesson — assert only what this host's timing noise
    cannot blur: the DISPATCH decomposition (device combine = exactly
    ONE offload per execution, host combine = one per shard) is
    asserted exactly, while the end-to-end shards=4 A/B is RECORDED,
    not asserted (measured 0.95-1.02x across runs on this shared
    1-core host — the paired-median estimator cannot stably resolve a
    ~1% effect under its ±3% drift, the exact trace_overhead lesson;
    a 1-core virtual mesh cannot show parallel speedup, so parity at
    1/4th the dispatches is the honest single-host result). Returns
    the shards=4 device-vs-host relational speedup."""
    import statistics

    import jax
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable

    _EXTRA["mesh_devices"] = len(jax.devices())
    rng = np.random.default_rng(53)
    npr, nb, keyspace = 1_000_000, 200_000, 400_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE sp (jk BIGINT, g INT, v BIGINT)")
    c.execute("CREATE TABLE sb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["sp"] = MemTable("sp", Batch.from_pydict({
        "jk": Column.from_numpy(
            rng.integers(0, keyspace, npr, dtype=np.int64)),
        "g": Column.from_numpy(rng.integers(0, 16, npr).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, npr, dtype=np.int64))}))
    db.schemas["main"].tables["sb"] = MemTable("sb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(nb, dtype=np.int64))),
        "w": Column.from_numpy(rng.integers(0, 100, nb, dtype=np.int64))}))
    # min/max ride pmin/pmax, count/sum the psum limb/direct paths
    q = ("SELECT g, count(*), sum(v), sum(w), min(w), max(v) FROM sp "
         "JOIN sb ON sp.jk = sb.k WHERE v > 0 GROUP BY g ORDER BY g")
    c.execute("SET serene_result_cache = off")
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    c.execute("SET serene_morsel_rows = 131072")   # 8 probe blocks
    c.execute("SET serene_workers = 4")

    c.execute("SET serene_shards = 1")
    ref = c.execute(q).rows()
    for sh in (2, 4):                 # parity + warm compiles/uploads
        for combine in ("host", "device"):
            c.execute(f"SET serene_shards = {sh}")
            c.execute(f"SET serene_shard_combine = {combine}")
            rows = c.execute(q).rows()
            assert rows == ref, \
                f"shards={sh} combine={combine} diverged from the oracle"
            c.execute(q)

    # structural decomposition (deterministic): the in-program combine
    # is ONE dispatch where the host combine pays one per shard — the
    # replaced-dispatch claim, asserted exactly via the offload gauge
    from serenedb_tpu.utils import metrics as _metrics
    c.execute("SET serene_shards = 4")
    c.execute("SET serene_shard_combine = device")
    d0 = _metrics.DEVICE_OFFLOADS.value
    c.execute(q)
    assert _metrics.DEVICE_OFFLOADS.value - d0 == 1, \
        "device combine must execute as ONE collective dispatch"
    c.execute("SET serene_shard_combine = host")
    d0 = _metrics.DEVICE_OFFLOADS.value
    c.execute(q)
    host_dispatches = _metrics.DEVICE_OFFLOADS.value - d0
    assert host_dispatches >= 4, \
        "host combine should pay one probe dispatch per shard"
    _EXTRA["dispatches_per_exec"] = {"device": 1, "host": host_dispatches}

    def once(sh, combine):
        c.execute(f"SET serene_shards = {sh}")
        c.execute(f"SET serene_shard_combine = {combine}")
        t0 = time.perf_counter()
        c.execute(q)
        return time.perf_counter() - t0

    detail: dict[str, dict] = {}
    ratio4 = 0.0
    for target in (2, 4):
        hs, ds = [], []
        for _ in range(12):           # alternating pairs (the ~1%
            hs.append(once(target, "host"))   # effect needs a tight
            ds.append(once(target, "device"))  # median on this host)
        h = statistics.median(hs)
        d = statistics.median(ds)
        detail[f"join_agg_shards_{target}"] = {
            "host_combine_s": round(h, 4),
            "device_combine_s": round(d, 4),
            "speedup": round(h / d, 2)}
        if target == 4:
            ratio4 = h / d
    c.execute("SET serene_shards = 1")
    c.execute("SET serene_shard_combine = auto")

    # -- search leg: in-program per-shard top-k + all_gather merge -------
    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.query import parse_query
    from serenedb_tpu.search.searcher import MultiSearcher, SegmentSearcher
    from serenedb_tpu.utils.config import REGISTRY as _settings

    an = get_analyzer("simple")
    seg_docs = 250_000
    ms = MultiSearcher(an)
    for si in range(4):
        fi = _synth_posting_index(seg_docs, 20_000, 3_000_000, 11 + si)
        ms.add_segment(SegmentSearcher(fi, an, seg_docs), si * seg_docs)
    terms = [f"w{100 + 13 * i:07d}" for i in range(32)]
    nodes = [parse_query(f"{terms[2 * i]} | {terms[2 * i + 1]}", an)
             for i in range(16)]

    rc_prior = _settings.get_global("serene_result_cache")
    cb_prior = _settings.get_global("serene_shard_combine")
    _settings.set_global("serene_result_cache", False)
    try:
        _settings.set_global("serene_shards", 1)
        refs = [ms.cpu_topk(n, 10) for n in nodes]
        for sh in (2, 4):
            _settings.set_global("serene_shards", sh)
            for combine in ("host", "device"):
                _settings.set_global("serene_shard_combine", combine)
                for node, (rs, rd) in zip(nodes, refs):
                    s2, d2 = ms.cpu_topk(node, 10)
                    assert np.array_equal(s2.view(np.uint32),
                                          rs.view(np.uint32)) and \
                        np.array_equal(d2, rd), \
                        f"sharded search diverged ({sh}, {combine})"

        def run_search(combine):
            _settings.set_global("serene_shard_combine", combine)
            t0 = time.perf_counter()
            for node in nodes:
                ms.cpu_topk(node, 10)
            return time.perf_counter() - t0

        _settings.set_global("serene_shards", 4)
        th, td = [], []
        for _ in range(3):
            th.append(run_search("host"))
            td.append(run_search("device"))
        h, d = statistics.median(th), statistics.median(td)
        detail["search_topk_shards_4"] = {
            "host_combine_s": round(h, 4),
            "device_combine_s": round(d, 4),
            "ratio": round(h / d, 2)}
    finally:
        _settings.set_global("serene_shards", 1)
        _settings.set_global("serene_result_cache", rc_prior)
        _settings.set_global("serene_shard_combine", cb_prior)

    _EXTRA["rows"] = npr
    _EXTRA["search_docs"] = 4 * seg_docs
    _EXTRA["detail"] = detail
    # end-to-end ratio recorded, not asserted (docstring): the exact
    # structural claims — bit parity and the 1-vs-N dispatch
    # decomposition — were asserted above
    return ratio4


def bench_device_observe() -> float:
    """Device telemetry overhead budget (ISSUE 15, <3%): the 1M-row
    fused join (the device_pipeline shape's workload) with
    `serene_device_telemetry` on vs off. Results are asserted
    bit-identical and the end-to-end alternating-pairs medians are
    recorded per mode — but like trace/mem_overhead (the PR 5/PR 10
    noise lesson) a sub-percent delta drowns in host drift end to end,
    so the ASSERTED number is a direct per-DISPATCH decomposition: the
    measured cost of one warm dispatch's actual telemetry traffic
    (compile-ledger hit probe + per-device dispatch note + one
    upload note + one fetch note + the enabled() reads), times the
    query's observed dispatch/transfer counts, divided by the off-mode
    median. Extras also record the cold-compile vs warm-hit latency
    split of the fused program (program LRU cleared → first dispatch
    pays the XLA compile; the ledger's compile_ms is the measured
    stall). Returns t_off/t_on (≈1.0; 0.97 ⇔ 3% overhead)."""
    import statistics

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec import device_pipeline as dp
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.obs import device as obs_device
    from serenedb_tpu.utils import metrics as _metrics
    from serenedb_tpu.utils.config import REGISTRY as _settings

    rng = np.random.default_rng(67)
    npr, nb, keyspace = 1_000_000, 200_000, 400_000
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE dto (jk BIGINT, g INT, v BIGINT)")
    c.execute("CREATE TABLE dtb (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["dto"] = MemTable("dto", Batch.from_pydict({
        "jk": Column.from_numpy(
            rng.integers(0, keyspace, npr, dtype=np.int64)),
        "g": Column.from_numpy(rng.integers(0, 16, npr).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, npr, dtype=np.int64))}))
    db.schemas["main"].tables["dtb"] = MemTable("dtb", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.permutation(np.arange(nb, dtype=np.int64))),
        "w": Column.from_numpy(
            rng.integers(0, 100, nb, dtype=np.int64))}))
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    c.execute("SET serene_result_cache = off")
    q = ("SELECT g, count(*), sum(v), sum(w) FROM dto "
         "JOIN dtb ON dto.jk = dtb.k WHERE v > 0 GROUP BY g ORDER BY g")

    old = _settings.get_global("serene_device_telemetry")
    try:
        # parity + warm-up (compile once, fill the data caches)
        _settings.set_global("serene_device_telemetry", True)
        rows_on = c.execute(q).rows()
        _settings.set_global("serene_device_telemetry", False)
        rows_off = c.execute(q).rows()
        assert rows_on == rows_off, "telemetry perturbed the fused join"

        # cold-compile vs warm-hit split (telemetry on so the ledger
        # measures the compile): program LRU cleared, data caches warm
        # → the delta IS the XLA compile stall
        _settings.set_global("serene_device_telemetry", True)
        obs_device.PROGRAMS.clear()
        t0 = time.perf_counter()
        c.execute(q)
        cold_s = time.perf_counter() - t0
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            c.execute(q)
            samples.append(time.perf_counter() - t0)
        warm_s = statistics.median(samples)
        fused_fam = [r for r in obs_device.PROGRAMS.snapshot()
                     if r["family"] == "fused"]
        compile_ms = fused_fam[0]["compile_ms_total"] if fused_fam else 0.0

        # per-query telemetry event counts (warm regime)
        led0 = obs_device.LEDGER.snapshot()
        off0 = _metrics.DEVICE_OFFLOADS.value
        c.execute(q)
        led1 = obs_device.LEDGER.snapshot()
        dispatches = max(1, _metrics.DEVICE_OFFLOADS.value - off0)

        def total(snap, field):
            return sum(d[field] for d in snap.values())

        transfers = (total(led1, "transfers_up") -
                     total(led0, "transfers_up")) + \
            (total(led1, "transfers_down") - total(led0, "transfers_down"))

        # e2e alternating pairs, recorded not asserted
        pairs = 7
        e2e: dict[str, list[float]] = {"on": [], "off": []}
        for _ in range(pairs):
            for mode, flag in (("off", False), ("on", True)):
                _settings.set_global("serene_device_telemetry", flag)
                t0 = time.perf_counter()
                c.execute(q)
                e2e[mode].append(time.perf_counter() - t0)
        med = {m: statistics.median(s) for m, s in e2e.items()}

        # direct decomposition: one warm dispatch's telemetry traffic,
        # probed at the real call sites' granularity
        _settings.set_global("serene_device_telemetry", True)
        probe_key = ("bench_probe",)
        prog = obs_device.compiled("bench_probe", probe_key,
                                   lambda: (lambda x: x))
        reps = 2000
        t0 = time.perf_counter()
        for _ in range(reps):
            obs_device.compiled("bench_probe", probe_key,
                                lambda: (lambda x: x))   # ledger hit
            obs_device.LEDGER.note_dispatch((0,))
            obs_device.note_upload(4096, (0,), 1000)
            obs_device.note_fetch(4096, (0,), 1000)
        per_event_s = (time.perf_counter() - t0) / reps
        assert prog is not None
        per_query_s = per_event_s * max(dispatches, transfers, 1)
        direct = per_query_s / med["off"]
    finally:
        _settings.set_global("serene_device_telemetry", old)

    _EXTRA["rows"] = npr
    _EXTRA["dispatches_per_query"] = dispatches
    _EXTRA["transfers_per_query"] = transfers
    _EXTRA["cold_compile_s"] = round(cold_s, 4)
    _EXTRA["warm_hit_s"] = round(warm_s, 4)
    _EXTRA["cold_vs_warm"] = round(cold_s / max(warm_s, 1e-9), 2)
    _EXTRA["fused_compile_ms"] = compile_ms
    _EXTRA["per_dispatch_telemetry_ms"] = round(per_event_s * 1e3, 5)
    _EXTRA["overhead_pct"] = round(direct * 100, 3)
    _EXTRA["e2e_overhead_pct"] = round(
        (med["on"] / med["off"] - 1.0) * 100, 2)
    assert direct < 0.03, \
        f"device telemetry over budget: {direct * 100:.2f}% (>3%)"
    return med["off"] / med["on"]


def bench_production() -> float:
    """The production mixed-fleet macrobench (ISSUE 20): a realistic
    serving day against the asyncio front door — dashboard clients
    re-running the same aggregate (result-cache hits between writer
    invalidations), live-search clients on ES `_search`, writer clients
    alternating `_bulk` appends with SQL INSERTs that invalidate the
    dashboards' cached aggregate, and ONE background heavy scan with a
    varying literal (never cache-served). The whole fleet speaks real
    HTTP/1.1 keep-alive over loopback from a single-thread asyncio
    client, so 512 clients is 512 concurrent SOCKETS against the tier —
    the thing PR 20 exists to survive — not 512 Python threads.

    Per fleet size (8 / 64 / 512) the extras record client-observed
    p50/p99 latency and qps PER CLASS (the acceptance numbers), plus
    the gate's accept-wait p99 and pause/reject counters. Returns
    qps_512 / qps_8 — total-throughput retention as the connection
    count scales 64x; a thread-per-connection tier degrades here, an
    event-loop tier should hold near (or above) 1.0."""
    import asyncio
    import resource

    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.engine import Database
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.sched.governor import CONNGATE
    from serenedb_tpu.server.http_server import HttpServer
    from serenedb_tpu.utils import metrics as _m
    from serenedb_tpu.utils.config import REGISTRY

    # 512 clients = 1024+ fds in this one process; lift the soft limit
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < 4096:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(hard, 4096), hard))
        except (ValueError, OSError):
            pass

    REGISTRY.set_global("serene_device", "cpu")
    REGISTRY.set_global("serene_frontdoor", True)
    REGISTRY.set_global("serene_max_connections", 0)
    REGISTRY.set_global("serene_idle_conn_timeout_s", 0.0)

    rng = np.random.default_rng(20)
    n_dash, n_big = 200_000, 2_000_000
    db = Database()
    boot = db.connect()
    boot.execute("CREATE TABLE dash (k INT, v BIGINT)")
    boot.execute("CREATE TABLE big (k INT, v BIGINT)")
    db.schemas["main"].tables["dash"] = MemTable("dash", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.integers(0, 200, n_dash).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(0, n_dash, n_dash, dtype=np.int64))}))
    db.schemas["main"].tables["big"] = MemTable("big", Batch.from_pydict({
        "k": Column.from_numpy(
            rng.integers(0, 1000, n_big).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(0, n_big, n_big, dtype=np.int64))}))

    srv = HttpServer(db, port=0)
    srv.start()
    port = srv.port
    words = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot",
             "golf", "hotel", "india", "juliet"]

    def _req(method, path, payload=b""):
        return (f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n\r\n"
                ).encode() + payload

    def _sql(q):
        return _req("POST", "/_sql",
                    json.dumps({"query": q}).encode())

    # seed the search corpus over the wire (the bulk path under test)
    seed_lines = []
    for i in range(2000):
        seed_lines.append(json.dumps(
            {"index": {"_index": "logs", "_id": str(i)}}))
        seed_lines.append(json.dumps(
            {"msg": " ".join(rng.choice(words, 6).tolist()),
             "n": int(i)}))
    import http.client
    hc = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    hc.request("POST", "/_bulk", "\n".join(seed_lines) + "\n",
               {"Content-Type": "application/x-ndjson"})
    r = hc.getresponse()
    r.read()
    assert r.status == 200

    DASH_Q = ("SELECT k, count(*), sum(v) FROM dash "
              "GROUP BY k ORDER BY k")

    # warm every class's cold path before any fleet measures: the
    # text index builds lazily on first search, the dashboard aggregate
    # pays its first (cache-miss) compute, the heavy scan compiles its
    # plan — none of that belongs in a serving percentile
    for w in words:
        hc.request("POST", "/logs/_search", json.dumps(
            {"query": {"match": {"msg": w}}, "size": 10}),
            {"Content-Type": "application/json"})
        r = hc.getresponse()
        r.read()
        assert r.status == 200
    for q in (DASH_Q, "SELECT count(*), sum(v % 11) FROM big "
                      "WHERE v % 13 <> 0"):
        hc.request("POST", "/_sql", json.dumps({"query": q}),
                   {"Content-Type": "application/json"})
        r = hc.getresponse()
        r.read()
        assert r.status == 200
    hc.close()

    class Cls:
        def __init__(self, name):
            self.name = name
            self.samples = []      # (t_done, latency_s)
            self.seq = 0

    async def _read_resp(reader):
        line = await reader.readline()
        if not line:
            raise ConnectionResetError
        status = int(line.split()[1])
        ln = 0
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            if h.lower().startswith(b"content-length"):
                ln = int(h.split(b":")[1])
        body = await reader.readexactly(ln) if ln else b""
        return status, body

    def build(cls, cid):
        if cls.name == "dashboard":
            return _sql(DASH_Q)
        if cls.name == "search":
            cls.seq += 1
            w = words[(cls.seq + cid) % len(words)]
            return _req("POST", "/logs/_search", json.dumps(
                {"query": {"match": {"msg": w}}, "size": 10}).encode())
        if cls.name == "writer":
            cls.seq += 1
            if cls.seq % 8:
                doc_id = f"w{cid}-{cls.seq}"
                nd = (json.dumps({"index": {"_index": "logs",
                                            "_id": doc_id}}) + "\n" +
                      json.dumps({"msg": " ".join(
                          words[(cls.seq + j) % len(words)]
                          for j in range(4)), "n": cls.seq}) + "\n")
                return _req("POST", "/_bulk", nd.encode())
            # every 8th write lands in `dash`, evicting the dashboards'
            # cached aggregate: the fleet's steady state is a MIX of
            # result-cache hits and real recomputes, like production
            return _sql(f"INSERT INTO dash VALUES "
                        f"({cls.seq % 200}, {cls.seq})")
        # heavy: varying literal defeats the result cache every time
        cls.seq += 1
        return _sql(f"SELECT count(*), sum(v % {11 + cls.seq % 7}) "
                    f"FROM big WHERE v % 13 <> {cls.seq % 13}")

    async def client(cls, cid, t_stop):
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
        except OSError:
            return
        try:
            while time.perf_counter() < t_stop:
                payload = build(cls, cid)
                t0 = time.perf_counter()
                writer.write(payload)
                await writer.drain()
                status, _body = await _read_resp(reader)
                t1 = time.perf_counter()
                if status == 200:
                    cls.samples.append((t1, t1 - t0))
        except (ConnectionResetError, asyncio.IncompleteReadError,
                OSError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, OSError):
                pass

    def pct(vals, q):
        if not vals:
            return None
        s = sorted(vals)
        return s[min(len(s) - 1, int(q * len(s)))]

    from serenedb_tpu.obs.statements import STATEMENTS, normalize
    dash_norm = normalize(DASH_Q)

    fleet_stats = {}
    measure_s, settle_s = 2.5, 0.5
    total_qps = {}
    for n_clients in (8, 64, 512):
        STATEMENTS.reset()
        classes = {n: Cls(n) for n in
                   ("dashboard", "search", "writer", "heavy")}
        # mixed fleet: 50% dashboards, ~30% search, ~10% writers,
        # ONE background heavy scan; remainder tops up search
        n_d = max(1, n_clients * 5 // 10)
        n_w = max(1, n_clients // 10)
        n_s = max(1, n_clients - n_d - n_w - 1)
        roster = (["dashboard"] * n_d + ["search"] * n_s +
                  ["writer"] * n_w + ["heavy"])

        async def fleet():
            t_stop = time.perf_counter() + settle_s + measure_s
            await asyncio.gather(*(
                client(classes[name], i, t_stop)
                for i, name in enumerate(roster)))

        t_start = time.perf_counter()
        asyncio.run(fleet())
        t_cut = t_start + settle_s
        per_class = {}
        n_total = 0
        for name, cls in classes.items():
            lats = [lat for (t, lat) in cls.samples if t >= t_cut]
            n_total += len(lats)
            per_class[name] = {
                "n": len(lats),
                "qps": round(len(lats) / measure_s, 1),
                "p50_ms": round((pct(lats, 0.50) or 0) * 1e3, 2),
                "p99_ms": round((pct(lats, 0.99) or 0) * 1e3, 2),
            }
        # the PR 10 statement histograms give the server-side view of
        # the SQL classes: the dashboard aggregate matches its exact
        # fingerprint, the heavy scan is the big-table fingerprint
        # (its varying literals collapse to `?` when normalized)
        for e in STATEMENTS.snapshot():
            if e["query"] == dash_norm:
                per_class["dashboard"]["stmt_p50_ms"] = e.get("p50_ms")
                per_class["dashboard"]["stmt_p99_ms"] = e.get("p99_ms")
            elif "from big" in e["query"]:
                per_class["heavy"]["stmt_p50_ms"] = e.get("p50_ms")
                per_class["heavy"]["stmt_p99_ms"] = e.get("p99_ms")
        fleet_stats[str(n_clients)] = per_class
        total_qps[n_clients] = n_total / measure_s
        print(f"  fleet={n_clients:4d}  total={n_total / measure_s:8.1f} "
              f"qps  dash p99="
              f"{per_class['dashboard']['p99_ms']:8.2f} ms  search p99="
              f"{per_class['search']['p99_ms']:8.2f} ms", flush=True)

    gate = CONNGATE.snapshot()
    wait_counts, _ = _m.ACCEPT_QUEUE_WAIT_HIST.snapshot()
    srv.stop()
    db.close()

    _EXTRA["fleet"] = fleet_stats
    _EXTRA["qps_8"] = round(total_qps[8], 1)
    _EXTRA["qps_64"] = round(total_qps[64], 1)
    _EXTRA["qps_512"] = round(total_qps[512], 1)
    _EXTRA["accepts"] = int(sum(wait_counts))
    _EXTRA["rejected_total"] = gate["rejected_total"]
    _EXTRA["pause_reads_total"] = gate["pause_reads_total"]
    # every class must have actually run at every fleet size — a silent
    # zero would ledger a vacuous mix
    for size, per_class in fleet_stats.items():
        for name, st in per_class.items():
            assert st["n"] > 0, f"class {name} starved at fleet {size}"
    return total_qps[512] / total_qps[8]


SHAPES = {
    "q1": bench_q1,
    "hits": bench_hits,
    "bm25": bench_bm25,
    "bm25_1m": bench_bm25_1m,
    "bm25_8m": bench_bm25_8m,
    "ingest": bench_ingest,
    "host_agg": bench_host_agg,
    "filter_scan": bench_filter_scan,
    "join": bench_join,
    "profile_overhead": bench_profile_overhead,
    "trace_overhead": bench_trace_overhead,
    "mem_overhead": bench_mem_overhead,
    "concurrency": bench_concurrency,
    "result_cache": bench_result_cache,
    "device_pipeline": bench_device_pipeline,
    "fused_admission": bench_fused_admission,
    "device_observe": bench_device_observe,
    "search_batch": bench_search_batch,
    "paged_search": bench_paged_search,
    "vector_search": bench_vector_search,
    "shard_exec": bench_shard_exec,
    "multichip": bench_multichip,
    "production": bench_production,
}

#: shapes whose ratio is a device-vs-CPU speedup and enters the headline
#: geomean; "ingest" is a host-side thread-scaling ratio, reported in
#: detail only.
HEADLINE_SHAPES = ("q1", "hits", "bm25", "bm25_1m", "bm25_8m")

#: shapes that never dispatch to a device: pure host-path measurements
HOST_SHAPES = ("ingest", "host_agg", "filter_scan", "join",
               "profile_overhead", "trace_overhead", "mem_overhead",
               "concurrency", "result_cache", "production")

#: shapes that measure the in-program multi-chip combine: their child
#: always runs on a 4-device VIRTUAL cpu mesh
#: (xla_force_host_platform_device_count=4 + JAX_PLATFORMS=cpu) — one
#: chip can't provide a real data axis, and XLA parses XLA_FLAGS once
#: per process so the env must be set before the child starts
VIRTUAL_MESH_SHAPES = ("multichip",)


# ------------------------------------------------------------- harness

#: side-channel for shapes to report extra metrics (HBM footprint, ...);
#: merged into the parent's detail dict as "<shape>_<key>"
_EXTRA: dict = {}


def _run_shape_child(name: str) -> None:
    """Child mode: run one shape, print its JSON result, exit non-zero
    on any failure."""
    try:
        if name in HOST_SHAPES:
            _EXTRA["platform"] = "host"
        else:
            from serenedb_tpu.utils.backend import init_backend
            _EXTRA["platform"] = init_backend()["platform"]
        # every shape times the SUBSYSTEM it measures: the result cache
        # would legitimately serve the repeat executions without running
        # them, so it is off by default in bench children — the
        # result_cache shape turns it back on per session
        from serenedb_tpu.utils.config import REGISTRY as _sdb_settings
        _sdb_settings.set_global("serene_result_cache", False)
        speedup = SHAPES[name]()
        print(json.dumps({"shape": name, "speedup": round(speedup, 4),
                          "extra": _EXTRA}),
              flush=True)
    except Exception as e:  # noqa: BLE001 — report, then fail the child
        print(json.dumps({"shape": name, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        sys.exit(1)


def _run_shape_subprocess(name: str, timeout_s: float) -> tuple[dict, str]:
    """Run one shape in a child process; returns (record, error)."""
    env = None
    if name in VIRTUAL_MESH_SHAPES:
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=4").strip()
        env["JAX_PLATFORMS"] = "cpu"
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--shape", name],
            capture_output=True, text=True, timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        return {}, f"timeout: shape exceeded {timeout_s:.0f}s"
    rec = None
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict):
            rec = parsed
            break
    if r.returncode == 0 and rec and \
            isinstance(rec.get("speedup"), (int, float)) \
            and rec["speedup"] > 0:
        return rec, ""
    msg = (rec or {}).get("error") or r.stderr[-400:] or "no output"
    return {}, str(msg)


def _required_platform(name: str) -> str:
    if name in HOST_SHAPES:
        return "host"
    if name in VIRTUAL_MESH_SHAPES:
        return "cpu"
    return "tpu"


def main(shape_names: list[str]) -> int:
    names = shape_names or list(SHAPES)
    bad = [n for n in names if n not in SHAPES]
    if bad:
        print(json.dumps({"errors": {"unknown_shapes": bad}}), flush=True)
        return 2
    timeout_s = float(os.environ.get("SDB_BENCH_SHAPE_TIMEOUT_S", "900"))
    results: dict[str, float] = {}
    extras: dict = {}
    errors: dict[str, str] = {}
    for name in names:
        rec, err = _run_shape_subprocess(name, timeout_s)
        if rec:
            platform = (rec.get("extra") or {}).get("platform")
            if platform != _required_platform(name):
                err = (f"ran on platform {platform!r}, requires "
                       f"{_required_platform(name)!r}")
                rec = {}
        if rec:
            results[name] = float(rec["speedup"])
            for ek, ev in (rec.get("extra") or {}).items():
                extras[f"{name}_{ek}"] = ev
        else:
            errors[name] = err
        print(json.dumps({"shape": name, "ok": bool(rec),
                          **({"speedup": results[name]} if rec
                             else {"error": err})}), flush=True)
    headline = {k: v for k, v in results.items() if k in HEADLINE_SHAPES}
    if headline:
        logs = [math.log(v) for v in headline.values()]
        value = round(math.exp(sum(logs) / len(logs)), 3)
    else:
        value = 0.0
    out = {
        "metric": METRIC,
        "value": value,
        "unit": "x",
        "vs_baseline": value,
        "detail": {**{f"{k}_speedup": v for k, v in results.items()},
                   **extras},
    }
    if errors:
        out["errors"] = errors
    print(json.dumps(out), flush=True)
    return 1 if errors else 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--shape":
        _run_shape_child(sys.argv[2])
    else:
        sys.exit(main(sys.argv[1:]))
