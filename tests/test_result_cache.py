"""Multi-tier query cache (ISSUE 5): correctness first.

The wall is determinism — cached and uncached executions must be
bit-identical at any `serene_workers`, and a write interleaved between
two identical statements must always surface fresh data. Everything
else (gauges, sdb_cache, LRU order, fragment survival) is attribution.
"""

import numpy as np
import pytest

from serenedb_tpu.cache.fragments import FRAGMENTS
from serenedb_tpu.cache.lru import BytesLRU
from serenedb_tpu.cache.result import RESULT_CACHE
from serenedb_tpu.columnar.column import Batch, Column
from serenedb_tpu.engine import Database
from serenedb_tpu.exec.tables import MemTable
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS


def _mk(n=5000, seed=7):
    rng = np.random.default_rng(seed)
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE t (k INT, v BIGINT, s TEXT)")
    words = np.asarray(["ash", "birch", "cedar", "oak", None], dtype=object)
    db.schemas["main"].tables["t"] = MemTable("t", Batch.from_pydict({
        "k": Column.from_numpy(rng.integers(0, 50, n).astype(np.int32)),
        "v": Column.from_numpy(
            rng.integers(-1000, 1000, n, dtype=np.int64)),
        "s": Column.from_pylist(list(words[rng.integers(0, 5, n)])),
    }))
    c.execute("SET serene_device = 'cpu'")
    return db, c


QUERIES = (
    "SELECT k, count(*), sum(v) FROM t GROUP BY k ORDER BY k",
    "SELECT s, min(v), max(v) FROM t WHERE v > 0 GROUP BY s ORDER BY s",
    "SELECT DISTINCT k FROM t WHERE v % 3 = 0 ORDER BY k LIMIT 10",
    "SELECT a.k, count(*) FROM t a JOIN t b ON a.k = b.k "
    "WHERE a.v > 900 GROUP BY a.k ORDER BY a.k",
)


def _hits():
    return metrics.RESULT_CACHE_HITS.value


def _misses():
    return metrics.RESULT_CACHE_MISSES.value


# -- hit/miss parity matrix -------------------------------------------------

def test_parity_cached_vs_uncached_across_workers():
    """Bit-identical results: cache on/off × workers 1/4 × repeat runs.
    The second cached run is a hit (gauge-asserted) and still equals the
    uncached oracle."""
    db, c = _mk()
    for q in QUERIES:
        baseline = None
        for cache in ("off", "on"):
            for workers in (1, 4):
                c.execute(f"SET serene_result_cache = {cache}")
                c.execute(f"SET serene_workers = {workers}")
                first = c.execute(q).rows()
                h0 = _hits()
                again = c.execute(q).rows()
                if baseline is None:
                    baseline = first
                assert first == baseline, (q, cache, workers)
                assert again == baseline, (q, cache, workers)
                if cache == "on":
                    assert _hits() > h0, f"expected a hit: {q}"


def test_settings_digest_partitions_entries():
    """Result-affecting settings are part of the key: flipping one
    creates a separate entry instead of serving the other digest's."""
    db, c = _mk(n=1000)
    q = QUERIES[0]
    c.execute("SET serene_device = 'cpu'")
    r_cpu = c.execute(q).rows()
    m0 = _misses()
    c.execute("SET serene_device = 'auto'")
    r_auto = c.execute(q).rows()
    assert _misses() > m0          # different digest ⇒ no cross-serve
    assert r_cpu == r_auto         # and identical data either way


def test_literal_and_param_values_key_separately():
    db, c = _mk(n=500)
    a = c.execute("SELECT count(*) FROM t WHERE k < 10").scalar()
    b = c.execute("SELECT count(*) FROM t WHERE k < 40").scalar()
    assert a < b                    # same fingerprint, different literals
    pa = c.execute("SELECT count(*) FROM t WHERE k < $1", [10]).scalar()
    pb = c.execute("SELECT count(*) FROM t WHERE k < $1", [40]).scalar()
    assert (pa, pb) == (a, b)


def test_multi_statement_text_no_cross_serve():
    db, c = _mk(n=100)
    for _ in range(2):   # second round would serve both from cache
        r = c.execute_all("SELECT count(*) FROM t WHERE k < 5; "
                          "SELECT count(*) FROM t WHERE k >= 5")
        assert r[0].scalar() + r[1].scalar() == 100
        assert r[0].scalar() != r[1].scalar()


# -- write interleaving: zero stale reads -----------------------------------

def test_write_between_identical_statements_always_fresh():
    db, c = _mk(n=2000)
    q = "SELECT count(*), sum(v) FROM t"
    base = c.execute(q).rows()[0]
    for i in range(1, 6):
        c.execute(f"INSERT INTO t VALUES (99, {1000 + i}, 'new')")
        got = c.execute(q).rows()[0]
        assert got[0] == base[0] + i, f"stale count after write {i}"
        # repeat WITHOUT a write: must hit and still be the fresh data
        h0 = _hits()
        assert c.execute(q).rows()[0] == got
        assert _hits() > h0


def test_update_delete_truncate_invalidate():
    db, c = _mk(n=1000)
    q = "SELECT count(*) FROM t WHERE v > 0"
    n1 = c.execute(q).scalar()
    c.execute("UPDATE t SET v = -1 WHERE v > 0")
    assert c.execute(q).scalar() == 0
    c.execute("INSERT INTO t VALUES (1, 5, 'x')")
    assert c.execute(q).scalar() == 1
    c.execute("DELETE FROM t WHERE v = 5")
    assert c.execute(q).scalar() == 0
    c.execute("TRUNCATE t")
    assert c.execute("SELECT count(*) FROM t").scalar() == 0
    assert n1 > 0


def test_cross_connection_write_invalidates():
    db, c = _mk(n=500)
    c2 = db.connect()
    q = "SELECT count(*) FROM t"
    n = c.execute(q).scalar()
    c2.execute("INSERT INTO t VALUES (1, 1, 'w')")
    assert c.execute(q).scalar() == n + 1


def test_drop_recreate_same_name_never_collides():
    db, c = _mk(n=10)
    q = "SELECT count(*) FROM t"
    assert c.execute(q).scalar() == 10
    c.execute("DROP TABLE t")
    c.execute("CREATE TABLE t (k INT, v BIGINT, s TEXT)")
    c.execute("INSERT INTO t VALUES (1, 1, 'a')")
    # fresh generation at (version, epoch) the old table also had once:
    # the publication token keeps the keys apart
    assert c.execute(q).scalar() == 1


def test_txn_statements_bypass_cache():
    db, c = _mk(n=100)
    q = "SELECT count(*) FROM t"
    n = c.execute(q).scalar()            # cached outside the txn
    c.execute("BEGIN")
    c.execute("INSERT INTO t VALUES (1, 1, 'x')")
    assert c.execute(q).scalar() == n + 1   # read-your-writes, no cache
    c.execute("ROLLBACK")
    assert c.execute(q).scalar() == n


# -- volatility gating ------------------------------------------------------

def test_volatile_functions_never_cache():
    db, c = _mk(n=50)
    before = len(RESULT_CACHE.snapshot())
    r1 = c.execute("SELECT sum(v + random()) FROM t").scalar()
    r2 = c.execute("SELECT sum(v + random()) FROM t").scalar()
    assert r1 != r2
    assert not any("random" in e["query"]
                   for e in RESULT_CACHE.snapshot()[before:])


def test_stable_functions_never_cache():
    """now() is statement-stable but NOT cacheable across statements —
    a cached entry would freeze the clock."""
    db, c = _mk(n=10)
    q = "SELECT k, now() FROM t LIMIT 1"
    c.execute(q)
    assert not any("now" in e["query"] for e in RESULT_CACHE.snapshot())
    m0 = _misses()
    h0 = _hits()
    c.execute(q)
    assert _hits() == h0 and _misses() == m0   # not even probed


def test_values_scalar_subquery_never_caches_stale():
    """The planner evaluates scalar subqueries inside VALUES at plan
    time and materializes the rows — the subplan's tables never reach
    the publication key, so these statements must refuse caching
    entirely or a write to the inner table would go unseen."""
    db, c = _mk(n=10)
    c.execute("CREATE TABLE u (x INT)")
    c.execute("INSERT INTO u VALUES (1)")
    q = "SELECT * FROM (VALUES ((SELECT count(*) FROM u))) v"
    assert c.execute(q).rows() == [(1,)]
    c.execute("INSERT INTO u VALUES (2)")
    assert c.execute(q).rows() == [(2,)]
    # same hole via IN/EXISTS inside VALUES-adjacent expressions: the
    # AST screen refuses every subquery-expression form
    q2 = "SELECT * FROM (VALUES ((SELECT max(x) FROM u))) v"
    assert c.execute(q2).rows() == [(2,)]
    c.execute("UPDATE u SET x = 7 WHERE x = 2")
    assert c.execute(q2).rows() == [(7,)]


def test_sdb_introspection_never_caches():
    db, c = _mk(n=10)
    r1 = c.execute("SELECT count(*) FROM sdb_metrics()").scalar()
    c.execute("SELECT count(*) FROM t")
    r2 = c.execute("SELECT count(*) FROM sdb_metrics()").scalar()
    assert r1 > 0 and r2 > 0    # live engine state, rebuilt per query


# -- bytes-LRU --------------------------------------------------------------

def test_bytes_lru_eviction_order():
    lru = BytesLRU()
    for i in range(4):
        assert lru.put(i, f"v{i}", 100, 350)
    # inserting 4x100 bytes under a 350 cap evicted the oldest
    assert lru.get(0) is None and lru.get(1) == "v1"
    # get(1) refreshed recency: inserting one more evicts 2, not 1
    assert lru.put(9, "v9", 100, 350)
    assert lru.get(2) is None and lru.get(1) == "v1"
    # an entry larger than the whole cap is refused
    assert not lru.put(10, "big", 400, 350)
    assert lru.total_bytes == 300


def test_result_cache_respects_byte_cap_and_evicts():
    old = SETTINGS.get_global("serene_result_cache_mb")
    db, c = _mk(n=200_000)
    try:
        SETTINGS.set_global("serene_result_cache_mb", 1)   # 1 MB
        e0 = metrics.RESULT_CACHE_EVICTIONS.value
        # each projection result is ~1.6MB (200k rows × int64) — bigger
        # than the cap, refused; the aggregate results are tiny and stay
        big = "SELECT v FROM t"
        c.execute(big)
        for i in range(5):
            c.execute(f"SELECT count(*) FROM t WHERE k < {i + 1}")
        assert metrics.RESULT_CACHE_BYTES.value <= 1 << 20
        snap = RESULT_CACHE.snapshot()
        assert not any(e["query"] == "select v from t" for e in snap)
        assert metrics.RESULT_CACHE_EVICTIONS.value >= e0
    finally:
        SETTINGS.set_global("serene_result_cache_mb", old)


def test_session_off_switch():
    db, c = _mk(n=100)
    c.execute("SET serene_result_cache = off")
    q = "SELECT count(*) FROM t WHERE k = 7"
    h0, m0 = _hits(), _misses()
    c.execute(q)
    c.execute(q)
    assert _hits() == h0 and _misses() == m0
    c.execute("SET serene_result_cache = on")
    c.execute(q)
    h1 = _hits()
    c.execute(q)
    assert _hits() == h1 + 1


# -- views ------------------------------------------------------------------

def test_view_redefinition_never_serves_stale():
    db, c = _mk(n=100)
    c.execute("CREATE VIEW hi AS SELECT k FROM t WHERE v > 0")
    a = c.execute("SELECT count(*) FROM hi").scalar()
    c.execute("CREATE OR REPLACE VIEW hi AS SELECT k FROM t WHERE v <= 0")
    b = c.execute("SELECT count(*) FROM hi").scalar()
    assert a + b == 100


# -- fragment cache ---------------------------------------------------------

def _mk_search():
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE d (id INT, body TEXT)")
    c.execute("INSERT INTO d VALUES (1,'red fox jumps'),"
              "(2,'lazy dog naps'),(3,'red dog runs'),(4,'gray owl')")
    c.execute("CREATE INDEX ON d USING inverted (body)")
    return db, c


def test_fragment_cache_hit_and_parity():
    db, c = _mk_search()
    # two DIFFERENT statements sharing one filter predicate: the result
    # tier misses (distinct statement digests) while the per-segment
    # filter fragment for 'red' is computed once and reused
    r1 = c.execute(
        "SELECT id FROM d WHERE body ## 'red' ORDER BY id").rows()
    f0 = metrics.FRAGMENT_CACHE_HITS.value
    n = c.execute("SELECT count(*) FROM d WHERE body ## 'red'").scalar()
    assert r1 == [(1,), (3,)] and n == 2
    assert metrics.FRAGMENT_CACHE_HITS.value > f0


def test_fragment_survives_append_not_mutation():
    db, c = _mk_search()
    q = "SELECT id FROM d WHERE body ## 'red' ORDER BY id"
    assert c.execute(q).rows() == [(1,), (3,)]
    t = db.schemas["main"].tables["d"]
    idx = list(t.indexes.values())[0]
    seg_before = idx.searchers["body"].segments[0][0]
    # append → refresh adds a segment; the OLD segment object (and its
    # cached fragments) must survive
    c.execute("INSERT INTO d VALUES (5, 'red crow')")
    f0 = metrics.FRAGMENT_CACHE_HITS.value
    assert c.execute(q).rows() == [(1,), (3,), (5,)]
    idx2 = list(t.indexes.values())[0]
    segs_after = [s for s, _b in idx2.searchers["body"].segments]
    assert seg_before in segs_after and len(segs_after) == 2
    assert metrics.FRAGMENT_CACHE_HITS.value > f0   # old fragment reused
    # mutation → full rebuild: new segment objects, fresh results
    c.execute("UPDATE d SET body = 'blue jay' WHERE id = 1")
    assert c.execute(q).rows() == [(3,), (5,)]
    idx3 = list(t.indexes.values())[0]
    assert seg_before not in [s for s, _b in
                              idx3.searchers["body"].segments]


def test_fragment_finalizer_lock_free_and_deferred():
    """drop_segment is a weakref-finalizer target: GC can run it on a
    thread that is ALREADY inside the cache holding its lock (observed
    as a tier-1 deadlock at sqllogic sdb/search tests), so it must only
    enqueue — reclaim happens at the next cache operation."""
    import threading as _threading

    from serenedb_tpu.cache.fragments import FRAGMENTS
    db, c = _mk_search()
    c.execute("SELECT id FROM d WHERE body ## 'red' ORDER BY id")
    done = _threading.Event()

    def finalizer_while_locked():
        FRAGMENTS.drop_segment(999_999_999)
        done.set()

    with FRAGMENTS._lock:                   # the interrupted frame
        t = _threading.Thread(target=finalizer_while_locked, daemon=True)
        t.start()
        t.join(timeout=10)
    assert done.is_set(), "drop_segment blocked on the cache lock"
    assert 999_999_999 in list(FRAGMENTS._pending_drops)
    FRAGMENTS._drain_drops()                # next cache op reclaims
    assert 999_999_999 not in list(FRAGMENTS._pending_drops)


def test_fragment_cache_disabled_with_session_switch():
    db, c = _mk_search()
    c.execute("SET serene_result_cache = off")
    q = "SELECT id FROM d WHERE body ## 'dog' ORDER BY id"
    c.execute(q)
    h0, m0 = (metrics.FRAGMENT_CACHE_HITS.value,
              metrics.FRAGMENT_CACHE_MISSES.value)
    c.execute(q)
    assert (metrics.FRAGMENT_CACHE_HITS.value,
            metrics.FRAGMENT_CACHE_MISSES.value) == (h0, m0)


# -- observability ----------------------------------------------------------

def test_sdb_cache_and_stat_statements_attribution():
    db, c = _mk(n=300)
    q = "SELECT k, sum(v) FROM t GROUP BY k ORDER BY k"
    c.execute(q)
    c.execute(q)
    c.execute(q)
    rows = c.execute(
        "SELECT query, hits, bytes FROM sdb_cache() "
        "WHERE tier = 'result' AND query LIKE '%group by k%'").rows()
    assert rows and any(r[1] >= 2 for r in rows)
    assert all(r[2] > 0 for r in rows)
    ss = c.execute(
        "SELECT calls, cache_hits FROM sdb_stat_statements() "
        "WHERE query LIKE '%sum ( v ) from t group by%'").rows()
    assert ss and ss[0][0] >= 3 and ss[0][1] >= 2
    # the objects column names the source table
    assert any("main.t" in r[0] for r in c.execute(
        "SELECT objects FROM sdb_cache() WHERE tier='result'").rows())


def test_explain_analyze_reports_cache_state():
    db, c = _mk(n=100)
    q = "SELECT count(*) FROM t WHERE k < 9"
    lines = [r[0] for r in c.execute(f"EXPLAIN ANALYZE {q}").rows()]
    assert "Result Cache: miss" in lines
    lines = [r[0] for r in c.execute(f"EXPLAIN ANALYZE {q}").rows()]
    assert "Result Cache: hit" in lines
    # and ANALYZE still really executed: per-operator actuals present
    assert any("actual time=" in ln for ln in lines)


def test_streaming_path_hits_and_stores():
    db, c = _mk(n=2000)
    from serenedb_tpu.sql import parser
    q = "SELECT k, count(*) FROM t GROUP BY k ORDER BY k"
    st = parser.parse(q)[0]
    names, types, it = c.execute_streaming(st, sql_text=q)
    streamed = [tuple(r) for b in it for r in b.rows()]
    h0 = _hits()
    names2, types2, it2 = c.execute_streaming(st, sql_text=q)
    streamed2 = [tuple(r) for b in it2 for r in b.rows()]
    assert _hits() > h0
    assert streamed == streamed2 == [tuple(r)
                                     for r in c.execute(q).rows()]
    assert names == names2


def test_sweep_reclaims_superseded_generations():
    db, c = _mk(n=100)
    # a table name unique to THIS test: the process-wide cache may
    # still hold entries for other suites' tables named `t` whose
    # normalized text would collide with the label counted below
    c.execute("CREATE TABLE sweep_gen_t (k INT)")
    c.execute("INSERT INTO sweep_gen_t VALUES (7)")
    q = "SELECT count(*) FROM sweep_gen_t"
    c.execute(q)
    c.execute("INSERT INTO sweep_gen_t VALUES (1)")
    c.execute(q)
    # two generations of the same statement live until the lazy sweep
    # (one, where the second store was the process-wide cache's
    # SWEEP_EVERY-th and swept by itself)
    label = "select count ( * ) from sweep_gen_t"
    live = [e["query"] for e in RESULT_CACHE.snapshot()].count(label)
    assert live in (1, 2)
    assert RESULT_CACHE.sweep() >= live - 1
    labels = [e["query"] for e in RESULT_CACHE.snapshot()]
    assert labels.count(label) == 1


def test_prometheus_and_stats_export_cache_sections():
    from serenedb_tpu.obs.export import prometheus_text, stats_json
    db, c = _mk(n=50)
    q = "SELECT count(*) FROM t"
    c.execute(q)
    c.execute(q)
    text = prometheus_text()
    assert "serenedb_result_cache_hits" in text
    assert "serenedb_statement_cache_hits" in text
    s = stats_json()
    assert s["cache"]["result"]["entries"] >= 1
    assert "fragments" in s["cache"]
