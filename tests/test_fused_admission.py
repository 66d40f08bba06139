"""PR 17: extended fused-tier admission + whole-query device residency.

Contract under test: every admission shape the fused pipeline gained —
FILTER / DISTINCT aggregates, string min/max over dictionary codes,
LEFT/RIGHT/FULL outer joins, residual join predicates, and the chained
agg→top-N handoff — is BIT-IDENTICAL to the host oracle
(`serene_device_fused = off`) across the full execution matrix
(workers 1/4 × shards 1/4 × zonemap on/off), and the machinery around
it holds:

- compile hygiene: varying row counts land in pow2 buckets, so the
  per-family compile counts stay bounded and `DeviceRecompileStorms`
  stays quiet;
- whole-query residency: a warm chained repeat moves ZERO host→device
  transfers (the stage-1 accumulators hand off to the top-N program
  inside HBM);
- decline observability: EXPLAIN ANALYZE's `Device:` line carries
  `declined=<reason>` and the per-reason counters accumulate;
- budget trade (`serene_device_cache_trade`): posting-pool residency
  squeezes the column cache's cap inside the one
  `serene_device_cache_mb` envelope, floored at a quarter of it.
"""

import pytest

from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS
from tests.test_device_pipeline import _mk_conn, _rows

# every NEW admission family; the host path is the oracle for each
NEW_SHAPES = [
    # FILTER aggregates (TRUE-only semantics; NULL predicate drops)
    "SELECT l.sk, count(*) FILTER (WHERE v > 0), sum(w) "
    "FROM l JOIN r ON l.ik = r.ik GROUP BY l.sk ORDER BY l.sk",
    "SELECT count(*) FILTER (WHERE w > 250), "
    "sum(v) FILTER (WHERE v < 0) FROM l JOIN r ON l.ik = r.ik",
    "SELECT l.ik, count(w) FILTER (WHERE w > 0), min(w) FILTER "
    "(WHERE w < 100) FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.ik ORDER BY l.ik NULLS LAST",
    # DISTINCT aggregates (probe-side presence grids)
    "SELECT l.sk, count(DISTINCT l.ik) FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.sk ORDER BY l.sk",
    "SELECT count(DISTINCT l.sk), sum(DISTINCT l.v) "
    "FROM l JOIN r ON l.ik = r.ik WHERE v > 400",
    "SELECT l.ik, count(DISTINCT l.sk), avg(DISTINCT l.v), count(*) "
    "FROM l JOIN r ON l.ik = r.ik GROUP BY l.ik ORDER BY l.ik NULLS LAST",
    # string min/max over sorted-dictionary codes
    "SELECT l.ik, min(l.sk), max(r.sk) FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.ik ORDER BY l.ik NULLS LAST",
    "SELECT min(r.sk), max(r.sk), count(*) FROM l JOIN r ON l.sk = r.sk "
    "WHERE v > 450",
    # residual join predicates (extra ON conjuncts beyond the equi-key)
    "SELECT l.sk, count(*), sum(w) FROM l JOIN r "
    "ON l.ik = r.ik AND l.v < r.w GROUP BY l.sk ORDER BY l.sk",
    "SELECT count(*), sum(v) FROM l JOIN r "
    "ON l.ik = r.ik AND r.w > 0 AND l.v > -400",
    # outer joins (NULL-extended rows land in the all-NULL key group)
    "SELECT l.sk, count(*), count(w), sum(w) FROM l LEFT JOIN r "
    "ON l.ik = r.ik GROUP BY l.sk ORDER BY l.sk",
    "SELECT r.sk, count(*), sum(l.v) FROM l RIGHT JOIN r "
    "ON l.ik = r.ik GROUP BY r.sk ORDER BY r.sk",
    "SELECT l.sk, count(*), min(w), max(w) FROM l FULL JOIN r "
    "ON l.ik = r.ik GROUP BY l.sk ORDER BY l.sk",
    "SELECT count(*), count(l.v), count(r.w), sum(l.bv) "
    "FROM l FULL JOIN r ON l.sk = r.sk",
    # combinations across the new families
    "SELECT l.sk, count(DISTINCT l.ik), min(r.sk), "
    "count(*) FILTER (WHERE w > 0) FROM l LEFT JOIN r ON l.ik = r.ik "
    "GROUP BY l.sk ORDER BY l.sk",
]

CHAINED_SHAPES = [
    "SELECT l.ik, count(*) AS n FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.ik ORDER BY n DESC LIMIT 5",
    "SELECT l.sk, count(*), sum(w) FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.sk ORDER BY l.sk LIMIT 3",
    "SELECT l.ik, count(w) AS c FROM l LEFT JOIN r ON l.ik = r.ik "
    "GROUP BY l.ik ORDER BY c LIMIT 4 OFFSET 2",
    "SELECT count(*) AS n, l.sk FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.sk ORDER BY l.sk DESC LIMIT 2",
]


@pytest.mark.parametrize("q", NEW_SHAPES + CHAINED_SHAPES)
def test_new_shape_parity_matrix(q):
    """workers 1/4 × shards 1/4 × zonemap on/off, oracle = fused off."""
    c = _mk_conn()
    c.execute("SET serene_device_fused = off")
    c.execute("SET serene_workers = 1")
    oracle = _rows(c, q)
    c.execute("SET serene_device_fused = on")
    for workers in (1, 4):
        c.execute(f"SET serene_workers = {workers}")
        for shards in (1, 4):
            c.execute(f"SET serene_shards = {shards}")
            for zm in ("on", "off"):
                c.execute(f"SET serene_zonemap = {zm}")
                got = _rows(c, q)
                assert got == oracle, (
                    f"diverged (workers={workers}, shards={shards}, "
                    f"zonemap={zm}): {q}")


def test_ext_off_restores_walls():
    """`serene_device_fused_ext = off` is the PR-7 oracle switch: the
    new shapes still answer (host fallback) and stay bit-identical."""
    c = _mk_conn()
    c.execute("SET serene_device_fused_ext = off")
    for q in NEW_SHAPES[:4]:
        on = _rows(c, q)
        c.execute("SET serene_device_fused = off")
        assert _rows(c, q) == on
        c.execute("SET serene_device_fused = on")


# -- compile hygiene ---------------------------------------------------------


def _family(name: str) -> dict:
    for p in obs_device.stats_section()["programs"]:
        if p["family"] == name:
            return p
    return {"compiles": 0, "storms": 0}


def test_row_count_churn_stays_in_pow2_buckets():
    """The same query over 6 different table sizes inside one pow2
    bucket pair must reuse ONE fused executable; crossing a bucket
    boundary may add one more — never one per size. Storms stay 0."""
    q = ("SELECT l.sk, count(*), count(DISTINCT l.ik) FROM l "
         "JOIN r ON l.ik = r.ik GROUP BY l.sk ORDER BY l.sk")
    storms0 = metrics.DEVICE_RECOMPILE_STORMS.value
    fam0 = _family("fused")["storms"]
    c0 = _family("fused")["compiles"]
    buckets = set()
    for nl, nr in ((4100, 2100), (4600, 2300), (5200, 2700),
                   (6000, 3000), (7100, 3500), (8100, 3900)):
        c = _mk_conn(nl=nl, nr=nr)
        got = _rows(c, q)
        c.execute("SET serene_device_fused = off")
        assert got == _rows(c, q), f"diverged at nl={nl}"
        from serenedb_tpu.exec.device_pipeline import _pow2_rows
        buckets.add((_pow2_rows(nl), _pow2_rows(nr)))
    compiled = _family("fused")["compiles"] - c0
    assert compiled <= len(buckets), (
        f"{compiled} fused compiles across 6 sizes in {len(buckets)} "
        f"pow2 buckets — bucketing failed")
    # deltas, not absolutes: earlier tests in the process legitimately
    # compile many DISTINCT query shapes in under a minute (the detector
    # fires on those by design); row-count churn must add none
    assert metrics.DEVICE_RECOMPILE_STORMS.value == storms0
    assert _family("fused")["storms"] == fam0


# -- whole-query residency ---------------------------------------------------


def _require_ext():
    """verify_tier1 pass 16 leg (b) forces the PR-7 walls back
    globally; the chained-device assertions are vacuous there."""
    if not SETTINGS.get_global("serene_device_fused_ext"):
        pytest.skip("serene_device_fused_ext forced off for this pass")


def test_chained_warm_repeat_zero_uploads():
    """After the cold run uploads the columns, a chained agg→top-N
    repeat is fully device-resident: zero host→device transfers, both
    program families warm, and the chained-stage gauge advances."""
    _require_ext()
    c = _mk_conn()
    q = ("SELECT l.ik, count(*) AS n FROM l JOIN r ON l.ik = r.ik "
         "GROUP BY l.ik ORDER BY n DESC LIMIT 5")
    chain0 = metrics.REGISTRY.gauge("DeviceChainedStages").value
    cold = _rows(c, q)
    assert metrics.REGISTRY.gauge("DeviceChainedStages").value > chain0, \
        "chained device path did not fire"
    ups0 = metrics.DEVICE_TRANSFERS_UP.value
    assert _rows(c, q) == cold
    assert metrics.DEVICE_TRANSFERS_UP.value == ups0, \
        "warm chained repeat moved host→device bytes"


def test_chained_declines_unsupported_sort_key():
    """min/max/sum sort keys have no NULL-consistent device order: the
    chain declines (reason recorded), the host answers, results match."""
    _require_ext()
    c = _mk_conn()
    q = ("SELECT l.ik, min(w) AS m FROM l JOIN r ON l.ik = r.ik "
         "GROUP BY l.ik ORDER BY m LIMIT 4")
    before = obs_device.fused_declines().get("chain_sort_key", 0)
    on = _rows(c, q)
    assert obs_device.fused_declines().get("chain_sort_key", 0) > before
    c.execute("SET serene_device_fused = off")
    assert _rows(c, q) == on


# -- decline observability ---------------------------------------------------


def test_explain_analyze_declined_reason():
    c = _mk_conn()
    # float aggregate argument: exactness wall → agg_type decline
    q = ("EXPLAIN ANALYZE SELECT l.sk, sum(l.fk) FROM l "
         "JOIN r ON l.ik = r.ik GROUP BY l.sk ORDER BY l.sk")
    before = obs_device.fused_declines().get("agg_type", 0)
    lines = [r[0] for r in c.execute(q).rows()]
    assert any("declined=agg_type" in ln for ln in lines), lines
    assert obs_device.fused_declines().get("agg_type", 0) > before
    # the per-reason counters surface in the device stats section
    assert obs_device.stats_section()["fused_declines"]["agg_type"] > 0


# -- budget trade ------------------------------------------------------------


def test_cache_cap_trades_against_pool_residency():
    from serenedb_tpu.exec.device_pipeline import DEVICE_CACHE
    from serenedb_tpu.search.vector_store import VPOOL

    env = int(SETTINGS.get_global("serene_device_cache_mb")) << 20
    old_trade = SETTINGS.get_global("serene_device_cache_trade")
    try:
        SETTINGS.set_global("serene_device_cache_trade", True)
        live = VPOOL.live_bytes()
        cap = DEVICE_CACHE.stats()["cap_bytes"]
        assert cap == max(env // 4, env - live)
        SETTINGS.set_global("serene_device_cache_trade", False)
        assert DEVICE_CACHE.stats()["cap_bytes"] == env
    finally:
        SETTINGS.set_global("serene_device_cache_trade", old_trade)


def test_pool_sheds_colder_tail():
    """shed_colder frees LRU pages idle longer than the threshold and
    stops at the first warmer entry — the column cache's cross-eviction
    primitive."""
    from serenedb_tpu.search.vector_store import PAGE_F32, VPOOL, _Entry

    page = PAGE_F32 * 4
    VPOOL.clear()
    with VPOOL._lock:
        VPOOL._region()
        # hand-plant two entries: a cold tail and a hot head
        slots_a = VPOOL._alloc(2, set())
        slots_b = VPOOL._alloc(1, set())
        ea = _Entry(1, slots_a, 8, 128, 1, None)
        eb = _Entry(2, slots_b, 4, 128, 2, None)
        import time as _t
        ea.last_ns = _t.perf_counter_ns() - int(60e9)   # idle 60 s
        VPOOL._entries[ea.key] = ea
        VPOOL._entries[eb.key] = eb
    assert VPOOL.live_bytes() == 3 * page
    # threshold 30 s: only the 60 s-idle tail qualifies
    freed = VPOOL.shed_colder(int(30e9), 10 * page)
    assert freed == 2 * page
    assert VPOOL.live_bytes() == page
    # the warm survivor blocks further shedding
    assert VPOOL.shed_colder(int(30e9), page) == 0
    VPOOL.clear()
