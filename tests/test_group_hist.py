"""The tiled MXU histogram (ops/agg.py: `group_count_hist`) against the
serial scatter and a numpy reference, and the `device_agg` programs that
pick between the two forms (`hist_form`). The CPU backend runs the kernel
through the Pallas interpreter; left alone it picks the scatter, so the
tests that drive SQL through the histogram steer `_lowers_hist`. Under
the mesh wrap's shard_map the interpreter cannot evaluate a kernel body
(its literals carry no varying axes); that the Mosaic kernel partitions
is `test_hist_tpu_compile.py`'s to show."""

import jax.numpy as jnp
import numpy as np
import pytest

from serenedb_tpu.columnar.column import Batch, Column
from serenedb_tpu.engine import Database
from serenedb_tpu.exec import device_agg
from serenedb_tpu.exec.tables import MemTable
from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.ops import agg
from serenedb_tpu.utils import metrics

LIMIT = agg.HIST_MAX_CELLS
CELLS = [1, 18, 64, 1024, 1025, 4096, 113503, 131072, LIMIT, LIMIT + 1]


def _tiles(a: np.ndarray, fill) -> jnp.ndarray:
    pad = (-len(a)) % 128
    return jnp.asarray(np.concatenate(
        [a, np.full(pad, fill, a.dtype)]).reshape(-1, 128))


@pytest.fixture
def hist_on(monkeypatch):
    """The form a TPU picks, on this backend (the kernel interprets)."""
    monkeypatch.setattr(agg, "_lowers_hist", lambda: True)


@pytest.mark.parametrize("mask_kind", ["none", "all", "random"])
@pytest.mark.parametrize("cells", CELLS)
def test_hist_matches_scatter_and_bincount(cells, mask_kind):
    rng = np.random.default_rng(cells)
    # not a multiple of the kernel's row tile, nor of the 128-lane tile
    n = agg.HIST_TILE_ROWS + 777
    codes = rng.integers(0, cells, n).astype(np.int32)
    codes[:3] = cells - 1                      # the last cell is hit
    mask = {"none": np.zeros(n, bool), "all": np.ones(n, bool),
            "random": rng.random(n) < 0.6}[mask_kind]
    want = np.bincount(codes[mask], minlength=cells)
    c, m = _tiles(codes, 0), _tiles(mask, False)
    hist = np.asarray(agg.group_count_hist(c, m, cells))
    scat = np.asarray(agg.group_count_scatter(c, m, cells))
    assert hist.dtype == scat.dtype == np.int32
    np.testing.assert_array_equal(hist, want)
    np.testing.assert_array_equal(scat, want)


def test_ladder_and_limit(hist_on):
    h, l = agg.hist_shape(LIMIT)
    assert h * l == LIMIT
    assert agg.hist_form(LIMIT) and not agg.hist_form(LIMIT + 1)
    # near-equal group spaces share one accumulator shape
    assert agg.hist_shape(113503) == agg.hist_shape(118953) == (256, 512)
    assert agg.hist_shape(1) == agg.hist_shape(64) == (32, 128)
    shapes = {agg.hist_shape(c) for c in range(1, LIMIT + 1, 997)}
    assert len(shapes) == 8 and all(
        a & (a - 1) == 0 and b & (b - 1) == 0 for a, b in shapes)


def test_form_needs_a_backend_that_lowers_the_kernel():
    assert not agg.hist_form(64)               # the CPU backend: scatter


def test_one_cell_past_a_tile_is_exact():
    # every row in ONE cell: the count passes one grid step's partial
    # (HIST_TILE_ROWS), int8's and bf16's ranges, and 2^16
    n = 300_000 + 5
    codes = np.full(n, 17, np.int32)
    mask = np.ones(n, bool)
    mask[::1000] = False
    got = np.asarray(agg.group_count_hist(
        _tiles(codes, 0), _tiles(mask, False), 18))
    want = np.zeros(18, np.int64)
    want[17] = int(mask.sum())
    np.testing.assert_array_equal(got, want)


def _presence_inputs(rng, n, groups, vsize, lo):
    g = rng.integers(0, groups, n).astype(np.int32)
    v = rng.integers(lo, lo + vsize - 1, n).astype(np.int32)
    ok = rng.random(n) > 0.2                   # NULL values
    mask = rng.random(n) < 0.7                 # the filter
    arrays = {0: (_tiles(v, lo), _tiles(ok, False))}
    return g, v, ok, mask, arrays


@pytest.mark.parametrize("groups,vsize", [(1, 300), (7, 41), (60, 2000)])
def test_presence_forms_agree(monkeypatch, groups, vsize):
    rng = np.random.default_rng(groups)
    lo = -5
    g, v, ok, mask, arrays = _presence_inputs(rng, 3000, groups, vsize, lo)
    want = np.zeros((groups, vsize), np.int32)
    live = mask & ok
    want[g[live], v[live] - lo] = 1
    got = {}
    for lowers in (False, True):
        monkeypatch.setattr(agg, "_lowers_hist", lambda lowers=lowers: lowers)
        got[lowers] = np.asarray(device_agg._presence(
            ("int", 0, lo, vsize), arrays, _tiles(g, 0),
            _tiles(mask, False), groups))
    np.testing.assert_array_equal(got[False], want)
    np.testing.assert_array_equal(got[True], want)


@pytest.fixture
def conn():
    rng = np.random.default_rng(11)
    n = 6000
    db = Database()
    c = db.connect()
    ks = rng.integers(0, 40, n).astype(np.int32)
    validity = rng.random(n) > 0.15
    wide = rng.integers(0, 1 << 19, n).astype(np.int32)
    wide[0], wide[1] = 0, (1 << 19) + 7        # a group space past the limit
    big = rng.integers(0, 1 << 62, n).astype(np.int64)
    batch = Batch.from_pydict({
        "k": Column.from_numpy(ks),
        "g": Column.from_numpy(rng.choice(["a", "b", "c"], n)),
        "nv": Column(Column.from_numpy(ks).type, ks, validity),
        "f": Column.from_numpy(rng.normal(size=n)),
        "wide": Column.from_numpy(wide),
        "big": Column.from_numpy(big),
    })
    db.schemas["main"].tables["h"] = MemTable("h", batch)
    return c


def _both(conn, q):
    conn.execute("SET serene_device = 'cpu'")
    cpu = conn.execute(q).rows()
    conn.execute("SET serene_device = 'tpu'")
    return cpu, conn.execute(q).rows()


def _forms(conn, q):
    h0, s0 = metrics.DEVICE_AGG_HISTOGRAM.value, \
        metrics.DEVICE_AGG_SCATTER.value
    cpu, dev = _both(conn, q)
    assert len(dev) == len(cpu), q
    for rc, rd in zip(cpu, dev):               # floats come back as f32
        assert rd == tuple(pytest.approx(a, rel=1e-5)
                           if isinstance(a, float) else a for a in rc), q
    return (metrics.DEVICE_AGG_HISTOGRAM.value - h0,
            metrics.DEVICE_AGG_SCATTER.value - s0)


@pytest.mark.parametrize("q,hist,scatter", [
    # the shared group count, COUNT(nv), and AVG's count half
    ("SELECT k, count(*), count(nv), avg(nv) FROM h GROUP BY k ORDER BY k",
     3, 0),
    # float MIN counts its non-NaN rows besides
    ("SELECT g, min(f) FROM h GROUP BY g ORDER BY g", 3, 0),
    # scalar DISTINCT: one presence table, no group count
    ("SELECT count(DISTINCT nv) FROM h WHERE k > 3", 1, 0),
    # group count + a (group x value) presence table
    ("SELECT g, count(DISTINCT nv), sum(DISTINCT k) FROM h GROUP BY g "
     "ORDER BY g", 3, 0),
    # 2^19 + 9 group slots: past the limit, the scatter
    ("SELECT wide, count(*) FROM h GROUP BY wide ORDER BY wide LIMIT 5",
     0, 1),
    # a scalar aggregate reduces nothing by group
    ("SELECT count(*), sum(k) FROM h", 0, 0),
])
def test_counters_say_which_form_ran(conn, hist_on, q, hist, scatter):
    assert _forms(conn, q) == (hist, scatter)


def test_counters_on_a_backend_without_the_kernel(conn):
    assert _forms(conn, "SELECT k, count(*), count(nv) FROM h GROUP BY k "
                        "ORDER BY k") == (0, 2)


def test_declined_distinct_builds_and_uploads_nothing(conn, hist_on):
    # the value space of `big` is past MAX_INT_KEY_RANGE: the device tier
    # declines before any program, upload or factorize
    provider = conn.db.schemas["main"].tables["h"]
    qs = ["SELECT count(DISTINCT big) FROM h",
          "SELECT k, count(DISTINCT big) FROM h GROUP BY k ORDER BY k",
          # 41 group slots x 524,297 values: past MAX_DISTINCT_CELLS
          "SELECT k, count(DISTINCT wide) FROM h GROUP BY k ORDER BY k"]
    conn.execute("SET serene_device = 'cpu'")
    want = [conn.execute(q).rows() for q in qs]
    conn.execute("SET serene_device = 'tpu'")
    before = (obs_device.PROGRAMS.family("device_agg")["compiles"],
              obs_device.PROGRAMS.entries(), metrics.DEVICE_BYTES.value,
              metrics.DEVICE_OFFLOADS.value,
              metrics.DEVICE_AGG_HISTOGRAM.value,
              metrics.DEVICE_AGG_SCATTER.value)
    got = [conn.execute(q).rows() for q in qs]
    assert got == want
    assert before == (obs_device.PROGRAMS.family("device_agg")["compiles"],
                      obs_device.PROGRAMS.entries(),
                      metrics.DEVICE_BYTES.value,
                      metrics.DEVICE_OFFLOADS.value,
                      metrics.DEVICE_AGG_HISTOGRAM.value,
                      metrics.DEVICE_AGG_SCATTER.value)
    assert not getattr(provider, "_factorize_cache", None)

