"""Device telemetry suite (ISSUE 15): the XLA compile ledger, transfer
accounting, per-device HBM attribution, and their surfaces.

Contract under test: `serene_device_telemetry` (default on) observes
only — results are BIT-IDENTICAL with telemetry on or off across the
full matrix (workers 1/4 × shards 1/4 × host/fused/collective
combines); the compile ledger's hit/miss counts match a
dispatch-count-style oracle across repeat queries; the bounded program
LRU (`serene_program_cache_entries`, the PR 7 `_PROGRAM_CACHE` leak
fix) genuinely evicts and re-compiles; recompile storms warn; and the
`sdb_device()` / `sdb_programs()` / `sdb_device_cache()` relations,
`GET /device`, `/metrics` / `/_stats` exports, and the EXPLAIN ANALYZE
`compile=hit|miss` key all round-trip.
"""

import json
import urllib.request

import numpy as np
import pytest

from serenedb_tpu.columnar import dtypes as dt
from serenedb_tpu.columnar.column import Batch, Column
from serenedb_tpu.engine import Database
from serenedb_tpu.exec.tables import MemTable
from serenedb_tpu.obs import device as obs_device
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS


def _mk_conn(nl=6000, nr=3000, seed=9):
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE l (ik INT, sk TEXT, v BIGINT, ts BIGINT)")
    c.execute("CREATE TABLE r (ik INT, w BIGINT)")
    rng = np.random.default_rng(seed)

    def mk(n, payload):
        ik = rng.integers(0, 40, n).astype(np.int32)
        cols = {"ik": Column(dt.INT, ik, rng.random(n) > 0.1)}
        if payload == "v":
            cols["sk"] = Column.from_numpy(
                rng.choice(["alpha", "beta", "gamma"], n))
        cols[payload] = Column.from_numpy(
            rng.integers(-500, 500, n, dtype=np.int64))
        if payload == "v":
            cols["ts"] = Column.from_numpy(np.arange(n, dtype=np.int64))
        return Batch.from_pydict(cols)

    db.schemas["main"].tables["l"] = MemTable("l", mk(nl, "v"))
    db.schemas["main"].tables["r"] = MemTable("r", mk(nr, "w"))
    c.execute("SET serene_device = 'tpu'")
    c.execute("SET serene_device_fused = on")
    c.execute("SET serene_result_cache = off")   # assert EXECUTION internals
    c.execute("SET serene_morsel_rows = 1024")
    c.execute("SET serene_parallel_min_rows = 1024")
    return c


def _rows(c, q):
    return repr(c.execute(q).rows())


class _global:
    """Set a GLOBAL setting for the scope, restore on exit."""

    def __init__(self, name, value):
        self.name, self.value = name, value

    def __enter__(self):
        self.old = SETTINGS.get_global(self.name)
        SETTINGS.set_global(self.name, self.value)

    def __exit__(self, *exc):
        SETTINGS.set_global(self.name, self.old)
        return False


PARITY_QUERIES = [
    "SELECT count(*), sum(v), sum(w), min(v), max(w) "
    "FROM l JOIN r ON l.ik = r.ik WHERE v > 0",
    "SELECT l.sk, count(*), sum(v) FROM l JOIN r ON l.ik = r.ik "
    "GROUP BY l.sk ORDER BY l.sk",
    "SELECT ik, count(*), sum(v) FROM l WHERE v % 3 = 0 "
    "GROUP BY ik ORDER BY ik NULLS LAST",
    "SELECT * FROM l WHERE v > 250 ORDER BY v DESC LIMIT 7",
]


@pytest.mark.parametrize("q", PARITY_QUERIES)
def test_telemetry_parity_matrix(q):
    """Telemetry on/off × workers 1/4 × shards 1/4 × combine
    host/device: every cell bit-identical — telemetry never steers
    (host path, fused single dispatch, sharded host combine, AND the
    collective shard_map combine all run under both switch values)."""
    c = _mk_conn()
    with _global("serene_device_telemetry", True):
        oracle = _rows(c, q)
    for tele in (True, False):
        with _global("serene_device_telemetry", tele):
            for workers in (1, 4):
                c.execute(f"SET serene_workers = {workers}")
                for shards in (1, 4):
                    c.execute(f"SET serene_shards = {shards}")
                    combines = ("host", "device") if shards > 1 \
                        else ("host",)
                    for comb in combines:
                        c.execute(f"SET serene_shard_combine = {comb}")
                        got = _rows(c, q)
                        assert got == oracle, \
                            (f"telemetry={tele} workers={workers} "
                             f"shards={shards} combine={comb} diverged")
    c.execute("SET serene_shards = 1")


def test_compile_ledger_hit_miss_dispatch_oracle():
    """sdb_programs() hit/miss counts must match the dispatch-count
    oracle: a fresh fused shape compiles exactly once (miss), every
    repeat dispatch is a ledger hit, and hits+misses equals the number
    of fused dispatches the offload gauge counted."""
    c = _mk_conn()
    q = ("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
         "ON l.ik = r.ik WHERE v > 100")
    fam0 = obs_device.PROGRAMS.family("fused")
    off0 = metrics.DEVICE_OFFLOADS.value
    c.execute(q)                                   # cold: compile
    fam1 = obs_device.PROGRAMS.family("fused")
    assert fam1["misses"] == fam0["misses"] + 1
    assert fam1["compiles"] == fam0["compiles"] + 1
    repeats = 3
    for _ in range(repeats):
        c.execute(q)                               # warm: ledger hits
    fam2 = obs_device.PROGRAMS.family("fused")
    assert fam2["misses"] == fam1["misses"]
    assert fam2["hits"] == fam1["hits"] + repeats
    dispatches = metrics.DEVICE_OFFLOADS.value - off0
    probes = (fam2["hits"] - fam0["hits"]) + \
        (fam2["misses"] - fam0["misses"])
    assert probes == dispatches == repeats + 1
    # the SQL relation reports the same ledger
    row = [r for r in c.execute(
        "SELECT family, compiles, hits, misses FROM sdb_programs()"
    ).rows() if r[0] == "fused"]
    assert row and row[0][1] == fam2["compiles"] and \
        row[0][2] == fam2["hits"] and row[0][3] == fam2["misses"]
    # compile wall time was recorded (first-dispatch trace)
    snap = [r for r in obs_device.PROGRAMS.snapshot()
            if r["family"] == "fused"][0]
    assert snap["compile_ms_total"] > 0


def test_program_cache_lru_eviction_and_recompile():
    """The bugfix satellite: the program LRU actually frees entries at
    the cap, and a re-request of an evicted key re-compiles through the
    builder (the PR 7 dict leaked one executable per novel shape)."""
    import jax.numpy as jnp
    builds = []

    def builder_for(tag):
        def build():
            builds.append(tag)
            return lambda x: x + 1
        return build

    with _global("serene_program_cache_entries", 2):
        n0 = obs_device.PROGRAMS.entries()
        progs = {}
        for tag in ("a", "b", "c"):
            progs[tag] = obs_device.compiled(
                "lru_unit", ("lru_unit", tag), builder_for(tag))
            assert int(progs[tag](jnp.int32(1))) == 2   # compile + run
        assert builds == ["a", "b", "c"]
        # cap 2: the whole ledger is bounded, so 'a' (oldest) is gone
        assert obs_device.PROGRAMS.entries() <= 2
        assert obs_device.PROGRAMS.entries() <= n0 + 2
        fam = obs_device.PROGRAMS.family("lru_unit")
        assert fam["compiles"] == 3
        # re-request the evicted key: the builder runs again
        again = obs_device.compiled("lru_unit", ("lru_unit", "a"),
                                    builder_for("a"))
        assert builds == ["a", "b", "c", "a"]
        assert int(again(jnp.int32(2))) == 3
        fam = obs_device.PROGRAMS.family("lru_unit")
        assert fam["compiles"] == 4 and fam["evictions"] >= 2


def test_ledger_hit_returns_same_program_no_rebuild():
    """A ledger hit must hand back the SAME compiled wrapper without
    invoking the builder (telemetry may count, never re-trace)."""
    calls = []

    def build():
        calls.append(1)
        return lambda x: x * 2

    p1 = obs_device.compiled("hit_unit", ("k",), build)
    p2 = obs_device.compiled("hit_unit", ("k",), build)
    assert p1 is p2 and calls == [1]


def test_recompile_storm_warns():
    """> RECOMPILE_STORM_PER_MIN fresh compiles of one family within
    the window fire the DeviceRecompileStorms gauge and a device-topic
    warning (rate-limited)."""
    from serenedb_tpu.utils import log as _log
    storms0 = metrics.DEVICE_RECOMPILE_STORMS.value
    for i in range(obs_device.RECOMPILE_STORM_PER_MIN + 2):
        obs_device.compiled("storm_unit", ("storm", i),
                            lambda: (lambda x: x))
    assert metrics.DEVICE_RECOMPILE_STORMS.value == storms0 + 1
    assert obs_device.PROGRAMS.family("storm_unit")["storms"] == 1
    recs = [r for r in _log.MANAGER.records()
            if r.topic == "device" and "recompile storm" in r.message]
    assert recs and "storm_unit" in recs[-1].message


def test_sdb_device_and_device_cache_round_trip():
    """sdb_device: dispatches/bytes land on the executing device;
    sdb_device_cache: per-publication/column occupancy with resolved
    table names, hits counting on repeat queries."""
    c = _mk_conn()
    q = ("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
         "ON l.ik = r.ik WHERE v > 0")
    c.execute(q)
    dev = c.execute(
        "SELECT device, dispatches, bytes_up, hbm_bytes_est "
        "FROM sdb_device WHERE dispatches > 0").rows()
    assert dev, "no device recorded a dispatch"
    assert any(r[2] > 0 for r in dev), "no upload bytes attributed"
    assert any(r[3] > 0 for r in dev), "no HBM occupancy estimated"
    rows = c.execute(
        "SELECT table_name, column_name, kind, bytes, hits "
        "FROM sdb_device_cache").rows()
    tables = {r[0] for r in rows}
    assert {"l", "r"} <= tables
    assert all(r[3] > 0 for r in rows)
    hits_before = {(r[0], r[1], r[2]): r[4] for r in rows}
    c.execute(q)                       # warm repeat: cache entries hit
    rows2 = c.execute(
        "SELECT table_name, column_name, kind, bytes, hits "
        "FROM sdb_device_cache").rows()
    assert any(r[4] > hits_before.get((r[0], r[1], r[2]), 0)
               for r in rows2)
    # device->host fetch accounting moved bytes too
    down = c.execute(
        "SELECT sum(bytes_down) FROM sdb_device").rows()[0][0]
    assert down > 0


def test_http_device_stats_and_metrics_export():
    """GET /device parses; /_stats carries the device section; /metrics
    exports the compile-ledger gauges and the DeviceCompile histogram."""
    from serenedb_tpu.server.http_server import HttpServer
    c = _mk_conn()
    c.execute("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
              "ON l.ik = r.ik WHERE v > 0")
    srv = HttpServer(c.db)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        dev = json.load(urllib.request.urlopen(base + "/device"))
        assert {"devices", "programs", "program_cache",
                "column_cache"} <= set(dev)
        assert any(d["dispatches"] > 0 for d in dev["devices"])
        assert any(p["family"] == "fused" for p in dev["programs"])
        assert dev["program_cache"]["cap"] >= 1
        stats = json.load(urllib.request.urlopen(base + "/_stats"))
        assert "device" in stats and "devices" in stats["device"]
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "serenedb_device_programs_compiled" in text
        assert "serenedb_device_program_cache_hits" in text
        assert "serenedb_device_compile_seconds_bucket" in text
        assert "serenedb_device_recompile_storms" in text
    finally:
        srv.stop()


def test_explain_compile_key_text_and_json():
    """First execution of a fresh fused shape pays the compile (EXPLAIN
    ANALYZE says compile=miss); the repeat says compile=hit. FORMAT
    JSON carries the same as "Device Compile"."""
    c = _mk_conn(seed=123)              # fresh providers => fresh keys
    q = ("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
         "ON l.ik = r.ik WHERE v > 17")
    out = "\n".join(r[0] for r in
                    c.execute(f"EXPLAIN ANALYZE {q}").rows())
    assert "compile=miss" in out
    out2 = "\n".join(r[0] for r in
                     c.execute(f"EXPLAIN ANALYZE {q}").rows())
    assert "compile=hit" in out2 and "compile=miss" not in out2
    j = json.loads(c.execute(
        f"EXPLAIN (ANALYZE, FORMAT JSON) {q}").rows()[0][0])

    def compile_keys(node, acc):
        if "Device Compile" in node:
            acc.append(node["Device Compile"])
        for sub in node.get("Plans", []):
            compile_keys(sub, acc)
        return acc

    keys = compile_keys(j[0]["Plan"] if isinstance(j, list) else j, [])
    assert keys and all(k == "hit" for k in keys)


def test_device_compile_trace_spans_at_all_sites():
    """The satellite: device_compile spans appear in the flight
    recorder for every program family's first dispatch — fused join,
    device aggregate, device top-N (the sites that stamped nothing
    before this PR)."""
    from serenedb_tpu.obs.trace import FLIGHT
    c = _mk_conn(seed=77)
    cases = [
        ("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
         "ON l.ik = r.ik WHERE v > 31", "fused"),
        ("SELECT ik, count(*), sum(v) FROM l WHERE v > 13 "
         "GROUP BY ik ORDER BY ik NULLS LAST", "device_agg"),
        ("SELECT * FROM l ORDER BY v DESC LIMIT 5", "device_topn"),
    ]
    for q, family in cases:
        c.execute(q)
        entry = FLIGHT.last()
        spans = [s for s in entry["spans"]
                 if s["name"] == "device_compile" and s["args"] and
                 s["args"].get("family") == family]
        assert spans, f"no device_compile span for {family}"
        assert all(s["end_ns"] > s["begin_ns"] for s in spans)


def test_telemetry_off_keeps_ledgers_dark():
    """With the switch off the program cache still works (bounded,
    identical keys) but no stats/transfer accounting accumulates."""
    with _global("serene_device_telemetry", False):
        c = _mk_conn(seed=31)
        q = ("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
             "ON l.ik = r.ik WHERE v > 5")
        fam0 = obs_device.PROGRAMS.family("fused")
        led0 = obs_device.LEDGER.snapshot()
        up0 = sum(d["bytes_up"] for d in led0.values())
        r1 = _rows(c, q)
        r2 = _rows(c, q)
        assert r1 == r2
        fam1 = obs_device.PROGRAMS.family("fused")
        led1 = obs_device.LEDGER.snapshot()
        assert fam1["hits"] == fam0["hits"] and \
            fam1["misses"] == fam0["misses"]
        assert sum(d["bytes_up"] for d in led1.values()) == up0


def test_device_section_lists_the_owners_that_remain():
    """`GET /device` and `/_stats` carry no posting-pool section, and
    `sdb_device()`'s hbm_bytes_est is the sum of the HBM owners that
    remain: the device column cache and the vector pool's region."""
    from serenedb_tpu.exec.device_pipeline import DEVICE_CACHE
    from serenedb_tpu.search.vector_store import VPOOL
    from serenedb_tpu.server.http_server import HttpServer
    c = _mk_conn()
    c.execute("SELECT count(*), sum(v), sum(w) FROM l JOIN r "
              "ON l.ik = r.ik WHERE v > 0")
    srv = HttpServer(c.db)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        dev = json.load(urllib.request.urlopen(base + "/device"))
        stats = json.load(urllib.request.urlopen(base + "/_stats"))
    finally:
        srv.stop()
    assert "posting_pool" not in dev
    assert "posting_pool" not in stats["device"]
    assert {"column_cache", "vector_pool"} <= set(dev)
    want = sum(DEVICE_CACHE.device_bytes().values()) + \
        sum(VPOOL.device_bytes().values())
    got = c.execute("SELECT sum(hbm_bytes_est) FROM sdb_device").rows()
    assert got[0][0] == want > 0


@pytest.mark.parametrize("stmt,sqlstate", [
    ("SET serene_posting_pool = off", "42704"),
    ("SET serene_posting_pages = 16", "42704"),
    ("SELECT * FROM sdb_posting_pool", "42P01"),
    ("SELECT * FROM sdb_posting_pool()", "42883")])
def test_the_posting_pool_names_are_unknown(stmt, sqlstate):
    """The pool went with its switch, its budget and its relation: each
    name is refused like any other unknown setting or relation."""
    from serenedb_tpu import errors
    c = Database().connect()
    with pytest.raises(errors.SqlError) as e:
        c.execute(stmt)
    assert e.value.sqlstate == sqlstate
    assert not any(n.startswith("PostingPool")
                   for n in metrics.REGISTRY.snapshot())


def test_settings_declared_and_not_result_affecting():
    from serenedb_tpu.cache.result import RESULT_AFFECTING_SETTINGS
    assert SETTINGS.get_global("serene_device_telemetry") in (True, False)
    assert SETTINGS.get_global("serene_program_cache_entries") >= 1
    assert "serene_device_telemetry" not in RESULT_AFFECTING_SETTINGS
    assert "serene_program_cache_entries" not in RESULT_AFFECTING_SETTINGS
    # both are GLOBAL scope: SET per session must be rejected
    c = Database().connect()
    from serenedb_tpu import errors
    for name in ("serene_device_telemetry",
                 "serene_program_cache_entries"):
        with pytest.raises(errors.SqlError):
            c.execute(f"SET {name} = 1")


# -- one meaning of device time, programs named after their family (ISSUE 24)


def _dispatch_hist():
    counts, total = metrics.DEVICE_DISPATCH_HIST.snapshot()
    return sum(counts), total


@pytest.mark.parametrize("q,family", [
    (PARITY_QUERIES[0], "fused"),
    ("SELECT ik, count(*), sum(v) FROM l WHERE v > -400 GROUP BY ik "
     "ORDER BY ik", "device_agg"),
    ("SELECT ts, v FROM l ORDER BY ts DESC LIMIT 5", "device_topn"),
    ("SELECT ts, v FROM l WHERE v > 0 ORDER BY ts DESC LIMIT 5",
     "fused_topn")])
def test_device_time_means_enqueue_to_readback_everywhere(q, family):
    """`DeviceDispatch`, `QueryProfile.device_ns` (EXPLAIN ANALYZE's
    `Device: time=`) and the timeline agree at every site: one
    observation per program call + readback, from the start of the
    enqueue to the end of the blocking readback — never the host work
    around it. The statement's own readings (its profile, its trace) are
    compared with each other; the histogram is the process's, so it is
    only held to have grown by at least as much."""
    from serenedb_tpu.obs.trace import FLIGHT
    c = _mk_conn()
    c.execute(q)                                   # compile, upload
    slack = []
    for _ in range(5):
        fam0 = obs_device.PROGRAMS.family(family)
        n0, sum0 = _dispatch_hist()
        c.execute(q)
        n1, sum1 = _dispatch_hist()
        own = c._active_profile.totals().device_ns
        entry = FLIGHT.get(c._active_trace.trace_id)
        assert obs_device.PROGRAMS.family(family)["hits"] > fam0["hits"]
        stages = [s for s in entry["spans"] if s["cat"] == "stage"]
        enq = [s for s in stages if s["name"] == "device_enqueue"]
        wait = [s for s in stages if s["name"] == "device_wait"]
        assert len(enq) == len(wait) >= 1
        assert n1 - n0 >= len(enq) and sum1 - sum0 >= own
        # the host decode of the outputs is a stage of its own at every
        # site
        assert any(s["name"] == "device_finalize" for s in stages)
        assert not any(s["name"] == "device_dispatch"
                       for s in entry["spans"])
        # the observed window = the union [enqueue begin, wait end] of
        # each pair, plus the clock reads between the stamps
        spanned = sum(w["end_ns"] - e["begin_ns"]
                      for e, w in zip(enq, wait))
        assert spanned <= own
        # and it lies inside `device_prepare`, which holds everything
        # else
        prep = max((s for s in stages if s["name"] == "device_prepare"),
                   key=lambda s: s["end_ns"] - s["begin_ns"])
        assert prep["begin_ns"] <= enq[0]["begin_ns"] and \
            wait[-1]["end_ns"] <= prep["end_ns"]
        assert own < prep["end_ns"] - prep["begin_ns"]
        slack.append((own - spanned) / len(enq))
        if slack[-1] <= 2_000_000:
            break
    # between the stamps lie two clock reads a dispatch: a worker that
    # is descheduled there reads more, so the quietest of the
    # executions is judged — host work inside the window would be in
    # every one of them
    assert min(slack) <= 2_000_000, slack
    # EXPLAIN ANALYZE reads the same quantity: what its own dispatches
    # fed the histogram, around the stages of its own trace
    n2, sum2 = _dispatch_hist()
    text = "\n".join(r[0] for r in c.execute("EXPLAIN ANALYZE " + q).rows())
    n3, sum3 = _dispatch_hist()
    times = [float(ln.split("time=")[1].split(" ms")[0])
             for ln in text.splitlines() if "Device: time=" in ln]
    assert times, text
    stages = [s for s in FLIGHT.get(c._active_trace.trace_id)["spans"]
              if s["cat"] == "stage"]
    spanned = sum(w["end_ns"] - e["begin_ns"] for e, w in zip(
        [s for s in stages if s["name"] == "device_enqueue"],
        [s for s in stages if s["name"] == "device_wait"]))
    assert spanned / 1e6 - 0.01 <= sum(times) <= (sum3 - sum2) / 1e6 + 0.01


def test_programs_are_named_after_their_family():
    """`compiled()` traces each body under `jax.named_scope(family)` and
    names the jitted callable after it, so a profiler trace's module
    line says `jit_device_agg`, not `jit_program`."""
    import jax.numpy as jnp

    def body(x):
        return (x * 2).sum()

    prog = obs_device.compiled("device_agg", ("named-test",), lambda: body)
    assert prog.fn.__name__ == "device_agg"
    x = jnp.arange(8, dtype=jnp.int32)
    lowered = prog.fn.lower(x)
    assert "jit_device_agg" in lowered.as_text()
    assert "device_agg" in lowered.as_text(debug_info=True)
    assert int(prog(x)) == 56


# -- a call's host operands cross BEFORE the call (ISSUE 35) -------------------


def _affine_program(donate=()):
    """A program of its own key (nothing else in the process shares its
    first call): out = a * k + b * n, plus an untouched pass-through."""
    import uuid
    return obs_device.compiled(
        "upload_unit", (uuid.uuid4().hex,),
        lambda: (lambda a, k, b, n, keep: (a * k + b * n, keep)),
        donate_argnums=donate)


def _upload_args():
    import jax.numpy as jnp
    a = np.arange(1024, dtype=np.float32) / 7.0
    b = np.arange(1024, dtype=np.int32)
    return a, 1.25, b, np.int32(3), jnp.ones(4, dtype=jnp.float32)


def _stage_names(entry):
    return [s["name"] for s in entry["spans"] if s["cat"] == "stage"]


@pytest.mark.parametrize("telemetry", [True, False])
def test_a_call_uploads_its_host_operands_then_enqueues(telemetry):
    """numpy arrays, a numpy scalar and a Python float among the
    operands: `device_upload` then `device_enqueue`, the jitted function
    sees `jax.Array`s only, the transfer gauge and the ledger's bytes
    move by the operands' bytes (dark with telemetry off), and the
    outputs are the bits of the plain call."""
    import jax

    from serenedb_tpu.obs.trace import QueryTrace
    prog = _affine_program()
    args = _upload_args()
    want = jax.jit(lambda a, k, b, n, keep: (a * k + b * n, keep))(*args)
    prog(*args)                                    # the compiling call
    seen = []
    inner = prog.fn
    prog.fn = lambda *xs: (seen.append(xs), inner(*xs))[1]
    obs_device._SCALARS.clear()                    # 1.25 crosses again
    prior = SETTINGS.get_global("serene_device_telemetry")
    SETTINGS.set_global("serene_device_telemetry", telemetry)
    try:
        up0 = metrics.DEVICE_TRANSFERS_UP.value
        led0 = sum(d["bytes_up"] for d in obs_device.LEDGER.snapshot()
                   .values())
        tr = QueryTrace("unit")
        with tr.pinned():
            out = prog(*args)
        entry = tr.finish()
        ups = metrics.DEVICE_TRANSFERS_UP.value - up0
        led = sum(d["bytes_up"] for d in obs_device.LEDGER.snapshot()
                  .values()) - led0
    finally:
        SETTINGS.set_global("serene_device_telemetry", prior)
        prog.fn = inner
    assert _stage_names(entry) == ["device_upload", "device_enqueue"]
    spans = {s["name"]: s for s in entry["spans"]}
    assert spans["device_upload"]["end_ns"] <= \
        spans["device_enqueue"]["begin_ns"]
    assert spans["device_upload"]["args"] == {"family": "upload_unit"} \
        == spans["device_enqueue"]["args"]
    (xs,) = seen
    assert all(isinstance(x, jax.Array) for x in xs)
    assert xs[1].weak_type and not xs[3].weak_type    # as the values were
    assert xs[4] is args[4]                           # left alone
    # 4 KiB + 4 KiB of arrays, 4 B + 4 B of scalars, four transfers
    assert (ups, led) == ((4, 2 * 4096 + 8) if telemetry else (0, 0))
    for got, ref in zip(out, want):
        assert got.dtype == ref.dtype
        assert np.asarray(got).tobytes() == np.asarray(ref).tobytes()


def test_resident_operands_open_no_upload_and_a_scalar_crosses_once():
    import jax

    from serenedb_tpu.obs.trace import QueryTrace
    prog = _affine_program()
    a, k, b, n, keep = _upload_args()
    prog(a, k, b, n, keep)                         # compile; 1.25 is kept
    res = jax.device_put((a, b, n))
    assert (float, 1.25) in obs_device._SCALARS
    up0 = metrics.DEVICE_TRANSFERS_UP.value
    tr = QueryTrace("unit")
    with tr.pinned():
        prog(res[0], k, res[1], res[2], keep)      # only the kept scalar
        prog(res[0], res[0][0], res[1], res[2], keep)     # none at all
    entry = tr.finish()
    assert _stage_names(entry) == ["device_enqueue", "device_enqueue"]
    assert metrics.DEVICE_TRANSFERS_UP.value == up0
    # a value not seen before crosses (once), under the stage
    tr = QueryTrace("unit")
    with tr.pinned():
        prog(res[0], 7.5, res[1], res[2], keep)
        prog(res[0], 7.5, res[1], res[2], keep)
    assert _stage_names(tr.finish()) == ["device_upload", "device_enqueue",
                                         "device_enqueue"]
    assert metrics.DEVICE_TRANSFERS_UP.value - up0 == 1


def test_a_kept_scalar_is_never_handed_to_a_donated_position():
    """Donation invalidates the buffer it is given: a Python scalar at a
    donated position is put afresh every call, and the kept one of the
    same value stays valid for the next program."""
    prog = obs_device.compiled(
        "upload_unit", ("donated",),
        lambda: (lambda plane, k: (plane + k,)), donate_argnums=(0,))
    keep = _affine_program()
    a, _k, b, n, tail = _upload_args()
    keep(a, 2.0, b, n, tail)                       # 2.0 is kept now
    for _ in range(3):
        (out,) = prog(2.0, 2.0)
        assert float(out) == 4.0
    (out, _t) = keep(a, 2.0, b, n, tail)
    assert np.asarray(out)[1] == np.float32(a[1] * 2.0 + b[1] * 3)


@pytest.mark.parametrize("trace,telemetry", [(True, True), (False, False)])
def test_enqueue_call_histogram_counts_calls_not_compiles(trace, telemetry):
    """`DeviceEnqueueCall`: one observation per program call whatever
    `serene_trace` and `serene_device_telemetry` say; the compiling call
    is `DeviceCompile`'s."""
    from serenedb_tpu.obs.trace import QueryTrace
    prior = SETTINGS.get_global("serene_device_telemetry")
    SETTINGS.set_global("serene_device_telemetry", telemetry)
    try:
        n0 = metrics.DEVICE_ENQUEUE_CALL_HIST.count
        c0 = metrics.DEVICE_COMPILE_HIST.count
        prog = _affine_program()
        args = _upload_args()
        tr = QueryTrace("unit") if trace else None
        if tr is not None:
            tr._cv_token = tr.pinned()
            tr._cv_token.__enter__()
        try:
            prog(*args)
            assert metrics.DEVICE_ENQUEUE_CALL_HIST.count == n0
            for _ in range(5):
                prog(*args)
        finally:
            if tr is not None:
                tr._cv_token.__exit__(None, None, None)
        assert metrics.DEVICE_ENQUEUE_CALL_HIST.count - n0 == 5
        assert metrics.DEVICE_COMPILE_HIST.count - c0 == int(telemetry)
        _counts, total = metrics.DEVICE_ENQUEUE_CALL_HIST.snapshot()
        assert total > 0
    finally:
        SETTINGS.set_global("serene_device_telemetry", prior)
