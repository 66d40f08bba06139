"""Timeline tracing (ISSUE 10): trace parity, span well-formedness,
coalesced-batch span fan-out, latency histograms, flight recorder,
sdb_trace / GET /trace/<id>, EXPLAIN (FORMAT JSON), pool gauges."""

import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from serenedb_tpu.columnar.column import Batch, Column
from serenedb_tpu.engine import Database
from serenedb_tpu.obs.trace import FLIGHT, chrome_trace, top_spans
from serenedb_tpu.utils import metrics as sdb_metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS


def _db_with_tables(n=16384):
    """Fact + build tables sized for the morsel-parallel path at
    serene_morsel_rows=1024 and for the fused device pipeline at
    serene_device_min_rows=1024 (cpu-backend jit)."""
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE facts (ts BIGINT, k BIGINT, v BIGINT)")
    rng = np.random.default_rng(11)
    db.schemas["main"].tables["facts"].replace(Batch.from_pydict({
        "ts": Column.from_numpy(np.arange(n, dtype=np.int64)),
        "k": Column.from_numpy(rng.integers(0, 100, n, dtype=np.int64)),
        "v": Column.from_numpy(
            rng.integers(0, 1000, n, dtype=np.int64))}))
    c.execute("CREATE TABLE build (k BIGINT, w BIGINT)")
    db.schemas["main"].tables["build"].replace(Batch.from_pydict({
        "k": Column.from_numpy(np.arange(100, dtype=np.int64)),
        "w": Column.from_numpy(np.arange(100, dtype=np.int64) * 10)}))
    c.execute("SET serene_device = 'cpu'")
    c.execute("SET serene_morsel_rows = 1024")
    c.execute("SET serene_parallel_min_rows = 1024")
    return db, c


AGG_Q = ("SELECT k, count(*), sum(v) FROM facts "
         "WHERE ts < 8192 GROUP BY k ORDER BY k")
JOIN_Q = ("SELECT count(*), sum(v + w) FROM facts "
          "JOIN build ON facts.k = build.k WHERE facts.ts < 8192")
FUSED_Q = ("SELECT count(*), sum(v) FROM facts "
           "JOIN build ON facts.k = build.k WHERE facts.v > 3")


def _last_entry(c):
    """The flight-recorder entry of the connection's LAST traced
    statement (capture the id before running anything else — the
    sdb_trace query itself is traced too)."""
    return FLIGHT.get(c._active_trace.trace_id)


def _spans_of(c, sql):
    c.execute(sql)
    return _last_entry(c)


# -- bit-identity: tracing observes, never steers ----------------------------


@pytest.mark.parametrize("query", [AGG_Q, JOIN_Q])
def test_trace_on_off_workers_shards_parity(query):
    db, c = _db_with_tables()
    results = {}
    for tr in ("on", "off"):
        for workers in (1, 4):
            for shards in (1, 4):
                c.execute(f"SET serene_trace = {tr}")
                c.execute(f"SET serene_workers = {workers}")
                c.execute(f"SET serene_shards = {shards}")
                results[(tr, workers, shards)] = c.execute(query).rows()
    base = results[("on", 1, 1)]
    assert base  # non-trivial result
    for key, rows in results.items():
        assert rows == base, f"{key} diverged from (on, 1, 1)"


# -- span tree well-formedness ----------------------------------------------

#: wait-category spans describe time spent OUTSIDE the recording thread
#: (queued behind another task / another group's dispatch) — they may
#: legitimately straddle an executing span on the same worker thread, so
#: the strict-nesting property applies to the execution spans only
_WAIT_SPANS = {"queue_wait", "batch_wait"}


def _assert_well_formed(entry):
    dur = entry["duration_ns"]
    root = [s for s in entry["spans"] if s["cat"] == "query"]
    assert len(root) == 1 and root[0]["begin_ns"] == 0 \
        and root[0]["end_ns"] == dur
    by_tid = {}
    for s in entry["spans"]:
        assert 0 <= s["begin_ns"] <= s["end_ns"], s
        # finalization happens after every span closed, so no span may
        # outlive the trace
        assert s["end_ns"] <= dur, s
        if s["cat"] != "query" and s["name"] not in _WAIT_SPANS:
            by_tid.setdefault(s["tid"], []).append(s)
    for tid, spans in by_tid.items():
        spans.sort(key=lambda s: (s["begin_ns"], -s["end_ns"]))
        stack = []
        for s in spans:
            while stack and stack[-1]["end_ns"] <= s["begin_ns"]:
                stack.pop()
            if stack:
                assert s["end_ns"] <= stack[-1]["end_ns"], \
                    f"partial overlap on tid {tid}: {stack[-1]} vs {s}"
            stack.append(s)


def test_span_tree_well_formed_parallel():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    entry = _spans_of(c, AGG_Q)
    _assert_well_formed(entry)
    names = [s["name"] for s in entry["spans"]]
    assert "plan" in names and "morsel_pipeline" in names
    # every pool task has a queue-wait span (recorded as a pair by the
    # worker that picked the task up)
    assert names.count("task") >= 1
    assert names.count("queue_wait") == names.count("task")


def test_span_tree_well_formed_sharded_device():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    c.execute("SET serene_shards = 2")
    c.execute("SET serene_device = 'auto'")
    c.execute("SET serene_device_min_rows = 1024")
    entry = _spans_of(c, FUSED_Q)
    _assert_well_formed(entry)
    cats = {s["cat"] for s in entry["spans"]}
    assert "device" in cats, f"no device spans in {cats}"
    names = [s["name"] for s in entry["spans"]]
    # the sharded fused join dispatches per shard (host combine: one
    # device_enqueue + device_wait pair per shard_pipeline lane) or as
    # ONE shard_map program (serene_shard_combine=device: a
    # collective_dispatch span around the pair)
    assert "device_enqueue" in names and "device_wait" in names
    assert "shard_pipeline" in names or "collective_dispatch" in names
    assert "device_prepare" in names


def _union_coverage(entry) -> float:
    """Fraction of the query's wall time covered by the UNION of its
    non-root spans — the root `query` span equals the duration by
    construction, so it must not count toward coverage."""
    iv = sorted((s["begin_ns"], s["end_ns"]) for s in entry["spans"]
                if s["cat"] != "query")
    total, cur_b, cur_e = 0, None, None
    for b, e in iv:
        if cur_b is None:
            cur_b, cur_e = b, e
        elif b <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_b
            cur_b, cur_e = b, e
    if cur_b is not None:
        total += cur_e - cur_b
    return total / entry["duration_ns"]


def test_trace_coverage_at_workers_shards():
    """Acceptance shape: workers=4, shards=2 — the union of the
    attributed (non-root) spans covers >=95% of measured wall time,
    with queue-wait and the device stages present. The agg leg
    runs device=cpu so the morsel pipeline (pool queue waits)
    executes; the join leg runs device=auto so the fused pipeline
    dispatches."""
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    c.execute("SET serene_shards = 2")
    c.execute(AGG_Q)
    entry_agg = _last_entry(c)
    c.execute("SET serene_device = 'auto'")
    c.execute("SET serene_device_min_rows = 1024")
    entry_dev = _spans_of(c, FUSED_Q)
    for entry in (entry_agg, entry_dev):
        cov = _union_coverage(entry)
        assert cov >= 0.95, \
            f"span coverage {cov:.3f} < 0.95 for {entry['query']}"
    assert any(s["name"] == "queue_wait" for s in entry_agg["spans"])
    assert any(s["name"] == "device_wait" for s in entry_dev["spans"])


# -- coalesced-batch span fan-out -------------------------------------------


@pytest.mark.parametrize("carried", ["batch_dispatch", "device_upload"])
def test_coalesced_batch_span_fanout(carried):
    """A coalesced search dispatch stamps its spans under EVERY member
    query's trace: concurrent identical top-k searches must yield at
    least one trace whose batch_dispatch span carries queries >= 2,
    and every member of that dispatch must carry the span too — and
    (`device_upload`) the dispatch's stages, the commit of its host
    operands among them, though one thread ran them."""
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE docs (id INT, body TEXT)")
    vals = ", ".join(f"({i}, 'quick brown fox number{i % 7} jumps')"
                     for i in range(512))
    c.execute("INSERT INTO docs VALUES " + vals)
    c.execute("CREATE INDEX ON docs USING inverted (body)")
    # the fragment cache would serve repeats without dispatching — force
    # misses so every thread really submits to the batcher
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    try:
        tids = []
        tid_lock = threading.Lock()

        def search():
            cc = db.connect()
            cc.execute("SELECT id, bm25(body) s FROM docs "
                       "WHERE body @@ 'fox jumps' "
                       "ORDER BY s DESC, id LIMIT 5")
            with tid_lock:
                tids.append(cc._active_trace.trace_id)

        for _ in range(6):   # repeat rounds until coalescing happens
            ts = [threading.Thread(target=search) for _ in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            fanout = {}
            for tid in tids:
                e = FLIGHT.get(tid)
                if e is None:
                    continue
                for s in e["spans"]:
                    if s["name"] == "batch_dispatch" and \
                            (s["args"] or {}).get("queries", 1) >= 2:
                        fanout.setdefault(
                            s["args"]["dispatch"], []).append(tid)
            coalesced = [m for m in fanout.values() if len(m) >= 2]
            if coalesced:
                break
        assert coalesced, "no coalesced dispatch fanned spans out to " \
                          "multiple member traces"
        # every member of the shared dispatch carries the span with the
        # same batch size
        members = coalesced[0]
        sizes = set()
        for tid in members:
            e = FLIGHT.get(tid)
            sizes.update(s["args"]["queries"] for s in e["spans"]
                         if s["name"] == "batch_dispatch")
        assert len(sizes) >= 1 and max(sizes) >= len(members)
        if carried == "device_upload":
            for tid in members:
                e = FLIGHT.get(tid)
                assert e["stages"].get("device_upload", 0) > 0, e["stages"]
                assert e["stages"].get("device_enqueue", 0) > 0
                assert sum(e["stages"].values()) == e["duration_ns"]
                ups = [s for s in e["spans"] if s["cat"] == "stage"
                       and s["name"] == "device_upload"]
                # inside the dispatch it rode
                (disp,) = [s for s in e["spans"]
                           if s["name"] == "batch_dispatch"]
                assert ups and all(
                    disp["begin_ns"] <= s["begin_ns"] and
                    s["end_ns"] <= disp["end_ns"] for s in ups)
    finally:
        SETTINGS.set_global("serene_result_cache", prior)


# -- histogram bucket math + Prometheus text --------------------------------


def test_histogram_bucket_math():
    h = sdb_metrics.Histogram("TestHist", "unit test")
    assert h.quantile_ns(0.5) == 0.0                      # empty
    # bucket boundaries: an observation exactly on a bound lands in
    # that bound's bucket (le semantics)
    assert sdb_metrics.hist_bucket_index(0) == 0
    assert sdb_metrics.hist_bucket_index(1000) == 0
    assert sdb_metrics.hist_bucket_index(1001) == 1
    assert sdb_metrics.hist_bucket_index(10 ** 18) == \
        len(sdb_metrics.HIST_BOUNDS_NS)                   # +Inf bucket
    for ns in (5_000, 5_000, 5_000, 1_000_000_000):
        h.observe_ns(ns)
    counts, sum_ns = h.snapshot()
    assert sum(counts) == 4 and sum_ns == 15_000 + 10 ** 9
    # p50 sits inside the 5µs observations' bucket, p99 near the 1s one
    assert h.quantile_ns(0.50) <= 8192 * 1000
    assert h.quantile_ns(0.99) > 5e8
    assert h.quantile_ns(0.50) < h.quantile_ns(0.99)
    p = h.percentiles_ms()
    assert p["count"] == 4 and p["p50_ms"] <= p["p99_ms"]
    # monotone in q
    qs = [h.quantile_ns(q) for q in (0.1, 0.5, 0.9, 0.99)]
    assert qs == sorted(qs)


def test_histogram_prometheus_text_parses():
    db, c = _db_with_tables()
    c.execute(AGG_Q)
    from serenedb_tpu.obs.export import prometheus_text
    txt = prometheus_text()
    assert "# TYPE serenedb_query_latency_seconds histogram" in txt
    buckets = re.findall(
        r'serenedb_query_latency_seconds_bucket\{le="([^"]+)"\} (\d+)',
        txt)
    assert len(buckets) == len(sdb_metrics.HIST_BOUNDS_NS) + 1
    # cumulative and monotone; +Inf bucket equals _count
    counts = [int(v) for _, v in buckets]
    assert counts == sorted(counts)
    assert buckets[-1][0] == "+Inf"
    m = re.search(r"serenedb_query_latency_seconds_count (\d+)", txt)
    assert m and int(m.group(1)) == counts[-1] and counts[-1] >= 1
    assert re.search(r"serenedb_query_latency_seconds_sum \d", txt)
    # finite le values parse as seconds and ascend
    les = [float(v) for v, _ in buckets[:-1]]
    assert les == sorted(les) and les[0] == 1e-06
    # the other tentpole histograms export too
    for series in ("serenedb_pool_queue_wait_seconds",
                   "serenedb_search_batch_window_seconds",
                   "serenedb_device_dispatch_seconds"):
        assert f"# TYPE {series} histogram" in txt


def test_stats_json_latency_percentiles():
    db, c = _db_with_tables()
    c.execute(AGG_Q)
    from serenedb_tpu.obs.export import stats_json
    sj = stats_json()
    lat = sj["latency"]["QueryLatency"]
    assert lat["count"] >= 1
    assert 0 <= lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
    assert "PoolQueueWait" in sj["latency"]
    assert any(t["trace_id"] for t in sj["traces"])


def test_stat_statements_percentiles():
    db, c = _db_with_tables()
    q = "SELECT count(*) FROM facts WHERE v < 500"
    for _ in range(5):
        c.execute(q)
    rows = c.execute(
        "SELECT calls, p50_time_ms, p95_time_ms, p99_time_ms "
        "FROM sdb_stat_statements() WHERE query LIKE "
        "'select count ( * ) from facts%'").rows()
    assert rows, "statement not tracked"
    calls, p50, p95, p99 = rows[-1]
    assert calls >= 5
    assert 0 < p50 <= p95 <= p99


# -- flight recorder --------------------------------------------------------


def test_flight_recorder_eviction_order():
    prior = SETTINGS.get_global("serene_flight_recorder_queries")
    SETTINGS.set_global("serene_flight_recorder_queries", 4)
    try:
        db, c = _db_with_tables(2048)
        ids = []
        for i in range(7):
            c.execute(f"SELECT count(*) FROM facts WHERE v <> {i}")
            ids.append(c._active_trace.trace_id)
        assert all(FLIGHT.get(t) is None for t in ids[:3]), \
            "oldest entries must evict"
        assert all(FLIGHT.get(t) is not None for t in ids[-4:]), \
            "newest entries must survive"
        listed = [e["trace_id"] for e in FLIGHT.snapshot()]
        assert listed == sorted(listed), "ring must list oldest->newest"
        assert len(listed) <= 4
    finally:
        SETTINGS.set_global("serene_flight_recorder_queries", prior)


def test_error_path_dumps_timeline():
    db, c = _db_with_tables(2048)
    with pytest.raises(Exception):
        c.execute("SELECT 1/0 FROM facts")
    entry = _last_entry(c)
    assert entry is not None and entry["error"]
    assert "division" in entry["error"]


def test_sdb_trace_table_function():
    db, c = _db_with_tables(2048)
    c.execute(AGG_Q)
    tid = c._active_trace.trace_id
    listing = c.execute("SELECT trace_id, query, duration_ms, spans "
                        "FROM sdb_trace()").rows()
    assert any(r[0] == tid and AGG_Q in r[1] for r in listing)
    spans = c.execute(
        f"SELECT span, category, begin_ms, end_ms, duration_ms "
        f"FROM sdb_trace({tid})").rows()
    assert spans[0][0] == "query"
    for name, cat, b, e, d in spans:
        assert 0 <= b <= e and abs((e - b) - d) < 0.01
    begins = [r[2] for r in spans]
    assert begins == sorted(begins), "spans must be begin-ordered"
    # unknown ids yield an empty relation (entry may have aged out)
    assert c.execute("SELECT * FROM sdb_trace(999999999)").rows() == []
    # sdb_trace also resolves as a bare system table (the listing)
    assert c.execute("SELECT count(*) FROM sdb_trace").rows()[0][0] >= 1


def test_trace_disabled_records_nothing():
    db, c = _db_with_tables(2048)
    c.execute("SET serene_trace = off")
    c.execute(AGG_Q)
    assert c._active_trace is None


def test_utility_statements_not_flight_recorded():
    """SET/SHOW/txn statements are bookkeeping, not work: they must not
    churn the bounded flight recorder (a per-query SET would halve the
    ring's post-incident reach)."""
    db, c = _db_with_tables(2048)
    c.execute(AGG_Q)
    tid = c._active_trace.trace_id
    c.execute("SET application_name = 'noise'")
    c.execute("SHOW application_name")
    c.execute("BEGIN")
    c.execute("COMMIT")
    assert c._active_trace is None
    listing = [e["trace_id"] for e in FLIGHT.snapshot()]
    assert tid in listing
    queries = [e["query"] for e in FLIGHT.snapshot()]
    assert not any(q.startswith(("SET ", "SHOW ", "BEGIN", "COMMIT"))
                   for q in queries)


# -- /trace endpoint --------------------------------------------------------


def test_trace_endpoint_chrome_json():
    from serenedb_tpu.server.http_server import HttpServer
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    c.execute(AGG_Q)
    tid = c._active_trace.trace_id
    srv = HttpServer(db)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        doc = json.loads(urllib.request.urlopen(
            f"{base}/trace/{tid}").read())
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        x = [e for e in events if e["ph"] == "X"]
        m = [e for e in events if e["ph"] == "M"]
        assert x and m
        for e in x:
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert e["pid"] == 1 and "tid" in e and e["name"]
        root = [e for e in x if e["name"] == "query"]
        assert len(root) == 1 and \
            root[0]["args"]["trace_id"] == tid
        assert doc["otherData"]["trace_id"] == tid
        # /trace/last serves the newest entry; the listing includes tid
        last = json.loads(urllib.request.urlopen(
            f"{base}/trace/last").read())
        assert last["otherData"]["trace_id"] >= tid
        listing = json.loads(urllib.request.urlopen(
            f"{base}/trace").read())
        assert any(e["trace_id"] == tid for e in listing)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/trace/999999999")
        assert ei.value.code == 404
    finally:
        srv.stop()


# -- EXPLAIN (ANALYZE, FORMAT JSON) -----------------------------------------


def test_explain_format_json_plain():
    db, c = _db_with_tables(2048)
    out = c.execute(f"EXPLAIN (FORMAT JSON) {AGG_Q}").rows()
    doc = json.loads(out[0][0])
    assert isinstance(doc, list) and "Plan" in doc[0]
    plan = doc[0]["Plan"]
    assert plan["Node Type"]
    assert "Actual Rows" not in plan          # structure only
    kids = plan.get("Plans", [])
    assert kids, "tree must nest"


def test_explain_analyze_format_json():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    expected = len(c.execute(AGG_Q).rows())
    out = c.execute(f"EXPLAIN (ANALYZE, FORMAT JSON) {AGG_Q}").rows()
    doc = json.loads(out[0][0])
    top = doc[0]
    assert top["Rows Returned"] == expected
    assert top["Execution Time"] > 0

    def walk(node):
        yield node
        for k in node.get("Plans", []):
            yield from walk(k)

    nodes = list(walk(top["Plan"]))
    agg = [n for n in nodes if "Actual Rows" in n]
    assert agg, "annotated nodes missing"
    scan = [n for n in nodes if "Morsels Scheduled" in n]
    assert scan, "prune counters missing from JSON tree"
    assert all("Actual Total Time" in n for n in agg)
    # text form unchanged alongside
    text = c.execute(f"EXPLAIN (ANALYZE) {AGG_Q}").rows()
    assert any("actual time=" in r[0] for r in text)


def test_explain_json_device_and_shard_keys():
    db, c = _db_with_tables()
    c.execute("SET serene_device = 'auto'")
    c.execute("SET serene_device_min_rows = 1024")
    c.execute("SET serene_shards = 2")
    out = c.execute(f"EXPLAIN (ANALYZE, FORMAT JSON) {FUSED_Q}").rows()
    doc = json.loads(out[0][0])

    def walk(node):
        yield node
        for k in node.get("Plans", []):
            yield from walk(k)

    nodes = list(walk(doc[0]["Plan"]))
    assert any("Device Time" in n for n in nodes), \
        "device attribution missing from JSON plan"


def test_explain_option_list_errors():
    db, c = _db_with_tables(2048)
    with pytest.raises(Exception):
        c.execute(f"EXPLAIN (FORMAT yaml) {AGG_Q}")
    with pytest.raises(Exception):
        c.execute(f"EXPLAIN (bogus) {AGG_Q}")
    # bare ANALYZE keyword form still works
    assert c.execute(f"EXPLAIN ANALYZE {AGG_Q}").rows()


# -- slow-query log timeline ------------------------------------------------


def test_slow_log_attaches_timeline():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    c.execute("SET serene_log_min_duration_ms = 0")
    c.execute(AGG_Q)
    rows = c.execute("SELECT message FROM sdb_log() "
                     "WHERE topic = 'slow_query'").rows()
    msgs = [m[0] for m in rows if AGG_Q.split()[1] in m[0]]
    assert msgs, "slow-query entry missing"
    last = msgs[-1]
    assert "timeline: trace_id=" in last
    assert "span " in last
    # top-5 widest spans: no more than 5 span lines after the header
    span_lines = [ln for ln in last.splitlines()
                  if ln.strip().startswith("span ")]
    assert 1 <= len(span_lines) <= 5
    # the plan tree still rides along
    assert "actual time=" in last


def test_top_spans_widest_first():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    entry = _spans_of(c, AGG_Q)
    tops = top_spans(entry, 5)
    widths = [s["end_ns"] - s["begin_ns"] for s in tops]
    assert widths == sorted(widths, reverse=True)
    assert all(s["cat"] != "query" for s in tops)


# -- pool observability gauges ----------------------------------------------


def test_pool_gauges_quiesce_and_accumulate():
    db, c = _db_with_tables()
    c.execute("SET serene_workers = 4")
    wait0 = sdb_metrics.POOL_TASK_WAIT_NS.value
    c.execute(AGG_Q)
    # live gauges settle back to idle once the statement drained
    assert sdb_metrics.POOL_QUEUE_DEPTH.value == 0
    assert sdb_metrics.POOL_RUNNING.value == 0
    assert sdb_metrics.POOL_TASK_WAIT_NS.value >= wait0
    # the ns counter and the histogram see the same task stream
    counts, _ = sdb_metrics.POOL_QUEUE_WAIT_HIST.snapshot()
    assert sum(counts) >= 1
    # the gauges surface through /metrics naming
    from serenedb_tpu.obs.export import prometheus_text
    txt = prometheus_text()
    assert "serenedb_pool_queue_depth" in txt
    assert "serenedb_pool_running_tasks" in txt
    assert "serenedb_pool_task_wait_ns" in txt


def test_chrome_trace_roundtrip_unit():
    entry = {"trace_id": 42, "query": "SELECT 1",
             "begin_epoch_us": 1000, "duration_ns": 5_000_000,
             "error": None, "spans_dropped": 0,
             "spans": [
                 {"name": "query", "cat": "query", "tid": 0,
                  "thread": "query", "begin_ns": 0,
                  "end_ns": 5_000_000,
                  "args": {"query": "SELECT 1", "trace_id": 42}},
                 {"name": "task", "cat": "pool", "tid": 7,
                  "thread": "sdb-morsel-0", "begin_ns": 1_000_000,
                  "end_ns": 2_000_000, "args": None}]}
    doc = chrome_trace(entry)
    json.loads(json.dumps(doc))      # serializable
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in x} == {"query", "task"}
    task = [e for e in x if e["name"] == "task"][0]
    assert task["ts"] == 1000.0 and task["dur"] == 1000.0
    names = [e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "sdb-morsel-0" in names


def test_trace_not_result_affecting():
    from serenedb_tpu.cache.result import RESULT_AFFECTING_SETTINGS
    assert "serene_trace" not in RESULT_AFFECTING_SETTINGS
    assert "serene_flight_recorder_queries" not in \
        RESULT_AFFECTING_SETTINGS


# -- one request, one timeline: stages (ISSUE 24) ----------------------------

from serenedb_tpu.obs import trace as trace_mod  # noqa: E402
from serenedb_tpu.sql import parser as sql_parser  # noqa: E402

DEVICE_AGG_Q = AGG_Q        # with serene_device = 'auto'
HOST_Q = "SELECT k, count(DISTINCT v + ts) AS u FROM facts GROUP BY k " \
         "ORDER BY u DESC, k LIMIT 5"
#: the front door's stages never occur without a front door
_ENGINE_STAGES = set(trace_mod.STAGES) - {"fd_parse", "fd_queue",
                                          "fd_encode"}


def _device_conn():
    db, c = _db_with_tables()
    c.execute("SET serene_device = 'auto'")
    c.execute("SET serene_device_min_rows = 1024")
    c.execute("SET serene_result_cache = off")
    return db, c


def _run_entry(c, sql, entry_point):
    """One statement through one of the engine's two entry points;
    returns (rows, its flight-recorder entry)."""
    if entry_point == "statement":
        rows = c.execute(sql).rows()
    else:
        st = sql_parser.parse(sql)[0]
        _names, _types, it = c.execute_streaming(st, [], sql_text=sql)
        rows = [r for b in it for r in b.rows()]
    return rows, _last_entry(c)


def _assert_partition(entry):
    """sum(stages) + other == request, in integer ns; the timeline's
    pieces are inside the request, in order and disjoint, and add up to
    the per-stage sums."""
    dur = entry["duration_ns"]
    stages = entry["stages"]
    assert all(isinstance(v, int) and v >= 0 for v in stages.values()), \
        stages
    assert set(stages) - {"other"} <= set(trace_mod.STAGES)
    assert sum(stages.values()) == dur
    by_name, cursor = {}, 0
    for name, b, e in entry["timeline"]:
        assert cursor <= b < e <= dur, entry["timeline"]
        cursor = e
        by_name[name] = by_name.get(name, 0) + e - b
    assert by_name == {k: v for k, v in stages.items()
                       if k != "other" and v}
    assert stages["other"] == dur - sum(by_name.values())


@pytest.mark.parametrize("entry_point", ["statement", "streaming"])
@pytest.mark.parametrize("sql,workers", [(DEVICE_AGG_Q, 0), (HOST_Q, 0),
                                         (AGG_Q, 4)])
def test_stage_partition_identity(sql, workers, entry_point):
    db, c = _device_conn()
    if workers:
        c.execute("SET serene_device = 'cpu'")
        c.execute("SET serene_workers = 4")    # stages on pool workers
    _run_entry(c, sql, entry_point)
    _rows, entry = _run_entry(c, sql, entry_point)
    _assert_partition(entry)
    names = {s["name"] for s in entry["spans"] if s["cat"] == "stage"}
    assert {"cache_probe", "plan"} <= names <= _ENGINE_STAGES
    # the engine began this trace itself: the request IS the statement
    assert entry["stages"]["other"] < entry["duration_ns"]


def test_partition_resolves_nesting_and_overlap():
    part, line = trace_mod.partition_stages(
        [(0, 100, "device_prepare"),        # the offload ...
         (20, 30, "device_enqueue"),        # ... but for what it holds
         (30, 70, "device_wait"),
         (60, 90, "host_group"),            # a worker, side by side
         (150, 260, "fd_encode")], 200)     # clipped to the request
    assert part == {"device_prepare": 30, "device_enqueue": 10,
                    "device_wait": 30, "host_group": 30, "fd_encode": 50,
                    "other": 50}
    assert sum(part.values()) == 200
    assert line == [["device_prepare", 0, 20], ["device_enqueue", 20, 30],
                    ["device_wait", 30, 60], ["host_group", 60, 90],
                    ["device_prepare", 90, 100], ["fd_encode", 150, 200]]


def _assert_parents(entry):
    by_id = {s["id"]: s for s in entry["spans"]}
    assert len(by_id) == len(entry["spans"]), "span ids repeat"
    assert by_id[0]["cat"] == "query" and by_id[0]["parent"] is None
    for s in entry["spans"]:
        if s["id"] == 0:
            continue
        par = by_id.get(s["parent"])
        assert par is not None, f"{s} names a parent that is not there"
        assert par["begin_ns"] <= s["begin_ns"] and \
            s["end_ns"] <= par["end_ns"], f"{par} does not enclose {s}"


@pytest.mark.parametrize("setup,sql", [
    (["SET serene_device = 'cpu'", "SET serene_workers = 4"], AGG_Q),
    (["SET serene_workers = 4", "SET serene_shards = 2"], FUSED_Q),
    (["SET serene_workers = 4", "SET serene_shards = 2",
      "SET serene_shard_combine = 'device'"], FUSED_Q),
    ([], DEVICE_AGG_Q),
    ([], HOST_Q)])
@pytest.mark.parametrize("entry_point", ["statement", "streaming"])
def test_every_span_has_an_enclosing_parent(setup, sql, entry_point):
    db, c = _device_conn()
    for s in setup:
        c.execute(s)
    _rows, entry = _run_entry(c, sql, entry_point)
    _assert_parents(entry)
    _assert_partition(entry)
    tasks = [s for s in entry["spans"] if s["name"] == "task"]
    assert tasks or "SET serene_device = 'cpu'" not in setup
    if tasks:
        # across the pool: a task's parent is the span that submitted
        # it, on another thread; what the task opened names the task
        by_id = {s["id"]: s for s in entry["spans"]}
        assert any(by_id[t["parent"]]["tid"] != t["tid"] for t in tasks)
        inner = [s for s in entry["spans"]
                 if by_id.get(s["parent"], {}).get("name") == "task"]
        assert inner and all(s["tid"] == by_id[s["parent"]]["tid"]
                             for s in inner)


def _gauges():
    return {n: g.value for n, g in (
        ("dev", sdb_metrics.STATEMENTS_ANSWERED_DEVICE),
        ("host", sdb_metrics.STATEMENTS_ANSWERED_HOST),
        ("hits", sdb_metrics.DEVICE_CACHE_HITS),
        ("misses", sdb_metrics.DEVICE_CACHE_MISSES))}


@pytest.mark.parametrize("entry_point", ["statement", "streaming"])
def test_device_and_host_statements_stage_sets_and_counters(entry_point):
    db, c = _device_conn()
    g0 = _gauges()
    _rows, first = _run_entry(c, DEVICE_AGG_Q, entry_point)
    g1 = _gauges()
    _rows, again = _run_entry(c, DEVICE_AGG_Q, entry_point)
    g2 = _gauges()
    _rows, host = _run_entry(c, HOST_Q, entry_point)
    g3 = _gauges()

    def stage_set(entry):
        return {s["name"] for s in entry["spans"] if s["cat"] == "stage"}

    device_set = {"cache_probe", "plan", "device_prepare",
                  "device_enqueue", "device_wait", "device_finalize",
                  "host_scan", "host_sort"}      # Project, Sort above it
    assert stage_set(first) == stage_set(again) == device_set
    assert first["answered"] == again["answered"] == "device"
    # HOST_Q: DISTINCT over an expression is refused before any device
    # work, so no device stage at all: host scan / group / sort
    assert stage_set(host) == {"cache_probe", "plan", "host_scan",
                               "host_group", "host_sort"}
    assert host["answered"] == "host"
    assert (g1["dev"] - g0["dev"], g2["dev"] - g1["dev"],
            g3["dev"] - g2["dev"]) == (1, 1, 0)
    assert (g1["host"] - g0["host"], g3["host"] - g2["host"]) == (0, 1)
    # the statement reads ts, k, v: three uploads the first time, three
    # resident columns found on the repeat, none asked for by HOST_Q
    assert (g1["misses"] - g0["misses"], g1["hits"] - g0["hits"]) == (3, 0)
    assert (g2["misses"] - g1["misses"], g2["hits"] - g1["hits"]) == (0, 3)
    assert (g3["misses"] - g2["misses"], g3["hits"] - g2["hits"]) == (0, 0)
    # the stage histograms saw each request once
    for e in (first, again, host):
        _assert_partition(e)


def test_result_cache_hit_counts_as_neither():
    db, c = _device_conn()
    c.execute("SET serene_result_cache = on")
    c.execute(DEVICE_AGG_Q)
    g0 = _gauges()
    c.execute(DEVICE_AGG_Q)
    entry = _last_entry(c)
    g1 = _gauges()
    assert entry["answered"] == "cache"
    assert (g1["dev"], g1["host"]) == (g0["dev"], g0["host"])
    assert {s["name"] for s in entry["spans"] if s["cat"] == "stage"} == \
        {"cache_probe"}
    _assert_partition(entry)


def test_stage_histograms_sum_to_request_latency():
    db, c = _device_conn()
    hists = dict(sdb_metrics.STAGE_HISTS,
                 request=sdb_metrics.REQUEST_LATENCY_HIST)
    before = {k: h.snapshot() for k, h in hists.items()}
    entries = [_run_entry(c, q, ep)[1]
               for q in (DEVICE_AGG_Q, HOST_Q, DEVICE_AGG_Q)
               for ep in ("statement", "streaming")]
    d_sum = {k: h.snapshot()[1] - before[k][1] for k, h in hists.items()}
    d_cnt = {k: sum(h.snapshot()[0]) - sum(before[k][0])
             for k, h in hists.items()}
    assert d_cnt["request"] == d_cnt["other"] == len(entries) == 6
    assert d_sum["request"] == sum(e["duration_ns"] for e in entries)
    assert sum(v for k, v in d_sum.items() if k != "request") == \
        d_sum["request"]
    # a stage is observed only in requests in which it occurred
    assert d_cnt["device_wait"] == 4 and d_cnt["host_group"] == 2
    assert d_cnt["fd_parse"] == d_cnt["fd_queue"] == 0


@pytest.mark.parametrize("sql", [DEVICE_AGG_Q, HOST_Q,
                                 "SELECT ts, v FROM facts ORDER BY v DESC "
                                 "LIMIT 7"])
def test_results_identical_with_trace_on_and_off(sql):
    db, c = _device_conn()
    got = {}
    for tr in ("on", "off", "on"):
        c.execute(f"SET serene_trace = {tr}")
        for ep in ("statement", "streaming"):
            if tr == "off":
                if ep == "statement":
                    rows = c.execute(sql).rows()
                else:
                    _n, _t, it = c.execute_streaming(
                        sql_parser.parse(sql)[0], [], sql_text=sql)
                    rows = [r for b in it for r in b.rows()]
                assert c._active_trace is None
            else:
                rows, _e = _run_entry(c, sql, ep)
            got.setdefault(repr(rows), []).append((tr, ep))
    assert len(got) == 1, got


def test_profiler_host_plane_holds_stages_not_envelopes(tmp_path):
    """Under `jax.profiler.start_trace` a stage is a TraceAnnotation on
    the profiler's own clock; envelopes are not annotated (they would
    swallow every idle gap in the benchmark's reduction)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    db, c = _device_conn()
    c.execute(DEVICE_AGG_Q)                  # compile outside the trace
    # a search's steps take host operands (a device aggregate's are
    # resident columns: its calls open no `device_upload`)
    c.execute("CREATE TABLE docs (id INT, body TEXT)")
    c.execute("INSERT INTO docs VALUES " + ", ".join(
        f"({i}, 'quick brown fox number{i % 7} jumps')"
        for i in range(256)))
    c.execute("CREATE INDEX ON docs USING inverted (body)")
    search = ("SELECT id, bm25(body) s FROM docs WHERE body @@ "
              "'fox number3' ORDER BY s DESC, id LIMIT 5")
    c.execute(search)
    jax.profiler.start_trace(str(tmp_path))
    try:
        c.execute(DEVICE_AGG_Q)
        tid = c._active_trace.trace_id
        c.execute(HOST_Q)
        assert len(c.execute(search).rows()) == 5
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    names, stats = set(), {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sdb."):
                    names.add(ev.name)
                    stats.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"sdb.plan", "sdb.device_wait", "sdb.device_enqueue",
            "sdb.host_group", "sdb.device_upload",
            "sdb.search_plan"} <= names
    assert names <= {"sdb." + s for s in trace_mod.STAGES}
    assert "sdb.request" not in names and "sdb.execute" not in names \
        and "sdb.query" not in names
    # the annotation carries the request's trace id
    assert any(str(d.get("trace_id")) == str(tid)
               for d in stats["sdb.device_wait"])


def test_stage_vocabulary_is_closed():
    """Every stage stamped anywhere in the program is one of STAGES (an
    unknown one would have no histogram), and the umbrella span and its
    helper are gone."""
    import os
    import re
    root = os.path.dirname(os.path.abspath(trace_mod.__file__))
    root = os.path.dirname(root)
    pat = re.compile(r'(?:\bstage|stage_of|add_stage)\(\s*(?:[\w.]+,\s*)?'
                     r'"([a-z_]+)"')
    seen = set()
    for d, _dirs, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(d, f)) as fh:
                text = fh.read()
            seen.update(pat.findall(text))
            assert '"device_dispatch"' not in text, f
            assert "_trace_span" not in text, f
    assert seen == set(trace_mod.STAGES)
    assert set(sdb_metrics.STAGE_HISTS) == set(trace_mod.STAGES) | {"other"}


@pytest.mark.parametrize("frontdoor", [True, False])
def test_http_sql_request_timeline(frontdoor):
    """`POST /_sql` through either HTTP transport: one trace from the
    receipt of the request to the response's last byte, readable back
    through `GET /trace/<id>`."""
    import time

    from serenedb_tpu.server.http_server import HttpServer
    db, c = _db_with_tables()
    old = SETTINGS.get_global("serene_frontdoor")
    SETTINGS.set_global("serene_frontdoor", frontdoor)
    try:
        srv = HttpServer(db)
        srv.start()
    finally:
        SETTINGS.set_global("serene_frontdoor", old)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        last = FLIGHT.last()
        mark = last["trace_id"] if last else 0
        sql = "SELECT k, count(*) FROM facts WHERE ts < 100 GROUP BY k " \
              f"ORDER BY k LIMIT {3 + frontdoor}"
        req = urllib.request.Request(
            f"{base}/_sql", data=json.dumps({"query": sql}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req).read())
        assert len(body["rows"]) == 3 + frontdoor
        deadline = time.monotonic() + 5.0
        while True:
            mine = [e for e in FLIGHT.snapshot()
                    if e["query"] == sql and e["trace_id"] > mark]
            if mine or time.monotonic() > deadline:
                break
            time.sleep(0.005)
        assert len(mine) == 1
        entry = mine[0]
        _assert_partition(entry)
        _assert_parents(entry)
        want = {"fd_parse", "plan", "fd_encode"} | \
            ({"fd_queue"} if frontdoor else set())
        assert want <= set(entry["stages"])
        doc = json.loads(urllib.request.urlopen(
            f"{base}/trace/{entry['trace_id']}").read())
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert want <= names
        assert doc["otherData"]["stages_ns"] == entry["stages"]
        assert doc["otherData"]["answered"] == entry["answered"]
    finally:
        srv.stop()
