"""The flat (exact) vector index: `USING ivf (col) WITH (type = 'flat')`,
scored by `knn_flat_scan` — one `dot_general` at Precision.HIGHEST per row
tile with a running exact top-k — against the benchmark's float64
reference (benchmark/references/knn_numpy.py: one reference, not two);
its closed program set (CREATE INDEX prebuilds the rungs, searches build
nothing); the pool it lives in; and the ES surface over it (`similarity`,
`index_options.type`, `_score`, `hits.total`, one request on one
timeline).
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.references import knn_numpy                    # noqa: E402
from serenedb_tpu.columnar import dtypes as dt                # noqa: E402
from serenedb_tpu.columnar.column import Batch, Column        # noqa: E402
from serenedb_tpu.engine import Database                      # noqa: E402
from serenedb_tpu.obs import device as obs_device             # noqa: E402
from serenedb_tpu.obs import trace as trace_mod               # noqa: E402
from serenedb_tpu.obs.trace import FLIGHT                     # noqa: E402
from serenedb_tpu.ops import vector as vops                   # noqa: E402
from serenedb_tpu.search import vector_store                  # noqa: E402
from serenedb_tpu.search.ivf import find_ivf_index            # noqa: E402
from serenedb_tpu.search.vector_store import VPOOL            # noqa: E402
from serenedb_tpu.server.es_api import EsApi                  # noqa: E402
from serenedb_tpu.server.http_server import Router            # noqa: E402
from serenedb_tpu.utils import metrics                        # noqa: E402
from serenedb_tpu.utils.config import REGISTRY as SETTINGS    # noqa: E402

LIMIT = 1e-5           # benchmark/configs/msmarco-passage-dense.json
KNN_FAMILIES = ("knn_flat_scan", "knn_flat_aux")


def _unit(n, dim, seed):
    """Clustered unit vectors, as the benchmark's look: near neighbours
    exist, so the top-10 is not decided by one digit."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((64, dim))
    x = centres[rng.integers(64, size=n)] + \
        0.7 * rng.standard_normal((n, dim))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32)


def _table(db, x, name="passages", options="type = 'flat', metric = 'cos'",
           nulls=()):
    c = db.connect()
    n, dim = x.shape
    c.execute(f'CREATE TABLE {name} ("_id" VARCHAR, "_source" VARCHAR, '
              f"emb VECTOR({dim}))")
    valid = np.ones(n, bool)
    valid[list(nulls)] = False
    ids = np.arange(n).astype(str)
    c._insert_batch(db.schemas["main"].tables[name], Batch(
        ["_id", "_source", "emb"],
        [Column.from_numpy(ids),
         Column.from_numpy(np.asarray(['{"pid": %d}' % i
                                       for i in range(n)])),
         Column(dt.vector_of(dim), x, valid)]))
    c.execute(f"CREATE INDEX {name}_emb ON {name} USING ivf (emb) "
              f"WITH ({options})")
    return c


@pytest.fixture(scope="module", params=[(20_000, 768), (3_001, 100)],
                ids=["20000x768", "3001x100"])
def corpus(request):
    n, dim = request.param
    x = _unit(n, dim, 31)
    db = Database()
    c = _table(db, x)
    rng = np.random.default_rng(77)
    qs = x[rng.integers(n, size=48)] + \
        (0.5 / np.sqrt(dim)) * rng.standard_normal((48, dim))
    qs = (qs / np.linalg.norm(qs, axis=1, keepdims=True)).astype(np.float32)
    return db, c, x, qs


def _index(db, name="passages"):
    return find_ivf_index(db.schemas["main"].tables[name], "emb")


def test_the_flat_program_gives_the_float64_references_top10(corpus):
    db, _c, x, qs = corpus
    idx = _index(db)
    assert idx.flat and idx.metric == "cos" and len(idx.segs) == 1
    assert idx.segs[0].vals is db.schemas["main"].tables["passages"] \
        .full_batch(["emb"]).column("emb").data     # no second host copy
    d, r = idx.search(qs, 10, nprobe=1)
    ref_i, ref_c = knn_numpy.topk(x, qs, 10)
    worst = 0.0
    for qi in range(len(qs)):
        got_cos = 1.0 - d[qi].astype(np.float64)
        exact = knn_numpy.cosines(x, qs[qi], r[qi])
        worst = max(worst, float(np.max(
            np.abs(knn_numpy.es_score(got_cos) -
                   knn_numpy.es_score(exact)) / knn_numpy.es_score(exact))))
        kth = knn_numpy.es_score(ref_c[qi][-1])
        for row, e in zip(r[qi], exact):      # ids equal but for stated ties
            assert row in ref_i[qi] or \
                knn_numpy.es_score(e) >= kth * (1 - LIMIT), (qi, row)
        assert np.all(np.diff(d[qi]) >= 0)
    assert 0 < worst < LIMIT


@pytest.mark.parametrize("batch", [1, 8, 9, 40])
def test_a_batch_of_any_size_equals_the_same_questions_alone(corpus, batch):
    """Rungs 1, 8 and 32: 9 rides the 32 rung, 40 is split in 32 + 8."""
    db, _c, _x, qs = corpus
    idx = _index(db)
    alone = [idx.search(qs[i:i + 1], 10, 1) for i in range(batch)]
    d0 = metrics.VECTOR_SEARCH_DISPATCHES.value
    r0 = metrics.VECTOR_ROWS_SCANNED.value
    d, r = idx.search(qs[:batch], 10, 1)
    for i in range(batch):
        # a matmul's sums are ordered by its shape, so a question's
        # distances move in the last bits with the rung it rides (the
        # IVF probe's add chain does not; its parity tests stay): the
        # ids are the same and the scores within the limit
        assert r[i].tolist() == alone[i][1][0].tolist()
        assert np.max(np.abs(d[i] - alone[i][0][0])) < LIMIT / 10
    want = 2 if batch > vops.FLAT_RUNGS[-1] else 1
    assert metrics.VECTOR_SEARCH_DISPATCHES.value - d0 == want
    # rows are counted per DISPATCH, not per question
    assert metrics.VECTOR_ROWS_SCANNED.value - r0 == want * idx.num_rows


def test_the_bfloat16_control_fails_at_this_size(corpus):
    _db, _c, x, qs = corpus
    ids, cos = knn_numpy.topk(x, qs, 10)

    class Src:
        size, k = 10, 10
        sent = [list(qs)]
    ops = [{"client": 0, "ok": True, "answer": {
        "total": 10, "relation": "eq",
        "hits": [(str(int(i)), float(knn_numpy.es_score(c)))
                 for i, c in zip(ids[j], cos[j])]}}
        for j in range(len(qs))]
    ds = {"emb": x, "n_docs": len(x)}
    cfg = {"limits": {"score_rel_err_max": LIMIT}}
    right, n = knn_numpy.check(ops, Src, ds, 3, {"sample": 1024}, cfg=cfg)
    assert n == len(qs)
    assert right["wrong_hits"] == 0 and right["wrong_totals"] == 0
    assert right["score_rel_err_max"] < 1e-12
    control, _ = knn_numpy.check(ops, Src, ds, 3, {"sample": 1024},
                                 control=True, cfg=cfg)
    assert control["score_rel_err_max"] > LIMIT      # by one limit at least
    assert control["wrong_totals"] == 0              # and not by each
    assert knn_numpy.scan_bytes(1_000_000, 768) == 3_072_000_000


# -- the closed program set ------------------------------------------------------


def _ledger_compiles():
    return {p["family"]: p["compiles"]
            for p in obs_device.PROGRAMS.snapshot()
            if p["family"] in KNN_FAMILIES}


class _JaxBuilds:
    _events: list = []
    _hooked = False

    def __enter__(self):
        import jax.monitoring as mon
        if not _JaxBuilds._hooked:
            mon.register_event_duration_secs_listener(
                lambda ev, dur, **kw: _JaxBuilds._events.append(
                    (ev, threading.current_thread())))
            _JaxBuilds._hooked = True
        self.n0 = len(_JaxBuilds._events)
        self.others = set(threading.enumerate()) - \
            {threading.current_thread()}
        return self

    def __exit__(self, *exc):
        self.built = sum(
            ev == "/jax/core/compile/backend_compile_duration"
            and th not in self.others
            for ev, th in _JaxBuilds._events[self.n0:])
        return False


def test_create_index_prebuilds_and_200_questions_build_nothing():
    x = _unit(1_500, 40, 5)
    db = Database()
    p0 = metrics.VECTOR_PROGRAMS_PREBUILT.value
    c0 = _ledger_compiles()
    _table(db, x, "closed")
    assert metrics.VECTOR_PROGRAMS_PREBUILT.value - p0 == \
        len(vops.FLAT_RUNGS)
    c1 = _ledger_compiles()
    assert c1.get("knn_flat_scan", 0) - c0.get("knn_flat_scan", 0) == \
        len(vops.FLAT_RUNGS)
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    rng = np.random.default_rng(8)
    qs = rng.standard_normal((200, 40)).astype(np.float32)
    f0 = metrics.VECTOR_QUERIES_SCORED_FLAT.value
    pr0 = metrics.VECTOR_QUERIES_SCORED_PROBE.value
    b0 = metrics.SEARCH_BATCH_QUERIES.value
    errs = []

    def client(lo):
        cc = db.connect()
        cc.execute("SET serene_search_batch = on")
        try:
            for q in qs[lo:lo + 25]:
                lit = "[" + ",".join(map(str, q)) + "]"
                rows = cc.execute(
                    f"SELECT \"_id\", vec_cos(emb, '{lit}') d FROM closed "
                    "ORDER BY d LIMIT 10").rows()
                assert len(rows) == 10
        except Exception as e:  # noqa: BLE001 — reported below
            errs.append(e)
    try:
        with _JaxBuilds() as jb:
            ts = [threading.Thread(target=client, args=(lo,))
                  for lo in range(0, 200, 25)]
            [t.start() for t in ts]
            [t.join(timeout=120) for t in ts]
    finally:
        SETTINGS.set_global("serene_result_cache", prior)
    assert not errs, errs
    assert _ledger_compiles() == c1              # by the ledger
    assert jb.built == 0                         # and by jax.monitoring
    assert metrics.VECTOR_QUERIES_SCORED_FLAT.value - f0 == 200
    assert metrics.VECTOR_QUERIES_SCORED_PROBE.value == pr0
    assert metrics.SEARCH_BATCH_QUERIES.value - b0 == 200
    fams = {p["family"]: p for p in
            obs_device.stats_section()["programs"]}
    assert fams["knn_flat_scan"]["compiles"] >= len(vops.FLAT_RUNGS)
    pool = obs_device.stats_section()["vector_pool"]
    assert pool["queries_flat"] >= 200 and pool["flat_bytes"] > 0


def test_the_index_type_alone_chooses_the_program():
    x = _unit(600, 24, 2)
    db = Database()
    _table(db, x, "flat_t")
    c = _table(db, x, "ivf_t", options="lists = 4, metric = 'cos'")
    assert _index(db, "flat_t").flat and not _index(db, "ivf_t").flat
    c.execute("SET serene_nprobe = 4")
    lit = "[" + ",".join(map(str, x[17])) + "]"
    f0 = metrics.VECTOR_QUERIES_SCORED_FLAT.value
    p0 = metrics.VECTOR_QUERIES_SCORED_PROBE.value
    a = c.execute(f"SELECT \"_id\" FROM flat_t ORDER BY "
                  f"vec_cos(emb, '{lit}') LIMIT 5").rows()
    assert (metrics.VECTOR_QUERIES_SCORED_FLAT.value - f0,
            metrics.VECTOR_QUERIES_SCORED_PROBE.value - p0) == (1, 0)
    b = c.execute(f"SELECT \"_id\" FROM ivf_t ORDER BY "
                  f"vec_cos(emb, '{lit}') LIMIT 5").rows()
    assert (metrics.VECTOR_QUERIES_SCORED_FLAT.value - f0,
            metrics.VECTOR_QUERIES_SCORED_PROBE.value - p0) == (1, 1)
    assert a == b and a[0] == ("17",)


@pytest.mark.parametrize("metric, fn", [("l2", "vec_l2"), ("ip", "vec_ip"),
                                        ("cos", "vec_cos")])
def test_every_metric_nulls_appends_and_deletes(metric, fn):
    x = _unit(900, 20, 4) * np.float32(1.7)
    db = Database()
    c = _table(db, x, "m", options=f"type = 'flat', metric = '{metric}'",
               nulls=(5, 899))
    q = x[5] * np.float32(0.9)            # nearest is row 5: a NULL
    lit = "[" + ",".join(map(str, q)) + "]"
    sql = f"SELECT \"_id\", {fn}(emb, '{lit}') d FROM m ORDER BY d LIMIT 6"

    def oracle(rows_valid):
        x64, q64 = x.astype(np.float64), q.astype(np.float64)
        d = {"l2": ((x64 - q64) ** 2).sum(1), "ip": -(x64 @ q64),
             "cos": 1 - (x64 @ q64) / (np.linalg.norm(x64, axis=1) *
                                       np.linalg.norm(q64))}[metric]
        order = [i for i in np.lexsort((np.arange(len(d)), d))
                 if rows_valid(i)][:6]
        return order, d[order]
    got = c.execute(sql).rows()
    want, wd = oracle(lambda i: i not in (5, 899))
    assert [int(r[0]) for r in got] == want
    assert np.allclose([r[1] for r in got], wd, rtol=1e-5, atol=1e-6)
    # a pure append: a tail segment, prebuilt, found by the next search
    p0 = metrics.VECTOR_PROGRAMS_PREBUILT.value
    c.execute(f"INSERT INTO m VALUES ('900', '{{}}', '{lit}')")
    got = c.execute(sql).rows()
    assert got[0][0] == "900" and [int(r[0]) for r in got[1:]] == want[:5]
    idx = _index(db, "m")
    assert len(idx.segs) == 2 and idx.segs[1].base == 900
    assert metrics.VECTOR_PROGRAMS_PREBUILT.value - p0 == \
        len(vops.FLAT_RUNGS)
    # a delete leaves the index stale: the host scan answers, exactly
    c.execute("DELETE FROM m WHERE \"_id\" = '900'")
    assert [int(r[0]) for r in c.execute(sql).rows()] == want


def test_flat_segments_share_the_pools_lru_under_the_devices_budget(
        monkeypatch):
    """One pool, one LRU (no fourth cache): a flat segment that does not
    fit evicts the least recently used flat segment; one larger than the
    budget is scanned from a per-call upload, same answers."""
    xa, xb = _unit(400, 16, 1), _unit(400, 16, 2)
    db = Database()
    one = 400 * 16 * 4 + 400 * 4
    monkeypatch.setattr(vector_store, "_flat_budget_bytes",
                        lambda: one + 100)
    ca = _table(db, xa, "a")
    ia = _index(db, "a")
    ua = VPOOL.seg_uid(ia.segs[0])
    assert ua in VPOOL._entries and VPOOL._entries[ua].nbytes == one
    ev0 = metrics.VECTOR_POOL_EVICTIONS.value
    _table(db, xb, "b")
    assert ua not in VPOOL._entries            # evicted, LRU
    assert metrics.VECTOR_POOL_EVICTIONS.value - ev0 == 1
    lit = "[" + ",".join(map(str, xa[3])) + "]"
    sql = f"SELECT \"_id\" FROM a ORDER BY vec_cos(emb, '{lit}') LIMIT 3"
    first = ca.execute(sql).rows()             # back in, b out
    assert first[0] == ("3",) and ua in VPOOL._entries
    assert metrics.VECTOR_BYTES_RESIDENT.value >= one
    monkeypatch.setattr(vector_store, "_flat_budget_bytes", lambda: 10)
    VPOOL.release_segment(ua)
    assert ca.execute(sql).rows() == first     # per-call upload
    assert ua not in VPOOL._entries
    rows = {r["segment"]: r for r in VPOOL.snapshot()}
    assert all(r["pages"] == 0 for r in rows.values() if r["bytes"] == one)


def test_the_budget_comes_from_the_device(monkeypatch):
    import jax

    class Dev:
        def memory_stats(self):
            return {"bytes_limit": 16_000_000_000}
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    assert vector_store._flat_budget_bytes() == \
        int(16_000_000_000 * vector_store._FLAT_SHARE)
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [type("D", (), {
                            "memory_stats": lambda self: None})()])
    assert vector_store._flat_budget_bytes() == \
        int(SETTINGS.get_global("serene_device_cache_mb")) << 20


# -- the ES surface ------------------------------------------------------------


def _knn_body(q, k=10, size=10, **extra):
    return {"knn": {"field": "emb", "query_vector": [float(v) for v in q],
                    "k": k, "num_candidates": 100, **extra}, "size": size}


def test_es_knn_scores_totals_and_pages(corpus):
    db, _c, x, qs = corpus
    es = EsApi(db)
    for q in qs[:6]:
        res = es.search("passages", _knn_body(q))
        hits = res["hits"]
        assert hits["total"] == {"value": 10, "relation": "eq"}
        ids = [int(h["_id"]) for h in hits["hits"]]
        exact = knn_numpy.es_score(knn_numpy.cosines(x, q, ids))
        got = np.array([h["_score"] for h in hits["hits"]])
        assert np.max(np.abs(got - exact) / exact) < LIMIT  # (1 + cos) / 2
        assert hits["hits"][0]["_source"] == {"pid": ids[0]}
        assert hits["max_score"] == got[0]
        page = es.search("passages", dict(_knn_body(q, size=3), **{
            "from": 2}))["hits"]
        assert [h["_id"] for h in page["hits"]] == \
            [str(i) for i in ids[2:5]]
        assert page["total"] == {"value": 10, "relation": "eq"}
    with pytest.raises(Exception) as e:
        es.search("passages", {"knn": {"field": "emb", "k": 3,
                                       "query_vector": ["a", {}]}})
    assert "query_vector" in str(e.value) or "vector" in str(e.value)


@pytest.mark.parametrize("similarity, score", [
    (None, lambda q, v: (1 + q @ v / np.linalg.norm(q) /
                         np.linalg.norm(v)) / 2),
    ("cosine", lambda q, v: (1 + q @ v / np.linalg.norm(q) /
                             np.linalg.norm(v)) / 2),
    ("dot_product", lambda q, v: (1 + q @ v) / 2),
    ("l2_norm", lambda q, v: 1 / (1 + ((q - v) ** 2).sum()))])
def test_es_mapping_declares_the_metric_and_the_type(similarity, score):
    db = Database()
    es = EsApi(db)
    fdef = {"type": "dense_vector", "dims": 4,
            "index_options": {"type": "flat"}}
    if similarity:
        fdef["similarity"] = similarity
    es.create_index("docs", {"mappings": {"properties": {
        "emb": fdef, "title": {"type": "text"}}}})
    t = db.schemas["main"].tables["docs"]
    assert t.column_types[t.column_names.index("emb")] == dt.vector_of(4)
    assert es.mapping("docs")["docs"]["mappings"]["properties"]["emb"] == \
        {"type": "dense_vector", "dims": 4}
    vs = np.array([[1, 0, 0, 0], [0, 1, 0, 0], [.6, .8, 0, 0],
                   [.5, .5, .5, .5]])
    for i, v in enumerate(vs):
        es.index_doc("docs", {"emb": v.tolist(), "title": f"t{i}"}, str(i))
    es.refresh("docs")
    idx = find_ivf_index(t, "emb")
    assert idx is not None and idx.flat and idx.metric == {
        None: "cos", "cosine": "cos", "dot_product": "ip",
        "l2_norm": "l2"}[similarity]
    q = np.array([.8, .6, 0, 0])
    res = es.search("docs", {"knn": {"field": "emb", "k": 3,
                                     "query_vector": q.tolist()}})
    want = sorted(((score(q, v), -i) for i, v in enumerate(vs)),
                  reverse=True)[:3]
    assert [h["_id"] for h in res["hits"]["hits"]] == \
        [str(-i) for _, i in want]
    assert np.allclose([h["_score"] for h in res["hits"]["hits"]],
                       [s for s, _ in want], rtol=1e-6)
    assert res["hits"]["total"] == {"value": 3, "relation": "eq"}


def test_es_mapping_without_flat_keeps_the_ivf_defaults():
    db = Database()
    es = EsApi(db)
    es.create_index("old", {"mappings": {"properties": {
        "emb": {"type": "dense_vector", "dims": 3}}}})
    t = db.schemas["main"].tables["old"]
    assert t.column_types[t.column_names.index("emb")] == dt.VARCHAR
    es.index_doc("old", {"emb": [1.0, 0.0, 0.0]}, "a")
    es.index_doc("old", {"emb": [0.0, 2.0, 0.0]}, "b")
    es.refresh("old")
    idx = find_ivf_index(t, "emb")
    assert not idx.flat and idx.metric == "l2"
    hits = es.search("old", {"knn": {"field": "emb", "k": 2,
                                     "query_vector": [1, 0, 0]}})["hits"]
    assert [(h["_id"], h["_score"]) for h in hits["hits"]] == \
        [("a", 1.0), ("b", 1.0 / 6.0)]                # 1 / (1 + d)
    with pytest.raises(Exception):
        es.create_index("bad", {"mappings": {"properties": {
            "emb": {"type": "dense_vector", "dims": 3,
                    "similarity": "manhattan"}}}})


def test_an_es_knn_is_one_request_on_one_timeline(corpus):
    db, _c, _x, qs = corpus
    router = Router(EsApi(db))
    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", False)
    try:
        router.handle("POST", "/passages/_search",
                      json.dumps(_knn_body(qs[40])).encode())       # warm
        n0 = metrics.REQUEST_LATENCY_HIST.count
        dev0 = metrics.STATEMENTS_ANSWERED_DEVICE.value
        status, data, _ = router.handle(
            "POST", "/passages/_search",
            json.dumps(_knn_body(qs[41])).encode())
    finally:
        SETTINGS.set_global("serene_result_cache", prior)
    assert status == 200 and len(json.loads(data)["hits"]["hits"]) == 10
    assert metrics.REQUEST_LATENCY_HIST.count - n0 == 1
    assert metrics.STATEMENTS_ANSWERED_DEVICE.value - dev0 == 1
    entry = FLIGHT.last()
    assert entry["query"].startswith("POST /passages/_search")
    # sum(stages) + other = the request, to the nanosecond
    assert sum(entry["stages"].values()) == entry["duration_ns"]
    assert set(entry["stages"]) - {"other"} <= set(trace_mod.STAGES)
    assert {"fd_parse", "plan", "device_prepare", "device_enqueue",
            "device_wait", "device_finalize", "fd_encode"} <= \
        set(entry["stages"])
    assert sum(s["name"] == "execute" for s in entry["spans"]) == 1
