"""ES-compatible HTTP API tests via urllib against a live HttpServer."""

import json
import urllib.error
import urllib.request

import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.server.http_server import HttpServer


@pytest.fixture(scope="module")
def srv():
    db = Database()
    s = HttpServer(db, port=0)
    s.start()
    yield s
    s.stop()


def req(srv, method, path, body=None, raw=False):
    data = None
    headers = {}
    if body is not None:
        data = body.encode() if isinstance(body, str) else \
            json.dumps(body).encode()
        headers["Content-Type"] = "application/json" if not raw else \
            "application/x-ndjson"
    r = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", data=data, headers=headers,
        method=method)
    try:
        with urllib.request.urlopen(r, timeout=30) as resp:
            ct = resp.headers.get("Content-Type", "")
            raw_body = resp.read().decode()
            return resp.status, (json.loads(raw_body)
                                 if "json" in ct else raw_body)
    except urllib.error.HTTPError as e:
        raw_body = e.read().decode()
        try:
            return e.code, json.loads(raw_body)
        except json.JSONDecodeError:
            return e.code, raw_body


def test_root_and_health(srv):
    status, body = req(srv, "GET", "/")
    assert status == 200 and body["tagline"] == "You Know, for Search"
    status, body = req(srv, "GET", "/_cluster/health")
    assert body["status"] == "green"


def test_index_lifecycle_and_docs(srv):
    status, body = req(srv, "PUT", "/books")
    assert status == 200 and body["acknowledged"]
    status, body = req(srv, "PUT", "/books")
    assert status == 400  # already exists
    status, body = req(srv, "PUT", "/books/_doc/1",
                       {"title": "The quick brown fox", "pages": 120})
    assert status == 201 and body["result"] == "created"
    req(srv, "PUT", "/books/_doc/2",
        {"title": "lazy dogs sleeping", "pages": 300})
    req(srv, "POST", "/books/_doc", {"title": "quick reference", "pages": 50})
    status, body = req(srv, "GET", "/books/_doc/1")
    assert status == 200 and body["_source"]["pages"] == 120
    status, body = req(srv, "GET", "/books/_doc/404")
    assert status == 404 and body["found"] is False

    status, body = req(srv, "GET", "/books/_count")
    assert body["count"] == 3

    # match query with scoring
    status, body = req(srv, "POST", "/books/_search",
                       {"query": {"match": {"title": "quick"}}})
    assert status == 200
    hits = body["hits"]["hits"]
    assert body["hits"]["total"]["value"] == 2
    ids = {h["_id"] for h in hits}
    assert "1" in ids and len(ids) == 2   # doc 1 + the auto-id doc
    scores = [h["_score"] for h in hits]
    assert scores == sorted(scores, reverse=True)

    # range + bool
    status, body = req(srv, "POST", "/books/_search", {
        "query": {"bool": {
            "must": [{"match": {"title": "quick"}}],
            "filter": [{"range": {"pages": {"gte": 100}}}]}}})
    assert [h["_id"] for h in body["hits"]["hits"]] == ["1"]

    # match_phrase
    status, body = req(srv, "POST", "/books/_search",
                       {"query": {"match_phrase": {"title": "quick brown"}}})
    assert [h["_id"] for h in body["hits"]["hits"]] == ["1"]

    # delete doc
    status, body = req(srv, "DELETE", "/books/_doc/2")
    assert body["result"] == "deleted"
    status, body = req(srv, "GET", "/books/_count")
    assert body["count"] == 2


def test_bulk_and_cat(srv):
    ndjson = "\n".join([
        json.dumps({"index": {"_index": "logs", "_id": "a"}}),
        json.dumps({"msg": "disk error on node1", "level": "error"}),
        json.dumps({"index": {"_index": "logs", "_id": "b"}}),
        json.dumps({"msg": "all systems normal", "level": "info"}),
        json.dumps({"delete": {"_index": "logs", "_id": "missing"}}),
    ]) + "\n"
    status, body = req(srv, "POST", "/_bulk", ndjson, raw=True)
    assert status == 200
    assert len(body["items"]) == 3
    status, body = req(srv, "GET", "/_cat/indices?format=json")
    names = {r["index"] for r in body}
    assert "logs" in names
    status, body = req(srv, "POST", "/logs/_search",
                       {"query": {"term": {"level": "error"}}})
    assert [h["_id"] for h in body["hits"]["hits"]] == ["a"]


def test_search_sort_and_pagination(srv):
    req(srv, "PUT", "/nums")
    for i in range(5):
        req(srv, "PUT", f"/nums/_doc/{i}", {"v": i})
    status, body = req(srv, "POST", "/nums/_search", {
        "query": {"match_all": {}}, "size": 2, "from": 1,
        "sort": [{"v": {"order": "desc"}}]})
    assert [h["_source"]["v"] for h in body["hits"]["hits"]] == [3, 2]
    assert body["hits"]["total"]["value"] == 5


def test_mapping_reflects_fields(srv):
    req(srv, "PUT", "/m1")
    req(srv, "PUT", "/m1/_doc/1", {"name": "x", "n": 3, "f": 1.5, "b": True})
    status, body = req(srv, "GET", "/m1/_mapping")
    props = body["m1"]["mappings"]["properties"]
    assert props["name"]["type"] == "text"
    assert props["n"]["type"] == "long"
    assert props["f"]["type"] == "double"
    assert props["b"]["type"] == "boolean"


def test_sql_endpoint(srv):
    status, body = req(srv, "POST", "/_sql", {"query": "SELECT 1 + 1 AS two"})
    assert status == 200
    assert body["columns"] == [{"name": "two"}]
    assert body["rows"] == [[2]]


def test_error_shapes(srv):
    status, body = req(srv, "GET", "/missing_index/_search")
    assert status == 404
    assert body["error"]["type"] == "index_not_found_exception"
    status, body = req(srv, "POST", "/_sql", {"query": "SELECT FROM"})
    assert status == 400 and body["error"]["type"] == "sql_exception"


def test_refresh_enables_index_scoring(srv):
    req(srv, "PUT", "/scored")
    # equal doc lengths so tf dominates (BM25 length normalization would
    # otherwise favor the shorter doc)
    req(srv, "PUT", "/scored/_doc/1", {"body": "alpha alpha beta"})
    req(srv, "PUT", "/scored/_doc/2", {"body": "alpha beta gamma"})
    status, body = req(srv, "POST", "/scored/_refresh")
    assert status == 200
    status, body = req(srv, "POST", "/scored/_search",
                       {"query": {"match": {"body": "alpha"}}})
    hits = body["hits"]["hits"]
    assert len(hits) == 2
    assert hits[0]["_id"] == "1"           # higher tf ranks first
    assert hits[0]["_score"] > hits[1]["_score"] > 0


def test_sql_injection_via_sort_and_fields_rejected(srv):
    req(srv, "PUT", "/inj")
    req(srv, "PUT", "/inj/_doc/1", {"v": 1})
    # injection through sort order
    status, body = req(srv, "POST", "/inj/_search", {
        "query": {"match_all": {}},
        "sort": [{"v": "asc; DROP TABLE inj; SELECT 1"}]})
    assert status == 400
    # injection through field names
    status, body = req(srv, "POST", "/inj/_search", {
        "query": {"term": {'v" = 1; DROP TABLE inj; --': 1}}})
    assert status == 400
    # table still there
    status, body = req(srv, "GET", "/inj/_count")
    assert status == 200 and body["count"] == 1


def test_unmatched_routes_respond(srv):
    req(srv, "PUT", "/resp")
    status, _ = req(srv, "POST", "/resp")      # no verb, POST
    assert status == 405
    status, _ = req(srv, "GET", "/resp/_doc")  # _doc without id
    assert status == 405


def test_sql_endpoint_does_not_poison_shared_state(srv):
    req(srv, "PUT", "/iso")
    req(srv, "PUT", "/iso/_doc/1", {"v": 1})
    req(srv, "POST", "/_sql", {"query": "BEGIN"})
    req(srv, "POST", "/_sql", {"query": "SELECT broken FROM nowhere"})
    status, body = req(srv, "GET", "/iso/_count")
    assert status == 200 and body["count"] == 1


def test_bulk_partial_failure_reports_per_item(srv):
    req(srv, "PUT", "/pb")
    req(srv, "PUT", "/pb/_doc/1", {"n": 5})
    ndjson = "\n".join([
        json.dumps({"index": {"_index": "pb", "_id": "2"}}),
        json.dumps({"n": 7}),
        json.dumps({"index": {"_index": "DROP TABLE pb", "_id": "3"}}),
        json.dumps({"n": 9}),
    ]) + "\n"
    status, body = req(srv, "POST", "/_bulk", ndjson, raw=True)
    assert status == 200
    assert body["errors"] is True
    assert body["items"][0]["index"]["status"] == 201
    assert body["items"][1]["index"]["status"] == 400


def test_scroll_pagination(srv):
    req(srv, "PUT", "/scr")
    for i in range(7):
        req(srv, "PUT", f"/scr/_doc/{i}", {"n": i})
    status, body = req(srv, "POST", "/scr/_search?scroll=1m",
                       {"query": {"match_all": {}}, "size": 3,
                        "sort": [{"n": "asc"}]})
    assert status == 200
    sid = body["_scroll_id"]
    assert [h["_source"]["n"] for h in body["hits"]["hits"]] == [0, 1, 2]
    status, body = req(srv, "POST", "/_search/scroll",
                       {"scroll_id": sid, "size": 3})
    assert [h["_source"]["n"] for h in body["hits"]["hits"]] == [3, 4, 5]
    status, body = req(srv, "POST", "/_search/scroll",
                       {"scroll_id": sid, "size": 3})
    assert [h["_source"]["n"] for h in body["hits"]["hits"]] == [6]
    status, body = req(srv, "DELETE", "/_search/scroll",
                       {"scroll_id": sid})
    assert body["succeeded"] is True
    status, body = req(srv, "POST", "/_search/scroll", {"scroll_id": sid})
    assert status == 404


def test_mget_and_stats(srv):
    req(srv, "PUT", "/mg")
    req(srv, "PUT", "/mg/_doc/a", {"v": 1})
    req(srv, "PUT", "/mg/_doc/b", {"v": 2})
    status, body = req(srv, "POST", "/mg/_mget", {"ids": ["a", "b", "zz"]})
    assert [d["found"] for d in body["docs"]] == [True, True, False]
    status, body = req(srv, "GET", "/mg/_stats")
    assert body["indices"]["mg"]["primaries"]["docs"]["count"] == 2


def test_scroll_covers_all_hits_and_keeps_size(srv):
    req(srv, "PUT", "/deep")
    ndjson = "\n".join(
        json.dumps({"index": {"_index": "deep", "_id": str(i)}}) + "\n" +
        json.dumps({"n": i}) for i in range(25)) + "\n"
    req(srv, "POST", "/_bulk", ndjson, raw=True)
    status, body = req(srv, "POST", "/deep/_search?scroll=30s",
                       {"size": 7, "sort": [{"n": "asc"}],
                        "query": {"match_all": {}}})
    sid = body["_scroll_id"]
    seen = [h["_source"]["n"] for h in body["hits"]["hits"]]
    assert len(seen) == 7
    while True:
        status, body = req(srv, "POST", "/_search/scroll",
                           {"scroll_id": sid})  # no size: reuse initial 7
        page = [h["_source"]["n"] for h in body["hits"]["hits"]]
        if not page:
            break
        assert len(page) <= 7
        seen += page
    assert seen == list(range(25))   # every hit reached, in order


def test_scroll_expiry():
    from serenedb_tpu.server.es_api import EsApi
    from serenedb_tpu.engine import Database
    api = EsApi(Database())
    api.index_doc("exp", {"n": 1}, "1")
    res = api.search_scroll_start("exp", {"size": 1}, "1ms")
    import time
    time.sleep(0.01)
    import pytest as _pytest
    from serenedb_tpu.server.es_api import EsError
    with _pytest.raises(EsError):
        api.search_scroll_next(res["_scroll_id"])


def test_mget_standard_docs_shape_and_errors(srv):
    req(srv, "PUT", "/mgs")
    req(srv, "PUT", "/mgs/_doc/x", {"v": 1})
    # per-doc _index (standard ES shape) at the top-level endpoint
    status, body = req(srv, "POST", "/_mget",
                       {"docs": [{"_index": "mgs", "_id": "x"},
                                 {"_index": "mgs", "_id": "nope"}]})
    assert status == 200
    assert [d["found"] for d in body["docs"]] == [True, False]
    # malformed doc entry → 400, not a phantom id
    status, body = req(srv, "POST", "/mgs/_mget", {"docs": [{"_idd": "x"}]})
    assert status == 400
    # stats on a missing index → 404
    status, body = req(srv, "GET", "/no_such/_stats")
    assert status == 404


def test_scroll_delete_list_form_and_refresh(srv):
    req(srv, "PUT", "/scr2")
    for i in range(4):
        req(srv, "PUT", f"/scr2/_doc/{i}", {"n": i})
    status, body = req(srv, "POST", "/scr2/_search?scroll=30s",
                       {"size": 2, "sort": [{"n": "asc"}]})
    sid = body["_scroll_id"]
    # continuation with the standard body shape refreshes keepalive
    status, body = req(srv, "POST", "/_search/scroll",
                       {"scroll": "30s", "scroll_id": sid})
    assert [h["_source"]["n"] for h in body["hits"]["hits"]] == [2, 3]
    # ES list form of delete
    status, body = req(srv, "DELETE", "/_search/scroll",
                       {"scroll_id": [sid]})
    assert body["succeeded"] is True and body["num_freed"] == 1


def test_msearch(srv):
    for i, txt in enumerate(["quick brown fox", "lazy dog", "quick wit"]):
        req(srv, "PUT", f"/ms/_doc/{i}", {"body": txt})
    nd = "\n".join([
        json.dumps({"index": "ms"}),
        json.dumps({"query": {"match": {"body": "quick"}}}),
        json.dumps({}),
        json.dumps({"query": {"match": {"body": "dog"}}, "size": 1}),
        json.dumps({"index": "nope"}),
        json.dumps({"query": {"match_all": {}}}),
    ]) + "\n"
    status, body = req(srv, "POST", "/ms/_msearch", nd, raw=True)
    assert status == 200
    rs = body["responses"]
    assert len(rs) == 3
    assert rs[0]["status"] == 200
    assert rs[0]["hits"]["total"]["value"] == 2
    assert rs[1]["hits"]["total"]["value"] == 1
    assert len(rs[1]["hits"]["hits"]) == 1
    # bad index fails only its own item
    assert rs[2]["status"] == 404 and "error" in rs[2]

    # top-level _msearch requires index per item
    nd = json.dumps({}) + "\n" + json.dumps({"query": {"match_all": {}}}) \
        + "\n"
    status, body = req(srv, "POST", "/_msearch", nd, raw=True)
    assert status == 200
    assert body["responses"][0]["status"] == 400

    # odd line count is a request-level error
    status, body = req(srv, "POST", "/_msearch",
                       json.dumps({"index": "ms"}) + "\n", raw=True)
    assert status == 400


def test_cat_health_and_count(srv):
    status, body = req(srv, "GET", "/_cat/health?format=json")
    assert status == 200 and body[0]["status"] == "green"
    status, body = req(srv, "GET", "/_cat/count/ms?format=json")
    assert status == 200 and body[0]["count"] == "3"
    status, body = req(srv, "GET", "/_cat/count?format=json")
    assert status == 200 and int(body[0]["count"]) >= 3
    status, body = req(srv, "GET", "/_cat/count/doesnotexist?format=json")
    assert status == 404
    status, body = req(srv, "GET", "/_cat/health")
    assert status == 200 and "green" in body
    status, body = req(srv, "GET", "/_cat/nosuch")
    assert status == 400


def test_msearch_empty_header_line(srv):
    # ES allows a blank header line meaning "defaults" — pairing must hold
    nd = "\n" + json.dumps({"query": {"match": {"body": "quick"}}}) + "\n"
    status, body = req(srv, "POST", "/ms/_msearch", nd, raw=True)
    assert status == 200
    assert body["responses"][0]["hits"]["total"]["value"] == 2
    # blank header item mixed with an explicit-index item
    nd = "\n" + json.dumps({"query": {"match_all": {}}}) + "\n" + \
        json.dumps({"index": "ms"}) + "\n" + \
        json.dumps({"query": {"match": {"body": "dog"}}}) + "\n"
    status, body = req(srv, "POST", "/ms/_msearch", nd, raw=True)
    rs = body["responses"]
    assert rs[0]["hits"]["total"]["value"] == 3
    assert rs[1]["hits"]["total"]["value"] == 1
    # blank BODY line is a per-item parse error, not mis-pairing
    nd = json.dumps({"index": "ms"}) + "\n\n"
    status, body = req(srv, "POST", "/ms/_msearch", nd + nd, raw=True)
    assert status == 200
    assert all(r["status"] == 400 for r in body["responses"])


def test_cat_indices_text_four_columns(srv):
    status, body = req(srv, "GET", "/_cat/indices")
    assert status == 200
    line = next(ln for ln in body.splitlines() if " ms " in f" {ln} ")
    assert line.split() == ["green", "open", "ms", "3"]


def test_analyze(srv):
    status, body = req(srv, "POST", "/_analyze",
                       {"analyzer": "standard", "text": "Quick-Brown Foxes"})
    assert status == 200
    toks = [t["token"] for t in body["tokens"]]
    assert toks == ["quick", "brown", "foxes"]
    assert body["tokens"][0]["start_offset"] == 0
    # stemming analyzer
    status, body = req(srv, "POST", "/_analyze",
                       {"analyzer": "text", "text": "running dogs"})
    assert [t["token"] for t in body["tokens"]] == ["run", "dog"]
    # unknown analyzer
    status, body = req(srv, "POST", "/_analyze",
                       {"analyzer": "nope", "text": "x"})
    assert status == 400
    # empty body → no tokens
    status, body = req(srv, "POST", "/_analyze", {})
    assert status == 200 and body["tokens"] == []


def test_analyze_index_scoped(srv):
    req(srv, "PUT", "/anz")
    req(srv, "PUT", "/anz/_doc/1", {"body": "running dogs"})
    # index-scoped without explicit analyzer uses the index's analyzer
    # (inverted default "text": stemming) — the terms the index stores
    status, body = req(srv, "POST", "/anz/_analyze",
                       {"text": "running dogs"})
    assert status == 200
    assert [t["token"] for t in body["tokens"]] == ["run", "dog"]
    # field routing
    status, body = req(srv, "POST", "/anz/_analyze",
                       {"field": "body", "text": "running"})
    assert [t["token"] for t in body["tokens"]] == ["run"]
    # explicit analyzer wins
    status, body = req(srv, "POST", "/anz/_analyze",
                       {"analyzer": "keyword", "text": "One Two"})
    assert [t["token"] for t in body["tokens"]] == ["One Two"]
    # unknown index 404s
    status, body = req(srv, "POST", "/ghost_idx/_analyze", {"text": "x"})
    assert status == 404
    # non-object body is a 400, not a 500
    status, body = req(srv, "POST", "/_analyze", '"hello"')
    assert status == 400


def test_update_doc(srv):
    req(srv, "PUT", "/upd")
    req(srv, "PUT", "/upd/_doc/1", {"title": "old", "count": 1})
    # partial merge
    status, body = req(srv, "POST", "/upd/_update/1",
                       {"doc": {"title": "new"}})
    assert status == 200 and body["result"] == "updated"
    status, body = req(srv, "GET", "/upd/_doc/1")
    assert body["_source"] == {"title": "new", "count": 1}
    # noop when nothing changes
    status, body = req(srv, "POST", "/upd/_update/1",
                       {"doc": {"title": "new"}})
    assert body["result"] == "noop"
    # missing doc without upsert -> 404
    status, body = req(srv, "POST", "/upd/_update/ghost",
                       {"doc": {"x": 1}})
    assert status == 404
    # upsert creates
    status, body = req(srv, "POST", "/upd/_update/2",
                       {"doc": {"x": 1}, "upsert": {"title": "fresh"}})
    assert body["result"] == "created"
    status, body = req(srv, "GET", "/upd/_doc/2")
    assert body["_source"] == {"title": "fresh"}
    # doc_as_upsert
    status, body = req(srv, "POST", "/upd/_update/3",
                       {"doc": {"v": 7}, "doc_as_upsert": True})
    assert body["result"] == "created"
    status, body = req(srv, "GET", "/upd/_doc/3")
    assert body["_source"] == {"v": 7}
    # malformed body
    status, body = req(srv, "POST", "/upd/_update/1", {})
    assert status == 400


def test_concurrent_updates_lose_no_fields(srv):
    import threading as _t
    req(srv, "PUT", "/cu")
    req(srv, "PUT", "/cu/_doc/1", {"base": 0})
    errs = []

    def worker(field):
        for i in range(10):
            st, body = req(srv, "POST", "/cu/_update/1",
                           {"doc": {field: i}})
            if st != 200:
                errs.append(body)

    ts = [_t.Thread(target=worker, args=(f"f{k}",)) for k in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errs
    st, body = req(srv, "GET", "/cu/_doc/1")
    src = body["_source"]
    # every thread's final write must survive (atomic read-merge-write)
    assert src["f0"] == 9 and src["f1"] == 9 and src["f2"] == 9
    assert src["base"] == 0


def test_update_empty_upsert_and_bulk_parity(srv):
    # {} upsert is legal and indexes an empty doc
    st, body = req(srv, "POST", "/eu/_update/1", {"upsert": {}})
    assert st == 200 and body["result"] == "created"
    # bulk update now shares update_doc semantics: missing doc -> error item
    nd = "\n".join([
        json.dumps({"update": {"_index": "eu", "_id": "ghost"}}),
        json.dumps({"doc": {"x": 1}}),
    ]) + "\n"
    st, body = req(srv, "POST", "/_bulk", nd, raw=True)
    assert body["errors"] is True
    assert body["items"][0]["update"]["status"] == 404
    # non-dict doc -> 400, not 500
    st, body = req(srv, "POST", "/eu/_update/1", {"doc": [1, 2]})
    assert st == 400


def test_delete_by_query(srv):
    req(srv, "PUT", "/dbq")
    for i, lvl in enumerate(["err", "err", "ok"]):
        req(srv, "PUT", f"/dbq/_doc/{i}", {"level": lvl})
    st, body = req(srv, "POST", "/dbq/_delete_by_query",
                   {"query": {"term": {"level": "err"}}})
    assert st == 200 and body["deleted"] == 2
    st, body = req(srv, "GET", "/dbq/_count")
    assert body["count"] == 1
    # match_all wipes the rest
    st, body = req(srv, "POST", "/dbq/_delete_by_query",
                   {"query": {"match_all": {}}})
    assert body["deleted"] == 1
    # missing query -> 400; unknown index -> 404
    st, _ = req(srv, "POST", "/dbq/_delete_by_query", {})
    assert st == 400
    st, _ = req(srv, "POST", "/ghostdbq/_delete_by_query",
                {"query": {"match_all": {}}})
    assert st == 404


def test_delete_by_query_max_docs_and_bad_body(srv):
    req(srv, "PUT", "/dbm")
    for i in range(4):
        req(srv, "PUT", f"/dbm/_doc/{i}", {"x": 1})
    st, body = req(srv, "POST", "/dbm/_delete_by_query",
                   {"query": {"match_all": {}}, "max_docs": 2})
    assert st == 200 and body["deleted"] == 2
    st, body = req(srv, "GET", "/dbm/_count")
    assert body["count"] == 2
    st, _ = req(srv, "POST", "/dbm/_delete_by_query", "[1, 2]")
    assert st == 400


@pytest.mark.parametrize("result_cache", [False, True],
                         ids=["result_cache_off", "result_cache_on"])
def test_the_total_of_a_match_is_one_number_on_every_route(srv,
                                                           result_cache):
    """`hits.total.value` of `_search`, `_count`, SQL count(*) and the
    length of the Stream-mode row set agree for the same `match`, before
    and after a refresh that adds a segment: the count is taken from doc
    bitsets (`count_filter`), the rows from `eval_filter`."""
    from serenedb_tpu.utils import metrics
    from serenedb_tpu.utils.config import REGISTRY as SETTINGS
    name = f"tot{int(result_cache)}"
    # word → the rows that hold it: every d-th; "zephyr" is a sparse term
    # (a handful of rows), the first ones dense
    words = {"alpha": 1, "beta": 2, "river": 3, "stone": 5, "amber": 7,
             "quill": 13, "zephyr": 97}

    def body_of(i):
        return " ".join(w for w, d in words.items() if i % d == 0)

    c = srv.db.connect()
    c.execute(f'CREATE TABLE {name} ("_id" VARCHAR, "_source" VARCHAR, '
              "body VARCHAR)")

    def insert(lo, hi):
        c.execute(f"INSERT INTO {name} VALUES " + ", ".join(
            f"('{i}', '{{}}', " +
            ("NULL" if i % 11 == 5 else f"'{body_of(i)}'") + ")"
            for i in range(lo, hi)))

    def totals(question):
        expected = sum(
            1 for i in range(n_rows) if i % 11 != 5 and
            set(question.split()) & set(body_of(i).split()))
        st, res = req(srv, "POST", f"/{name}/_search",
                      {"query": {"match": {"body": question}}, "size": 3})
        assert st == 200 and res["hits"]["total"]["relation"] == "eq"
        st, cnt = req(srv, "POST", f"/{name}/_count",
                      {"query": {"match": {"body": question}}})
        assert st == 200
        pred = "body @@ '" + " | ".join(question.split()) + "'"
        sql = c.execute(f"SELECT count(*) FROM {name} WHERE {pred}").scalar()
        rows = c.execute(f'SELECT "_id" FROM {name} WHERE {pred}').rows()
        assert res["hits"]["total"]["value"] == cnt["count"] == sql == \
            len(rows) == expected
        return expected

    prior = SETTINGS.get_global("serene_result_cache")
    SETTINGS.set_global("serene_result_cache", result_cache)
    try:
        n_rows = 260
        insert(0, n_rows)
        c.execute(f"CREATE INDEX {name}_body ON {name} USING inverted (body)")
        b0 = metrics.SEARCH_COUNT_BITSET.value
        m0 = metrics.SEARCH_COUNT_MATERIALIZED.value
        for question in ("beta zephyr", "quill amber nosuchword", "zephyr"):
            assert totals(question) > 0
        assert metrics.SEARCH_COUNT_BITSET.value > b0
        n_rows = 400
        insert(260, n_rows)
        req(srv, "POST", f"/{name}/_refresh")
        from serenedb_tpu.search.index import find_index
        t = srv.db.schemas["main"].tables[name]
        assert len(find_index(t, "body").searcher("body").segments) == 2
        for question in ("beta zephyr", "quill amber nosuchword", "zephyr"):
            assert totals(question) > 0
        # a disjunction of terms never builds its doc set to be counted
        # (with the cache on, the Stream statement's set is found there)
        if not result_cache:
            assert metrics.SEARCH_COUNT_MATERIALIZED.value == m0
    finally:
        SETTINGS.set_global("serene_result_cache", prior)
