"""Front-door tests: the unified asyncio serving tier
(server/frontdoor.py) — socket-level admission (53300/429 before any
parse), keep-alive pipelining semantics, slow-reader backpressure, idle
reaping, deterministic shutdown, connection observability, and
bit-identity with the legacy ThreadingHTTPServer parity oracle."""

import http.client
import json
import socket
import threading
import time

import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.sched.governor import CONNGATE
from serenedb_tpu.server.http_server import HttpServer, LegacyHttpServer
from serenedb_tpu.utils import metrics
from serenedb_tpu.utils.config import REGISTRY as SETTINGS


@pytest.fixture()
def setting():
    """Set globals for one test, restoring priors afterwards (pass 19
    runs this suite with SERENE_MAX_CONNECTIONS=8 forced — tests must
    put back what they found, not a hardcoded default)."""
    prior = {}

    def set_(name, value):
        if name not in prior:
            prior[name] = SETTINGS.get_global(name)
        SETTINGS.set_global(name, value)

    yield set_
    for name, value in prior.items():
        SETTINGS.set_global(name, value)


@pytest.fixture(scope="module")
def db():
    d = Database()
    c = d.connect()
    c.execute("CREATE TABLE kv (k INT, v VARCHAR)")
    c.execute("INSERT INTO kv VALUES (1, 'one'), (2, 'two')")
    yield d
    d.close()


@pytest.fixture(scope="module")
def front(db):
    s = HttpServer(db, port=0)   # serene_frontdoor defaults on
    s.start()
    from serenedb_tpu.server.frontdoor import FrontDoor
    assert isinstance(s._impl, FrontDoor)
    yield s
    s.stop()


# -- raw h1 client helpers ---------------------------------------------------

def _request_bytes(method, path, body=b"", headers=()):
    head = [f"{method} {path} HTTP/1.1", "Host: x",
            f"Content-Length: {len(body)}"]
    head += [f"{k}: {v}" for k, v in headers]
    return ("\r\n".join(head) + "\r\n\r\n").encode() + body


def _read_response(sock, buf=b""):
    """One HTTP/1.1 response off a raw socket: (status, headers, body,
    leftover). Pipelined readers pass the previous call's leftover back
    in as `buf` — two small responses can share one recv."""
    while b"\r\n\r\n" not in buf:
        d = sock.recv(65536)
        assert d, f"peer closed mid-header: {buf[:200]!r}"
        buf += d
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    ln = int(headers.get("content-length") or 0)
    while len(rest) < ln:
        d = sock.recv(65536)
        assert d, "peer closed mid-body"
        rest += d
    return status, headers, rest[:ln], rest[ln:]


def _sql(port, query, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/_sql", json.dumps({"query": query}),
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, json.loads(body)


# -- parity oracle -----------------------------------------------------------

def test_parity_frontdoor_vs_legacy(db, setting):
    """The acceptance bit: identical requests through the asyncio front
    door and the legacy ThreadingHTTPServer produce byte-identical
    bodies (both run the same pure Router, so this is structural — the
    test guards the transports' body handling)."""
    legacy = LegacyHttpServer(db, port=0)
    legacy.start()
    front = HttpServer(db, port=0)
    front.start()
    try:
        # seed through ONE server only (mutations must not run twice)
        conn = http.client.HTTPConnection("127.0.0.1", front.port)
        nd = (json.dumps({"index": {"_index": "par", "_id": "1"}}) + "\n" +
              json.dumps({"title": "quick brown fox", "n": 1}) + "\n" +
              json.dumps({"index": {"_index": "par", "_id": "2"}}) + "\n" +
              json.dumps({"title": "lazy dog", "n": 2}) + "\n")
        conn.request("POST", "/_bulk", nd,
                     {"Content-Type": "application/x-ndjson"})
        assert conn.getresponse().read()
        conn.close()

        reads = [
            ("GET", "/", None),
            ("GET", "/_cluster/health", None),
            ("GET", "/_cat/indices?format=json", None),
            ("GET", "/_cat/count/par", None),
            ("GET", "/par/_mapping", None),
            ("POST", "/par/_count", None),
            ("GET", "/par/_doc/1", None),
            ("HEAD", "/par", None),
            ("HEAD", "/nosuch", None),
            ("POST", "/par/_search", json.dumps(
                {"query": {"match": {"title": "fox"}}})),
            ("POST", "/par/_msearch",
             '{}\n{"query": {"match_all": {}}, "sort": ["n"]}\n'),
            ("POST", "/_analyze", json.dumps({"text": "Quick Brown"})),
            ("POST", "/_mget", json.dumps(
                {"index": "par", "ids": ["1", "2"]})),
            ("POST", "/_sql", json.dumps(
                {"query": "SELECT k, v FROM kv ORDER BY k"})),
            ("POST", "/_test/echo", '{"a": 1}'),
            ("GET", "/_test/ping", None),
            ("GET", "/_unknown_endpoint", None),
            ("POST", "/par/_nosuchverb", None),
        ]
        for method, path, body in reads:
            results = []
            for srv in (front, legacy):
                c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                               timeout=30)
                c.request(method, path, body,
                          {"Content-Type": "application/json"}
                          if body else {})
                r = c.getresponse()
                results.append((r.status, r.read(),
                                r.getheader("Content-Type")))
                c.close()
            assert results[0] == results[1], \
                f"parity break on {method} {path}: {results}"
    finally:
        front.stop()
        legacy.stop()


# -- socket-level admission --------------------------------------------------

def test_http_429_past_max_connections(db, setting):
    srv = HttpServer(db, port=0)
    srv.start()
    try:
        # sockets of the tests before this one leave the gate when their
        # servers' loops get to it: the one slot has to be free first
        from serenedb_tpu.sched.governor import CONNGATE
        deadline = time.monotonic() + 10
        while CONNGATE._conns and time.monotonic() < deadline:
            time.sleep(0.02)
        # the gate is the process's: a socket an earlier file of this
        # worker still holds counts against the cap, so the cap is what
        # is held plus the one slot this test fills
        setting("serene_max_connections", len(CONNGATE._conns) + 1)
        hold = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=30)
        hold.request("GET", "/_test/ping")
        assert hold.getresponse().read() == b'{"ok": true}'
        # the keep-alive connection above holds the only slot: the next
        # SOCKET is answered 429 without us sending a single byte —
        # rejection strictly before any request parse
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        status, headers, body, _ = _read_response(s)
        assert status == 429
        assert headers.get("retry-after") == "1"
        assert b"too_many_connections" in body
        s.close()
        assert CONNGATE.snapshot()["rejected_total"] >= 1
        assert metrics.CONNECTIONS_REJECTED.value >= 1
        # releasing the slot re-opens the door
        hold.close()
        deadline = time.time() + 5
        while time.time() < deadline:
            s2 = socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=10)
            s2.sendall(_request_bytes("GET", "/_test/ping"))
            status, _, body, _ = _read_response(s2)
            s2.close()
            if status == 200:
                break
            time.sleep(0.05)
        assert status == 200 and body == b'{"ok": true}'
    finally:
        srv.stop()


def test_pg_53300_shares_gate_with_http(db, setting):
    """Both protocols drain ONE serene_max_connections budget: with an
    HTTP keep-alive holding the only slot, a pgwire connect gets a
    clean 53300 ErrorResponse before any startup parse."""
    from serenedb_tpu.server.frontdoor import FrontDoor
    from serenedb_tpu.server.pgwire import PgServer

    pg = PgServer(db, port=0)
    fd = FrontDoor(db, http_port=0, pg=pg)
    fd.start()
    try:
        assert pg.pool is fd.executor    # one engine-boundary pool
        hold = http.client.HTTPConnection("127.0.0.1", fd.port,
                                          timeout=30)
        hold.request("GET", "/_test/ping")
        hold.getresponse().read()
        setting("serene_max_connections", 1)
        s = socket.create_connection(("127.0.0.1", pg.port), timeout=10)
        data = s.recv(4096)       # server speaks first: ErrorResponse
        assert data[:1] == b"E" and b"53300" in data
        s.close()
        hold.close()
    finally:
        fd.stop()


# -- keep-alive pipelining (PR 8 isolation contract over the new tier) -------

def test_pipelined_requests_serialized_on_one_connection(db, front):
    """Pipelined requests on ONE connection are processed strictly in
    order — the second statement observes the first's write — and an
    error response doesn't kill the keep-alive session."""
    port = front.port
    _sql(port, "CREATE TABLE IF NOT EXISTS pipe (n INT)")
    _sql(port, "DELETE FROM pipe")
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    b1 = json.dumps({"query": "INSERT INTO pipe VALUES (7)"}).encode()
    b2 = json.dumps({"query": "SELECT count(*) AS c FROM pipe"}).encode()
    s.sendall(_request_bytes("POST", "/_sql", b1) +
              _request_bytes("POST", "/_sql", b2) +
              _request_bytes("POST", "/_sql", b"{not json") +
              _request_bytes("GET", "/_test/ping"))
    st1, _, r1, rest = _read_response(s)
    assert st1 == 200
    status, _, r2, rest = _read_response(s, rest)
    assert status == 200
    assert json.loads(r2)["rows"] == [[1]]   # saw the pipelined INSERT
    status, _, r3, rest = _read_response(s, rest)
    assert status == 400                      # malformed fails ALONE
    status, _, r4, _ = _read_response(s, rest)
    assert status == 200 and r4 == b'{"ok": true}'  # session survived
    s.close()


def test_concurrent_across_connections_serial_within(front):
    """Transport concurrency contract: two connections run their
    requests CONCURRENTLY (wall ≈ one sleep), while two pipelined
    requests on one connection run back-to-back (wall ≈ two sleeps)."""
    port = front.port

    def timed_single():
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(_request_bytes("GET", "/_test/sleep?ms=400"))
        _read_response(s)
        s.close()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=timed_single) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    concurrent_s = time.perf_counter() - t0
    assert concurrent_s < 0.75, \
        f"two connections did not run concurrently: {concurrent_s:.2f}s"

    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    t0 = time.perf_counter()
    s.sendall(_request_bytes("GET", "/_test/sleep?ms=400") +
              _request_bytes("GET", "/_test/sleep?ms=400"))
    _, _, _, rest = _read_response(s)
    _read_response(s, rest)
    pipelined_s = time.perf_counter() - t0
    s.close()
    assert pipelined_s >= 0.8, \
        f"pipelined requests overlapped on one connection: " \
        f"{pipelined_s:.2f}s"


def test_msearch_and_bulk_keepalive_one_connection(db, front):
    """ES _bulk/_msearch over the new frontend on a single keep-alive
    connection: a malformed bulk item still fails alone (PR 8 isolation
    survives the port), and _msearch works on the same socket after."""
    conn = http.client.HTTPConnection("127.0.0.1", front.port,
                                      timeout=30)
    nd = (json.dumps({"index": {"_index": "iso", "_id": "1"}}) + "\n" +
          json.dumps({"v": 1}) + "\n" +
          json.dumps({"index": {"_index": "DROP TABLE iso",
                                "_id": "2"}}) + "\n" +
          json.dumps({"v": 2}) + "\n" +
          json.dumps({"index": {"_index": "iso", "_id": "3"}}) + "\n" +
          json.dumps({"v": 3}) + "\n")
    conn.request("POST", "/_bulk", nd,
                 {"Content-Type": "application/x-ndjson"})
    r = conn.getresponse()
    body = json.loads(r.read())
    assert r.status == 200 and body["errors"] is True
    states = [next(iter(i.values())) for i in body["items"]]
    assert any("error" in s for s in states)          # the bad item
    assert any("error" not in s for s in states)      # good ones landed
    # same socket, next request: keep-alive survived the item error
    conn.request("POST", "/iso/_msearch",
                 '{}\n{"query": {"match_all": {}}}\n',
                 {"Content-Type": "application/x-ndjson"})
    r = conn.getresponse()
    ms = json.loads(r.read())
    assert r.status == 200
    assert ms["responses"][0]["hits"]["total"]["value"] == 2
    conn.close()


def test_chunked_request_body(front):
    s = socket.create_connection(("127.0.0.1", front.port), timeout=30)
    payload = b'{"chunked": true}'
    req = (b"POST /_test/echo HTTP/1.1\r\nHost: x\r\n"
           b"Transfer-Encoding: chunked\r\n\r\n")
    for i in range(0, len(payload), 5):
        part = payload[i:i + 5]
        req += f"{len(part):x}\r\n".encode() + part + b"\r\n"
    req += b"0\r\n\r\n"
    s.sendall(req)
    status, _, body, _ = _read_response(s)
    assert status == 200 and body == payload
    s.close()


# -- slow-client robustness --------------------------------------------------

def test_slow_reader_triggers_pause_reading_bounded_buffer(front, setting):
    """A reader that stops consuming mid-resultset: the session hits the
    write high-water mark, pauses reading, and buffers a BOUNDED number
    of bytes (PR 12 RSS accounting confirms no unbounded growth) until
    the client drains."""
    from serenedb_tpu.obs.resources import read_rss_bytes

    setting("serene_conn_write_high_kb", 64)
    n = 16 * 1024 * 1024
    payload = b"x" * n
    pauses0 = CONNGATE.snapshot()["pause_reads_total"]
    rss0 = read_rss_bytes()

    s = socket.create_connection(("127.0.0.1", front.port), timeout=60)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 16 * 1024)
    s.sendall(_request_bytes("POST", "/_test/echo", payload))
    first = s.recv(1024)          # a taste of the response, then stall
    assert first
    deadline = time.time() + 20
    while time.time() < deadline:
        snap = CONNGATE.snapshot()
        if snap["pause_reads_total"] > pauses0:
            break
        time.sleep(0.05)
    assert snap["pause_reads_total"] > pauses0, \
        "write high-water never paused reading"
    # bounded buffering while stalled: the transport holds at most the
    # high-water mark plus one write chunk, not the 16 MB body
    assert snap["buffered_bytes"] <= 64 * 1024 + 64 * 1024 + 4096
    rss_stalled = read_rss_bytes()
    assert rss_stalled - rss0 < 200 * 1024 * 1024
    # drain: the full, correct response arrives
    expect_total = None
    buf = first
    while True:
        d = s.recv(1 << 20)
        if not d:
            break
        buf += d
        if expect_total is None and b"\r\n\r\n" in buf:
            head, _, _rest = buf.partition(b"\r\n\r\n")
            for ln in head.split(b"\r\n"):
                if ln.lower().startswith(b"content-length"):
                    expect_total = len(head) + 4 + int(ln.split(b":")[1])
        if expect_total is not None and len(buf) >= expect_total:
            break
    s.close()
    assert buf.endswith(payload[-1024:])
    assert buf.count(b"x" * 4096) > 0
    head, _, got_body = buf.partition(b"\r\n\r\n")
    assert got_body == payload, \
        f"drained body mismatch: {len(got_body)} vs {len(payload)}"


def test_half_open_client_reaped_without_pool_slot(db, setting):
    """SYN, no bytes, silence: the idle timeout reaps the socket and
    its admission slot; the engine-boundary executor never sees it."""
    setting("serene_idle_conn_timeout_s", 0.4)
    srv = HttpServer(db, port=0)
    srv.start()
    try:
        impl = srv._impl
        exec_threads0 = len(getattr(impl.executor, "_threads", ()))
        open0 = metrics.CONNECTIONS_OPEN.value
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.settimeout(5)
        t0 = time.time()
        data = s.recv(1024)       # blocks until the server reaps us
        assert data == b""        # clean close, no bytes ever exchanged
        assert time.time() - t0 < 4
        s.close()
        deadline = time.time() + 5
        while time.time() < deadline and \
                metrics.CONNECTIONS_OPEN.value > open0:
            time.sleep(0.05)
        assert metrics.CONNECTIONS_OPEN.value == open0
        assert len(getattr(impl.executor, "_threads", ())) == \
            exec_threads0, "half-open client burned an executor slot"
    finally:
        srv.stop()


def test_half_open_pg_client_reaped(db, setting):
    from serenedb_tpu.server.pgwire import PgServer

    setting("serene_idle_conn_timeout_s", 0.4)
    from serenedb_tpu.server.frontdoor import FrontDoor
    pg = PgServer(db, port=0)
    fd = FrontDoor(db, http_port=0, pg=pg)
    fd.start()
    try:
        s = socket.create_connection(("127.0.0.1", pg.port), timeout=10)
        s.settimeout(5)
        assert s.recv(1024) == b""    # reaped mid-handshake
        s.close()
    finally:
        fd.stop()


# -- shutdown ---------------------------------------------------------------

def test_shutdown_deterministic_no_lingering_threads(db):
    before = set(threading.enumerate())
    srv = HttpServer(db, port=0)
    srv.start()
    # leave one idle keep-alive session parked in a read and one
    # completed request behind — both must be reaped by stop()
    idle = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    idle.request("GET", "/_test/ping")
    idle.getresponse().read()
    open0 = metrics.CONNECTIONS_OPEN.value
    assert open0 >= 1
    impl = srv._impl
    srv.stop()
    # stop() joined the loop thread (or raised) and shut the executor
    # down with wait=True — every thread THIS server started is gone
    assert impl._thread is None
    for t in getattr(impl.executor, "_threads", ()):
        assert not t.is_alive(), f"executor thread leaked: {t.name}"
    leaked = [t.name for t in set(threading.enumerate()) - before
              if t.is_alive()]
    assert not leaked, f"threads outlived stop(): {leaked}"
    idle.close()
    deadline = time.time() + 5
    while time.time() < deadline and \
            metrics.CONNECTIONS_OPEN.value > open0 - 1:
        time.sleep(0.05)
    assert metrics.CONNECTIONS_OPEN.value <= open0 - 1


# -- observability -----------------------------------------------------------

def test_connection_observability_surfaces(db, front):
    port = front.port
    # hold one idle keep-alive connection so the surfaces have a row
    hold = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    hold.request("GET", "/_test/ping")
    hold.getresponse().read()
    time.sleep(0.1)

    # /_stats.connections
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/_stats")
    stats = json.loads(conn.getresponse().read())
    conn.close()
    cs = stats["connections"]
    assert cs["open"] >= 2                 # hold + the _stats request
    assert cs["idle"] >= 1
    assert set(cs) >= {"open", "idle", "active", "max_connections",
                       "rejected_total", "pause_reads_total",
                       "buffered_bytes"}
    assert stats["metrics"]["ConnectionsOpen"] >= 2

    # /metrics Prometheus exposition
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    for series in ("serenedb_connections_open",
                   "serenedb_connections_idle",
                   "serenedb_connections_active",
                   "serenedb_connections_rejected",
                   "serenedb_socket_bytes_buffered",
                   "serenedb_accept_queue_wait_seconds_bucket"):
        assert series in text, f"missing {series} in /metrics"

    # sdb_connections(): the pg_stat_activity analog at the socket
    c = db.connect()
    rows = list(c.execute(
        "SELECT pid, protocol, state, idle_s FROM sdb_connections() "
        "ORDER BY pid").rows())
    assert any(p == "http" and s == "idle" and i >= 0
               for _, p, s, i in rows), rows
    assert all(pid > 0 for pid, _, _, _ in rows)
    # the bare-relation spelling works too, like sdb_admission
    rows2 = list(c.execute("SELECT protocol FROM sdb_connections").rows())
    assert len(rows2) >= 1
    c.close()
    hold.close()


def test_accept_queue_wait_histogram_observes(front):
    counts0, _ = metrics.ACCEPT_QUEUE_WAIT_HIST.snapshot()
    s = socket.create_connection(("127.0.0.1", front.port), timeout=10)
    s.sendall(_request_bytes("GET", "/_test/ping"))
    _read_response(s)
    s.close()
    counts1, _ = metrics.ACCEPT_QUEUE_WAIT_HIST.snapshot()
    assert sum(counts1) > sum(counts0)


# -- scale smoke -------------------------------------------------------------

@pytest.mark.slow
def test_10k_idle_connections_near_zero_threads(db, setting):
    """The tentpole target: 10k idle sockets at near-zero thread count
    — RSS growth < 10 KB/connection, zero per-connection threads on
    the HTTP tier (loopback; scaled down only if the fd rlimit is
    low)."""
    import gc
    import resource

    from serenedb_tpu.obs.resources import read_rss_bytes

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = 10_000
    need = want * 2 + 512        # client + server end per connection
    if soft < need:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(hard, need), hard))
            soft, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
        except (ValueError, OSError):
            pass
    n = min(want, max(0, (soft - 512) // 2))
    if n < 1000:
        pytest.skip(f"fd rlimit too low for an idle-fleet smoke "
                    f"(soft={soft})")
    setting("serene_max_connections", 0)
    setting("serene_idle_conn_timeout_s", 0.0)
    srv = HttpServer(db, port=0)
    srv.start()
    socks = []
    try:
        # settle: one request warms the route/executor path
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s.sendall(_request_bytes("GET", "/_test/ping"))
        _read_response(s)
        s.close()
        gc.collect()
        threads0 = threading.active_count()
        rss0 = read_rss_bytes()
        for _ in range(n):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.connect(("127.0.0.1", srv.port))
            socks.append(s)
        deadline = time.time() + 120
        while time.time() < deadline and \
                metrics.CONNECTIONS_OPEN.value < n:
            time.sleep(0.2)
        assert metrics.CONNECTIONS_OPEN.value >= n
        gc.collect()
        rss1 = read_rss_bytes()
        per_conn = (rss1 - rss0) / n
        assert per_conn < 10 * 1024, \
            f"{per_conn:.0f} B/connection idle RSS (target < 10 KiB)"
        # zero per-connection threads: the fleet added NO threads
        assert threading.active_count() == threads0, \
            (threads0, threading.active_count())
        # and the fleet still serves: a request through the pile works
        q = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        q.sendall(_request_bytes("GET", "/_test/ping"))
        status, _, body, _ = _read_response(q)
        q.close()
        assert status == 200 and body == b'{"ok": true}'
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        srv.stop()
