"""PG wire protocol tests with a minimal raw-socket client (no driver deps —
the reference tests this with real drivers; a raw client checks framing)."""

import asyncio
import socket
import struct

import pytest

from serenedb_tpu.engine import Database
from serenedb_tpu.server.pgwire import PgServer


class RawPg:
    def __init__(self, port, user="tester", password=None, tls=False,
                 database=None):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=15)
        self.buf = b""
        if tls:
            import ssl
            self.sock.sendall(struct.pack("!II", 8, 80877103))  # SSLRequest
            resp = self.sock.recv(1)
            assert resp == b"S", f"server declined TLS: {resp!r}"
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE   # self-signed test certs
            self.sock = ctx.wrap_socket(self.sock)
        params = f"user\x00{user}\x00".encode()
        if database is not None:
            params += f"database\x00{database}\x00".encode()
        params += b"\x00"
        body = struct.pack("!I", 196608) + params
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self.params = {}
        self.backend_key = None
        scram_cont = scram_verify = None
        while True:
            kind, payload = self.read_msg()
            if kind == b"R":
                (code,) = struct.unpack("!I", payload[:4])
                if code == 3:
                    assert password is not None, "server demands password"
                    pw = password.encode() + b"\x00"
                    self.send(b"p", pw)
                elif code == 10:   # AuthenticationSASL → SCRAM-SHA-256
                    assert password is not None, "server demands password"
                    from serenedb_tpu.scram import client_exchange
                    mechs = payload[4:].split(b"\x00")
                    assert b"SCRAM-SHA-256" in mechs
                    first, scram_cont, scram_verify = client_exchange(
                        password)
                    init = first.encode()
                    self.send(b"p", b"SCRAM-SHA-256\x00" +
                              struct.pack("!i", len(init)) + init)
                elif code == 11:   # SASLContinue
                    final = scram_cont(payload[4:].decode())
                    self.send(b"p", final.encode())
                elif code == 12:   # SASLFinal
                    assert scram_verify(payload[4:].decode()), \
                        "server signature mismatch"
                elif code == 0:
                    pass
                else:
                    raise AssertionError(f"unexpected auth {code}")
            elif kind == b"S":
                k, v = payload.split(b"\x00")[:2]
                self.params[k.decode()] = v.decode()
            elif kind == b"K":
                self.backend_key = struct.unpack("!II", payload)
            elif kind == b"Z":
                self.status = payload
                return
            elif kind == b"E":
                raise AssertionError(f"error in startup: {payload}")

    def send(self, kind, payload=b""):
        self.sock.sendall(kind + struct.pack("!I", len(payload) + 4) + payload)

    def read_msg(self):
        while len(self.buf) < 5:
            data = self.sock.recv(65536)
            if not data:
                raise ConnectionError("closed")
            self.buf += data
        kind = self.buf[:1]
        (ln,) = struct.unpack("!I", self.buf[1:5])
        while len(self.buf) < 1 + ln:
            self.buf += self.sock.recv(65536)
        payload = self.buf[5:1 + ln]
        self.buf = self.buf[1 + ln:]
        return kind, payload

    def query(self, sql):
        """Simple query; returns (columns, rows, tags, errors)."""
        self.send(b"Q", sql.encode() + b"\x00")
        cols, rows, tags, errs = [], [], [], []
        while True:
            kind, payload = self.read_msg()
            if kind == b"T":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                cols = []
                for _ in range(n):
                    end = payload.index(b"\x00", off)
                    cols.append(payload[off:end].decode())
                    off = end + 1 + 18
            elif kind == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                row = []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif kind == b"C":
                tags.append(payload[:-1].decode())
            elif kind == b"E":
                errs.append(_parse_err(payload))
            elif kind == b"Z":
                self.status = payload
                return cols, rows, tags, errs

    def extended(self, sql, params=()):
        """Parse/Bind/Describe/Execute/Sync round."""
        self.send(b"P", b"\x00" + sql.encode() + b"\x00" + b"\x00\x00")
        parts = [b"\x00", b"\x00", struct.pack("!H", 0),
                 struct.pack("!H", len(params))]
        for p in params:
            if p is None:
                parts.append(struct.pack("!i", -1))
            else:
                enc = str(p).encode()
                parts.append(struct.pack("!i", len(enc)) + enc)
        parts.append(struct.pack("!H", 0))
        self.send(b"B", b"".join(parts))
        self.send(b"D", b"P\x00")
        self.send(b"E", b"\x00" + struct.pack("!I", 0))
        self.send(b"S")
        cols, rows, tags, errs = [], [], [], []
        while True:
            kind, payload = self.read_msg()
            if kind == b"T":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                for _ in range(n):
                    end = payload.index(b"\x00", off)
                    cols.append(payload[off:end].decode())
                    off = end + 1 + 18
            elif kind == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                row = []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif kind == b"C":
                tags.append(payload[:-1].decode())
            elif kind == b"E":
                errs.append(_parse_err(payload))
            elif kind == b"Z":
                return cols, rows, tags, errs

    def close(self):
        try:
            self.send(b"X")
        except OSError:
            pass
        self.sock.close()


def _parse_err(payload):
    fields = {}
    for part in payload.split(b"\x00"):
        if part:
            fields[chr(part[0])] = part[1:].decode()
    return fields


@pytest.fixture(scope="module")
def server():
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE t (a INT, s TEXT)")
    c.execute("INSERT INTO t VALUES (1, 'x'), (2, NULL)")
    srv = PgServer(db, port=0)
    loop = asyncio.new_event_loop()
    import threading

    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def go():
            await srv.start()
            started.set()
            await asyncio.Event().wait()
        try:
            loop.run_until_complete(go())
        except RuntimeError:
            pass
    t = threading.Thread(target=run, daemon=True)
    t.start()
    started.wait(10)
    yield srv
    loop.call_soon_threadsafe(loop.stop)


def test_startup_and_simple_query(server):
    c = RawPg(server.port)
    assert c.params.get("server_encoding") == "UTF8"
    cols, rows, tags, errs = c.query("SELECT a, s FROM t ORDER BY a")
    assert cols == ["a", "s"]
    assert rows == [("1", "x"), ("2", None)]
    assert tags == ["SELECT 2"]
    assert not errs
    c.close()


def test_multi_statement_and_tags(server):
    c = RawPg(server.port)
    cols, rows, tags, errs = c.query("SELECT 1; SELECT 2;")
    assert tags == ["SELECT 1", "SELECT 1"]
    assert rows == [("1",), ("2",)]
    c.close()


def test_error_has_sqlstate(server):
    c = RawPg(server.port)
    _, _, _, errs = c.query("SELECT * FROM missing_table")
    assert errs and errs[0]["C"] == "42P01"
    # session still usable after error
    _, rows, _, _ = c.query("SELECT 42")
    assert rows == [("42",)]
    c.close()


def test_extended_protocol_with_params(server):
    c = RawPg(server.port)
    cols, rows, tags, errs = c.extended(
        "SELECT a, s FROM t WHERE a > $1 ORDER BY a", (0,))
    assert not errs, errs
    assert rows == [("1", "x"), ("2", None)]
    cols, rows, tags, errs = c.extended(
        "SELECT a FROM t WHERE s = $1", ("x",))
    assert rows == [("1",)]
    c.close()


def test_extended_error_then_sync_recovers(server):
    c = RawPg(server.port)
    _, _, _, errs = c.extended("SELECT * FROM nope")
    assert errs and errs[0]["C"] == "42P01"
    _, rows, _, errs = c.extended("SELECT 7")
    assert rows == [("7",)] and not errs
    c.close()


def test_password_auth():
    db = Database()
    srv = PgServer(db, port=0, password="sesame")
    loop = asyncio.new_event_loop()
    import threading
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def go():
            await srv.start()
            started.set()
            await asyncio.Event().wait()
        try:
            loop.run_until_complete(go())
        except RuntimeError:
            pass
    threading.Thread(target=run, daemon=True).start()
    started.wait(10)
    c = RawPg(srv.port, password="sesame")
    _, rows, _, _ = c.query("SELECT 1")
    assert rows == [("1",)]
    c.close()
    with pytest.raises(AssertionError):
        RawPg(srv.port, password=None)
    loop.call_soon_threadsafe(loop.stop)


def test_transaction_status_bytes(server):
    c = RawPg(server.port)
    c.query("BEGIN")
    assert c.status == b"T"
    c.query("SELECT broken syntax here from")
    assert c.status == b"E"   # failed transaction block
    _, _, _, errs = c.query("SELECT 1")
    assert errs and errs[0]["C"] == "25P02"
    c.query("ROLLBACK")
    assert c.status == b"I"
    c.close()


def test_copy_from_stdin_and_to_stdout(server):
    c = RawPg(server.port)
    c.query("CREATE TABLE cp (a INT, s TEXT)")
    # COPY FROM STDIN: expect CopyInResponse then send data
    c.send(b"Q", b"COPY cp FROM STDIN\x00")
    kind, payload = c.read_msg()
    assert kind == b"G", kind
    c.send(b"d", b"1\thello\n2\t\\N\n")
    c.send(b"c")
    tags = []
    while True:
        kind, payload = c.read_msg()
        if kind == b"C":
            tags.append(payload[:-1].decode())
        elif kind == b"Z":
            break
    assert tags == ["COPY 2"]
    _, rows, _, _ = c.query("SELECT a, s FROM cp ORDER BY a")
    assert rows == [("1", "hello"), ("2", None)]
    # COPY TO STDOUT
    c.send(b"Q", b"COPY cp TO STDOUT\x00")
    kind, payload = c.read_msg()
    assert kind == b"H"
    data = []
    while True:
        kind, payload = c.read_msg()
        if kind == b"d":
            data.append(payload)
        elif kind == b"c":
            pass
        elif kind == b"C":
            assert payload[:-1] == b"COPY 2"
        elif kind == b"Z":
            break
    assert b"".join(data) == b"1\thello\n2\t\\N\n"
    c.query("DROP TABLE cp")
    c.close()


def test_copy_literal_backslash_n_roundtrip(server):
    c = RawPg(server.port)
    c.query("CREATE TABLE cpb (s TEXT)")
    c.send(b"Q", b"COPY cpb FROM STDIN\x00")
    k, _ = c.read_msg(); assert k == b"G"
    # literal backslash-N is escaped as \\N — must NOT become NULL
    c.send(b"d", b"\\\\N\n\\N\nplain\n")
    c.send(b"c")
    while True:
        k, p = c.read_msg()
        if k == b"Z":
            break
    _, rows, _, _ = c.query(
        "SELECT s IS NULL, coalesce(s, '<null>') FROM cpb")
    got = sorted(rows)
    assert ("f", "\\N") in got       # the literal two-char value survives
    assert ("t", "<null>") in got    # the bare marker is NULL
    assert ("f", "plain") in got
    c.query("DROP TABLE cpb")
    c.close()


def test_copy_rejected_in_aborted_txn(server):
    c = RawPg(server.port)
    c.query("CREATE TABLE cpt (a INT)")
    c.query("BEGIN")
    c.query("SELECT broken from syntax here")
    c.send(b"Q", b"COPY cpt FROM STDIN\x00")
    errs = []
    while True:
        k, p = c.read_msg()
        if k == b"E":
            errs.append(_parse_err(p))
        elif k == b"G":
            raise AssertionError("CopyInResponse in aborted txn")
        elif k == b"Z":
            break
    assert errs and errs[0]["C"] == "25P02"
    c.query("ROLLBACK")
    assert c.query("SELECT count(*) FROM cpt")[1] == [("0",)]
    c.query("DROP TABLE cpt")
    c.close()


def test_portal_row_paging_with_suspension(server):
    c = RawPg(server.port)
    c.query("CREATE TABLE pg_page (n INT)")
    c.query("INSERT INTO pg_page VALUES (1),(2),(3),(4),(5)")
    # Parse + Bind once, Execute with max_rows=2 repeatedly
    c.send(b"P", b"cur\x00SELECT n FROM pg_page ORDER BY n\x00\x00\x00")
    c.send(b"B", b"p1\x00cur\x00" + struct.pack("!HHH", 0, 0, 0))
    rows, suspended, complete = [], 0, 0
    for _ in range(4):
        c.send(b"E", b"p1\x00" + struct.pack("!I", 2))
        c.send(b"H")
        while True:
            kind, payload = c.read_msg()
            if kind == b"D":
                (ncols,) = struct.unpack("!H", payload[:2])
                (ln,) = struct.unpack("!i", payload[2:6])
                rows.append(payload[6:6 + ln].decode())
            elif kind == b"s":
                suspended += 1
                break
            elif kind == b"C":
                complete += 1
                break
            elif kind in (b"1", b"2"):
                continue
        if complete:
            break
    c.send(b"S")
    while c.read_msg()[0] != b"Z":
        pass
    assert rows == ["1", "2", "3", "4", "5"]
    assert suspended == 2 and complete == 1
    c.query("DROP TABLE pg_page")
    c.close()


class TestBinaryResults:
    def _extended_raw(self, pg, sql, rfmts, params=()):
        """Parse/Bind(with result formats)/Execute/Sync; raw value bytes."""
        pg.send(b"P", b"\x00" + sql.encode() + b"\x00" + b"\x00\x00")
        parts = [b"\x00", b"\x00", struct.pack("!H", 0),
                 struct.pack("!H", len(params))]
        for p in params:
            enc = str(p).encode()
            parts.append(struct.pack("!i", len(enc)) + enc)
        parts.append(struct.pack("!H", len(rfmts)))
        parts.extend(struct.pack("!h", f) for f in rfmts)
        pg.send(b"B", b"".join(parts))
        pg.send(b"D", b"P\x00")
        pg.send(b"E", b"\x00" + struct.pack("!I", 0))
        pg.send(b"S")
        rows, errs, desc_fmts = [], [], []
        while True:
            kind, payload = pg.read_msg()
            if kind == b"T":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                for _ in range(n):
                    end = payload.index(b"\x00", off)
                    off = end + 1 + 18
                    desc_fmts.append(struct.unpack(
                        "!h", payload[off - 2:off])[0])
            elif kind == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off = 2
                row = []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln])
                        off += ln
                rows.append(row)
            elif kind == b"E":
                errs.append(_parse_err(payload))
            elif kind == b"Z":
                return rows, errs, desc_fmts

    def test_all_binary(self, server):
        pg = RawPg(server.port)
        pg.query("CREATE TABLE bin (b BOOL, i INT, l BIGINT, d DOUBLE, "
                 "s TEXT)")
        pg.query("INSERT INTO bin VALUES (true, -7, 5000000000, 2.5, 'hi'),"
                 " (false, NULL, 1, -0.5, NULL)")
        rows, errs, fmts = self._extended_raw(
            pg, "SELECT b, i, l, d, s FROM bin ORDER BY i NULLS LAST", [1])
        assert not errs and fmts == [1, 1, 1, 1, 1]
        assert rows[0][0] == b"\x01"
        assert struct.unpack("!i", rows[0][1])[0] == -7
        assert struct.unpack("!q", rows[0][2])[0] == 5000000000
        assert struct.unpack("!d", rows[0][3])[0] == 2.5
        assert rows[0][4] == b"hi"
        assert rows[1][0] == b"\x00" and rows[1][1] is None \
            and rows[1][4] is None
        pg.close()

    def test_per_column_formats(self, server):
        pg = RawPg(server.port)
        rows, errs, fmts = self._extended_raw(
            pg, "SELECT 300, 'x', 1.5", [1, 0, 1])
        assert not errs and fmts == [1, 0, 1]
        assert struct.unpack("!i", rows[0][0])[0] == 300
        assert rows[0][1] == b"x"
        assert struct.unpack("!d", rows[0][2])[0] == 1.5
        pg.close()

    def test_binary_timestamp_date(self, server):
        pg = RawPg(server.port)
        rows, errs, _ = self._extended_raw(
            pg, "SELECT TIMESTAMP '2000-01-01 00:00:01', "
                "DATE '2000-01-02'", [1])
        assert not errs
        assert struct.unpack("!q", rows[0][0])[0] == 1_000_000
        assert struct.unpack("!i", rows[0][1])[0] == 1
        pg.close()

    def test_invalid_format_code(self, server):
        pg = RawPg(server.port)
        rows, errs, _ = self._extended_raw(pg, "SELECT 1", [7])
        assert errs and errs[0]["C"] == "08P01"
        pg.close()

    def test_text_default_unchanged(self, server):
        pg = RawPg(server.port)
        rows, errs, fmts = self._extended_raw(pg, "SELECT 42", [])
        assert not errs and fmts == [0] and rows[0][0] == b"42"
        pg.close()


def test_truncated_bind_result_formats(server):
    # declared 3 format codes, sent 1: must answer 08P01, not kill the
    # session
    pg = RawPg(server.port)
    pg.send(b"P", b"\x00SELECT 1\x00\x00\x00")
    body = (b"\x00\x00" + struct.pack("!H", 0) + struct.pack("!H", 0) +
            struct.pack("!H", 3) + struct.pack("!h", 1))
    pg.send(b"B", body)
    pg.send(b"S")
    errs = []
    while True:
        kind, payload = pg.read_msg()
        if kind == b"E":
            errs.append(_parse_err(payload))
        elif kind == b"Z":
            break
    assert errs and errs[0]["C"] == "08P01"
    cols, rows, tags, qerrs = pg.query("SELECT 7")
    assert rows == [("7",)] and not qerrs
    pg.close()


def test_scram_auth_role_password(server):
    pg0 = RawPg(server.port)
    pg0.query("CREATE ROLE scrammy LOGIN PASSWORD 'tops3cret'")
    # correct password over SCRAM
    pg = RawPg(server.port, user="scrammy", password="tops3cret")
    cols, rows, tags, errs = pg.query("SELECT 1")
    assert rows == [("1",)] and not errs
    pg.close()
    # wrong password rejected
    with pytest.raises(AssertionError):
        RawPg(server.port, user="scrammy", password="wrong")
    pg0.query("DROP ROLE scrammy")
    pg0.close()


def _run_pg_server(db, password=None, **kwargs):
    """Start a PgServer via its real start() in a thread; returns
    (srv, stop_fn) — same bootstrap the module `server` fixture uses."""
    import threading
    srv = PgServer(db, port=0, password=password, **kwargs)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def go():
            await srv.start()
            started.set()
            await asyncio.Event().wait()
        try:
            loop.run_until_complete(go())
        except RuntimeError:
            pass
    t = threading.Thread(target=run, daemon=True)
    t.start()
    started.wait(10)
    return srv, (lambda: loop.call_soon_threadsafe(loop.stop))


def test_scram_server_password():
    srv, stop = _run_pg_server(Database(), password="gatekeeper")
    try:
        pg = RawPg(srv.port, user="serene", password="gatekeeper")
        cols, rows, tags, errs = pg.query("SELECT 2")
        assert rows == [("2",)]
        pg.close()
        with pytest.raises(AssertionError):
            RawPg(srv.port, user="serene", password="nope")
    finally:
        stop()


def test_scram_saslprep_unicode_password():
    # U+00A0 no-break space must normalize to a plain space on both sides
    # (RFC 4013 / pg_saslprep) so drivers that normalize interoperate
    srv, stop = _run_pg_server(Database(), password="pa\u00a0ss")
    try:
        pg = RawPg(srv.port, user="serene", password="pa ss")
        assert pg.query("SELECT 5")[1] == [("5",)]
        pg.close()
        with pytest.raises(AssertionError):
            RawPg(srv.port, user="serene", password="pass")
    finally:
        stop()


def test_scram_login_after_password_rotation():
    db = Database()
    srv, stop = _run_pg_server(db)
    try:
        admin = RawPg(srv.port, user="serene")
        admin.query("CREATE ROLE rotor LOGIN PASSWORD 'first'")
        pg = RawPg(srv.port, user="rotor", password="first")
        pg.close()
        admin.query("ALTER ROLE rotor PASSWORD 'second'")
        with pytest.raises(AssertionError):
            RawPg(srv.port, user="rotor", password="first")
        pg = RawPg(srv.port, user="rotor", password="second")
        assert pg.query("SELECT 1")[1] == [("1",)]
        pg.close()
        admin.close()
    finally:
        stop()


def test_listen_notify(server):
    listener = RawPg(server.port)
    sender = RawPg(server.port)
    assert listener.query("LISTEN events")[2] == ["LISTEN"]
    assert sender.query("NOTIFY events, 'payload-1'")[2] == ["NOTIFY"]
    # notification arrives at the listener's next statement boundary
    listener.send(b"Q", b"SELECT 1\x00")
    got = []
    while True:
        kind, payload = listener.read_msg()
        if kind == b"A":
            pid = struct.unpack("!I", payload[:4])[0]
            channel, load = payload[4:-1].split(b"\x00")[:2]
            got.append((pid, channel.decode(), load.decode()))
        elif kind == b"Z":
            break
    assert got == [(sender.backend_key[0], "events", "payload-1")]
    # UNLISTEN stops delivery
    listener.query("UNLISTEN events")
    sender.query("NOTIFY events, 'after'")
    kinds = []
    listener.send(b"Q", b"SELECT 1\x00")
    while True:
        kind, _ = listener.read_msg()
        kinds.append(kind)
        if kind == b"Z":
            break
    assert b"A" not in kinds
    # notify with no listeners is a no-op; self-notify works
    sender.query("NOTIFY nowhere")
    sender.query("LISTEN selfchan")
    # self-notify is delivered at the NOTIFY's own statement boundary
    sender.send(b"Q", b"NOTIFY selfchan, 'me'\x00")
    got = []
    while True:
        kind, payload = sender.read_msg()
        if kind == b"A":
            got.append(payload[4:-1].split(b"\x00")[1].decode())
        elif kind == b"Z":
            break
    assert got == ["me"]
    listener.close()
    sender.close()


def test_notify_pushed_to_idle_listener(server):
    import select
    lis, snd = RawPg(server.port), RawPg(server.port)
    lis.query("LISTEN idlechan")
    snd.query("NOTIFY idlechan, 'wake'")
    # listener sends NOTHING: the 'A' must arrive as an async push
    ready, _, _ = select.select([lis.sock], [], [], 5.0)
    assert ready, "no async NotificationResponse within 5s"
    kind, payload = lis.read_msg()
    assert kind == b"A"
    assert payload[4:-1].split(b"\x00")[:2] == [b"idlechan", b"wake"]
    lis.close()
    snd.close()


def test_notify_in_txn_is_transactional(server):
    lis, snd = RawPg(server.port), RawPg(server.port)
    lis.query("LISTEN txchan")
    snd.query("BEGIN")
    snd.query("NOTIFY txchan, 'rolled-back'")
    snd.query("ROLLBACK")
    snd.query("BEGIN")
    snd.query("NOTIFY txchan, 'committed'")
    snd.query("COMMIT")
    import select
    ready, _, _ = select.select([lis.sock], [], [], 5.0)
    assert ready
    kind, payload = lis.read_msg()
    assert kind == b"A"
    # only the committed txn's notification arrives
    assert payload[4:-1].split(b"\x00")[1] == b"committed"
    # nothing else pending
    ready, _, _ = select.select([lis.sock], [], [], 0.3)
    assert not ready
    lis.close()
    snd.close()


def test_returning_described_in_extended_protocol(server):
    pg = RawPg(server.port)
    pg.query("CREATE TABLE retd (a INT, b TEXT)")
    cols, rows, tags, errs = pg.extended(
        "INSERT INTO retd VALUES ($1, 'p') RETURNING a, b", ["5"])
    assert not errs
    assert cols == ["a", "b"]          # Describe produced RowDescription
    assert rows == [("5", "p")]
    pg.query("DROP TABLE retd")
    pg.close()


# -- streaming wire collector (reference: wire_collector.h:20-60) -----------

def test_streaming_select_flushes_per_batch():
    """A large SELECT must stream: multiple flushes (one per executor
    batch), not one materialized send."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.exec.tables import MemTable
    from serenedb_tpu.server import pgwire as pgwire_mod

    db = Database()
    n = 400_000   # > 3 executor batches of 2^17 rows
    batch = Batch.from_pydict({
        "id": Column.from_numpy(np.arange(n, dtype=np.int64))})
    db.schemas["main"].tables["big"] = MemTable("big", batch)
    srv, stop = _run_pg_server(db)
    flushes = []
    orig_flush = pgwire_mod.Writer.flush

    async def counting_flush(self):
        flushes.append(1)
        await orig_flush(self)
    pgwire_mod.Writer.flush = counting_flush
    try:
        pg = RawPg(srv.port)
        before = len(flushes)
        cols, rows, tags, errs = pg.query("SELECT id FROM big")
        assert len(rows) == n
        assert tags == [f"SELECT {n}"]
        # at least one flush per executor batch (4 batches for 400k rows)
        assert len(flushes) - before >= 4
        pg.close()
    finally:
        pgwire_mod.Writer.flush = orig_flush
        stop()


def test_streaming_select_midstream_error():
    """An error in a later batch arrives after earlier DataRows; the
    session stays usable (ErrorResponse then ReadyForQuery)."""
    import numpy as np

    from serenedb_tpu.columnar.column import Batch, Column
    from serenedb_tpu.exec.tables import MemTable

    db = Database()
    n = 300_000
    ids = np.arange(n, dtype=np.int64)
    batch = Batch.from_pydict({"id": Column.from_numpy(ids)})
    db.schemas["main"].tables["big2"] = MemTable("big2", batch)
    srv, stop = _run_pg_server(db)
    try:
        pg = RawPg(srv.port)
        # division by zero on a row in the third executor batch
        cols, rows, tags, errs = pg.query(
            "SELECT 100 / (id - 280000) FROM big2")
        assert errs, "expected a mid-stream error"
        assert len(rows) >= (1 << 17), "rows before the error must stream"
        assert not tags     # no CommandComplete after an error
        # session still alive
        assert pg.query("SELECT 5")[1] == [("5",)]
        pg.close()
    finally:
        stop()


class TestProxyProtocol:
    """HAProxy PROXY v1/v2 preface (reference: proxy_protocol.cpp)."""

    def _server(self, mode):
        import asyncio
        import threading

        from serenedb_tpu.engine import Database
        from serenedb_tpu.server.pgwire import PgServer
        db = Database(None)
        srv = PgServer(db, "127.0.0.1", 0, proxy_protocol=mode)
        loop = asyncio.new_event_loop()
        started = threading.Event()
        port = {}

        async def boot():
            server = await asyncio.start_server(
                lambda r, w: __import__(
                    "serenedb_tpu.server.pgwire",
                    fromlist=["PgSession"]).PgSession(srv, r, w).run(),
                "127.0.0.1", 0)
            port["p"] = server.sockets[0].getsockname()[1]
            started.set()
            async with server:
                await server.serve_forever()

        t = threading.Thread(target=lambda: loop.run_until_complete(boot()),
                             daemon=True)
        t.start()
        started.wait(10)
        return port["p"]

    def _query(self, port, sql, preface=b""):
        import socket
        import struct as st
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        if preface:
            s.sendall(preface)
        body = st.pack("!i", 196608) + b"user\x00u\x00database\x00d\x00\x00"
        s.sendall(st.pack("!i", len(body) + 4) + body)

        def read_msg():
            t = s.recv(1)
            if not t:
                raise ConnectionError("closed")
            ln = st.unpack("!i", s.recv(4))[0]
            p = b""
            while len(p) < ln - 4:
                p += s.recv(ln - 4 - len(p))
            return t, p

        while True:
            t, p = read_msg()
            if t == b"Z":
                break
        b2 = sql.encode() + b"\x00"
        s.sendall(b"Q" + st.pack("!i", len(b2) + 4) + b2)
        rows = []
        while True:
            t, p = read_msg()
            if t == b"D":
                rows.append(p)
            elif t == b"Z":
                s.close()
                return rows

    def test_v1_preface(self):
        port = self._server("optional")
        rows = self._query(port, "SELECT 1",
                           b"PROXY TCP4 10.1.2.3 10.0.0.1 5555 5432\r\n")
        assert len(rows) == 1

    def test_v2_preface(self):
        import struct as st
        port = self._server("optional")
        sig = b"\r\n\r\n\x00\r\nQUIT\n"
        addr = (bytes([10, 1, 2, 3]) + bytes([10, 0, 0, 1]) +
                st.pack("!HH", 5555, 5432))
        preface = sig + bytes([0x21, 0x11]) + st.pack("!H", len(addr)) + addr
        rows = self._query(port, "SELECT 1", preface=preface)
        assert len(rows) == 1

    def test_optional_without_preface(self):
        port = self._server("optional")
        assert len(self._query(port, "SELECT 1")) == 1

    def test_require_rejects_plain(self):
        import pytest
        port = self._server("require")
        with pytest.raises((ConnectionError, OSError)):
            self._query(port, "SELECT 1")


# -- one request, one timeline, from the socket (ISSUE 24) -------------------


def _entry_of(sql, after_id=0, timeout=5.0):
    """The flight-recorder entry of the request that carried `sql`. The
    request ends after its last byte went to the transport, so the client
    may have read the whole answer a moment before the entry is there."""
    import time

    from serenedb_tpu.obs.trace import FLIGHT
    deadline = time.monotonic() + timeout
    while True:
        hits = [e for e in FLIGHT.snapshot()
                if e["query"] == sql[:500] and e["trace_id"] > after_id]
        if hits:
            return hits[-1]
        assert time.monotonic() < deadline, f"no timeline for {sql!r}"
        time.sleep(0.005)


def _newest_trace_id():
    from serenedb_tpu.obs.trace import FLIGHT
    last = FLIGHT.last()
    return last["trace_id"] if last else 0


def _assert_request_timeline(entry):
    dur = entry["duration_ns"]
    stages = entry["stages"]
    assert sum(stages.values()) == dur          # integer ns, exactly
    cursor = 0
    for _name, b, e in entry["timeline"]:
        assert cursor <= b < e <= dur
        cursor = e
    by_id = {s["id"]: s for s in entry["spans"]}
    assert len(by_id) == len(entry["spans"])
    for s in entry["spans"]:
        if s["id"]:
            par = by_id[s["parent"]]
            assert par["begin_ns"] <= s["begin_ns"] and \
                s["end_ns"] <= par["end_ns"], (par, s)
    staged = [s for s in entry["spans"] if s["cat"] == "stage"]
    names = {s["name"] for s in staged}
    # from the receipt of the message to the last flush, one trace id
    assert {"fd_parse", "fd_queue", "plan", "fd_encode"} <= names, names
    first = min(staged, key=lambda s: s["begin_ns"])
    last = max(staged, key=lambda s: s["end_ns"])
    assert first["name"] == "fd_parse" and first["begin_ns"] < 200_000
    assert last["name"] == "fd_encode" and dur - last["end_ns"] < 5_000_000
    assert by_id[0]["args"]["trace_id"] == entry["trace_id"]
    return names


WIRE_STATEMENTS = [
    "SELECT a, s FROM t ORDER BY a",
    "SELECT count(*), sum(a) FROM t WHERE a > 0",
    "SELECT s, count(DISTINCT a + 1) FROM t GROUP BY s ORDER BY s",
]


@pytest.mark.parametrize("sql", WIRE_STATEMENTS)
@pytest.mark.parametrize("protocol", ["simple", "extended"])
def test_request_timeline_through_the_socket(server, sql, protocol):
    from serenedb_tpu.utils import metrics
    c = RawPg(server.port)
    c.query("SET serene_result_cache = off")
    mark = _newest_trace_id()
    req0 = metrics.REQUEST_LATENCY_HIST.snapshot()
    stmt0 = metrics.QUERY_LATENCY_HIST.snapshot()
    cols, rows, tags, errs = c.query(sql) if protocol == "simple" \
        else c.extended(sql)
    assert not errs and rows
    entry = _entry_of(sql, mark)
    c.close()
    names = _assert_request_timeline(entry)
    assert entry["error"] is None and entry["answered"] == "host"
    if "GROUP BY" in sql:
        assert "host_group" in names
    # exactly one request and one statement were observed, and the
    # request holds the statement
    req1 = metrics.REQUEST_LATENCY_HIST.snapshot()
    stmt1 = metrics.QUERY_LATENCY_HIST.snapshot()
    assert sum(req1[0]) - sum(req0[0]) == 1
    assert sum(stmt1[0]) - sum(stmt0[0]) == 1
    assert req1[1] - req0[1] == entry["duration_ns"]
    assert req1[1] - req0[1] >= stmt1[1] - stmt0[1]


def test_utility_statements_stay_untraced_over_the_wire(server):
    import time
    c = RawPg(server.port)
    mark = _newest_trace_id()
    c.query("SET serene_result_cache = off")
    c.query("BEGIN")
    c.query("COMMIT")
    c.extended("SET serene_workers = 1")
    cols, rows, tags, errs = c.query("SHOW serene_workers")
    assert rows == [("1",)]
    time.sleep(0.05)
    assert _newest_trace_id() == mark
    c.close()


def test_trace_off_session_has_no_timeline_and_the_same_answer(server):
    import time
    sql = "SELECT a, s FROM t WHERE a > 0 ORDER BY a"
    c = RawPg(server.port)
    on = c.query(sql)
    _entry_of(sql)
    c.query("SET serene_trace = off")
    mark = _newest_trace_id()
    off = c.query(sql)
    off_ext = c.extended(sql)
    time.sleep(0.05)
    assert _newest_trace_id() == mark
    assert on[1] == off[1] == off_ext[1] and on[2] == off[2]
    c.close()


def test_each_statement_of_one_message_is_a_request(server):
    c = RawPg(server.port)
    c.query("SET serene_result_cache = off")
    mark = _newest_trace_id()
    sql = "SELECT 1; SET serene_workers = 1; SELECT a FROM t ORDER BY a"
    cols, rows, tags, errs = c.query(sql)
    assert tags == ["SELECT 1", "SET", "SELECT 2"] and not errs
    c.close()
    import time

    from serenedb_tpu.obs.trace import FLIGHT
    deadline = time.monotonic() + 5.0
    while True:
        mine = [e for e in FLIGHT.snapshot()
                if e["query"] == sql and e["trace_id"] > mark]
        if len(mine) >= 2 or time.monotonic() > deadline:
            break
        time.sleep(0.005)
    assert len(mine) == 2                        # the SET is not traced
    last = mine[1]
    first = mine[0]
    # the first holds the message's parse; the second begins where the
    # first ended and holds the flush of ReadyForQuery
    assert "fd_parse" in first["stages"] and \
        "fd_parse" not in last["stages"]
    for e in mine:
        assert sum(e["stages"].values()) == e["duration_ns"]
        assert "plan" in e["stages"] and "fd_encode" in e["stages"]


def test_a_failed_statement_keeps_its_timeline(server):
    c = RawPg(server.port)
    mark = _newest_trace_id()
    sql = "SELECT a / 0 FROM t"
    _, _, _, errs = c.query(sql)
    assert errs
    entry = _entry_of(sql, mark)
    c.close()
    assert entry["error"] and entry["answered"] is None
    assert sum(entry["stages"].values()) == entry["duration_ns"]
    assert {"fd_parse", "plan", "fd_encode"} <= set(entry["stages"])


def test_a_suspended_portal_keeps_its_request_open(server):
    """Execute with a row budget suspends the portal mid-stream: the
    request stays open across Execute messages and ends with the one
    that drains it — one trace id, one timeline."""
    c = RawPg(server.port)
    mark = _newest_trace_id()
    sql = "SELECT a FROM t ORDER BY a"
    c.send(b"P", b"\x00" + sql.encode() + b"\x00" + b"\x00\x00")
    c.send(b"B", b"\x00\x00" + struct.pack("!HHH", 0, 0, 0))
    c.send(b"E", b"\x00" + struct.pack("!I", 1))      # one row, suspend
    c.send(b"H")
    kinds = []
    while not kinds or kinds[-1] != b"s":
        kinds.append(c.read_msg()[0])
    assert kinds.count(b"D") == 1
    import time
    time.sleep(0.05)
    assert _newest_trace_id() == mark                 # still open
    c.send(b"E", b"\x00" + struct.pack("!I", 0))      # the rest
    c.send(b"S")
    while c.read_msg()[0] != b"Z":
        pass
    entry = _entry_of(sql, mark)
    c.close()
    assert entry["error"] is None
    assert sum(entry["stages"].values()) == entry["duration_ns"]
    encodes = [s for s in entry["spans"] if s["name"] == "fd_encode"]
    assert len(encodes) >= 3
