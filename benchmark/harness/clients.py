"""Raw wire clients: PG v3 simple query and one-shot HTTP JSON.

Copied from `chip_smoke.py` (PR 21) so that the yardstick does not move
when the smoke does; no driver is installed here and none is wanted — a
latency read by these clients is the time between the last byte sent and
the last byte of the answer read, nothing else.
"""

from __future__ import annotations

import http.client
import json
import socket
import struct


class WireError(Exception):
    """The server refused, errored or hung up: the operation FAILED
    (counted under `failed`, never dropped)."""


def data_row(payload: bytes) -> tuple:
    """The text fields of one DataRow message (None for SQL NULL)."""
    (n,) = struct.unpack("!H", payload[:2])
    off, row = 2, []
    for _ in range(n):
        (ln,) = struct.unpack("!i", payload[off:off + 4])
        off += 4
        if ln < 0:
            row.append(None)
        else:
            row.append(payload[off:off + ln].decode())
            off += ln
    return tuple(row)


class Pg:
    """PG v3 simple-query client over one socket."""

    def __init__(self, port: int, timeout: float = 600.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""
        body = struct.pack("!I", 196608) + b"user\x00bench\x00\x00"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        while True:
            kind, payload = self._msg()
            if kind == b"E":
                raise WireError(f"pg startup refused: {payload!r}")
            if kind == b"R":
                (code,) = struct.unpack("!I", payload[:4])
                if code != 0:
                    raise WireError(f"pg demands auth method {code}")
            if kind == b"Z":
                return

    def _msg(self):
        while len(self.buf) < 5:
            self._fill()
        kind = self.buf[:1]
        (ln,) = struct.unpack("!I", self.buf[1:5])
        while len(self.buf) < 1 + ln:
            self._fill()
        payload = self.buf[5:1 + ln]
        self.buf = self.buf[1 + ln:]
        return kind, payload

    def _fill(self):
        data = self.sock.recv(1 << 20)
        if not data:
            raise WireError("pg connection closed by server")
        self.buf += data

    def query(self, sql: str) -> list[tuple]:
        """Rows of the LAST result set as text tuples; raises WireError
        on any ErrorResponse."""
        q = sql.encode()
        self.sock.sendall(b"Q" + struct.pack("!I", len(q) + 5) + q + b"\x00")
        rows: list[tuple] = []
        err = None
        while True:
            kind, payload = self._msg()
            if kind == b"T":
                rows = []
            elif kind == b"D":
                rows.append(data_row(payload))
            elif kind == b"E":
                err = payload.replace(b"\x00", b" ").decode(errors="replace")
            elif kind == b"Z":
                if err is not None:
                    raise WireError(f"SQL error for {sql[:160]!r}: {err}")
                return rows

    def close(self):
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        except OSError:
            pass
        self.sock.close()


def http_once(port: int, method: str, path: str, body=None, raw=False,
              timeout: float = 600.0):
    """One HTTP request on a connection of its own (the harness's reads of
    `/metrics` and `/device`; the load goes through loadgen.py)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None
        if body is not None:
            data = body if isinstance(body, (bytes, str)) else json.dumps(body)
        try:
            conn.request(method, path, data,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            payload = r.read()
        except (OSError, http.client.HTTPException) as e:
            raise WireError(f"HTTP {method} {path}: {type(e).__name__}: {e}")
    finally:
        conn.close()
    if r.status >= 300:
        raise WireError(f"HTTP {method} {path} -> {r.status}: "
                        f"{payload[:300]!r}")
    return payload.decode() if raw else json.loads(payload)
