"""Wire protocol `pgwire` of a traffic file: one PG v3 session, simple
queries. A protocol is a module of its own under `benchmark/protocols/`,
found by the traffic file's `protocol`; it gives `PORT` (which of the
server's ports it connects to) and `Conn(port, session)` with `sock`,
`send(payload)`, `feed(bytes) -> answer | None` and `close()`.
"""

from __future__ import annotations

import struct

from ..harness.clients import Pg, WireError, data_row

PORT = "pg"


class Conn:
    """A pgwire session (startup + session statements done blocking),
    then non-blocking simple queries."""

    def __init__(self, port: int, session: list[str]):
        self._pg = Pg(port)
        for stmt in session:
            self._pg.query(stmt)
        self.sock = self._pg.sock
        self.buf = b""
        self._rows: list = []
        self._err = None

    def send(self, payload: str) -> None:
        q = payload.encode()
        self.sock.sendall(b"Q" + struct.pack("!I", len(q) + 5) + q + b"\x00")

    def feed(self, data: bytes):
        """The answer (list of text tuples) once ReadyForQuery arrived,
        else None; raises WireError for an ErrorResponse."""
        self.buf += data
        while len(self.buf) >= 5:
            (ln,) = struct.unpack("!I", self.buf[1:5])
            if len(self.buf) < 1 + ln:
                return None
            kind, payload = self.buf[:1], self.buf[5:1 + ln]
            self.buf = self.buf[1 + ln:]
            if kind == b"T":
                self._rows = []
            elif kind == b"D":
                self._rows.append(data_row(payload))
            elif kind == b"E":
                self._err = payload.replace(b"\x00", b" ").decode(
                    errors="replace")
            elif kind == b"Z":
                rows, err = self._rows, self._err
                self._rows, self._err = [], None
                if err is not None:
                    raise WireError(err)
                return rows
        return None

    def close(self):
        self._pg.close()
