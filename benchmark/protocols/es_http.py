"""Wire protocol `es_http` of a traffic file: HTTP/1.1 keep-alive to the
server's HTTP port, one JSON request at a time per connection. A payload
is (path, JSON body text); the answer of a `_search` is reduced to what
is compared: {"total": exact count, "relation", "hits": [(id, score)]}.
A status of 300 or more is a failed operation (`WireError`).
"""

from __future__ import annotations

import json
import socket

from ..harness.clients import WireError

PORT = "http"


class Conn:
    def __init__(self, port: int, session: list):
        if session:
            raise WireError("es_http has no session statements")
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=600.0)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def send(self, payload) -> None:
        path, body = payload
        data = body.encode()
        self.sock.sendall(
            (f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
             "Content-Type: application/json\r\n"
             f"Content-Length: {len(data)}\r\n\r\n").encode() + data)

    def feed(self, data: bytes):
        """The reduced answer once the whole response arrived, else
        None; raises WireError for a status of 300 or more."""
        self.buf += data
        end = self.buf.find(b"\r\n\r\n")
        if end < 0:
            return None
        head = self.buf[:end].decode("latin-1").split("\r\n")
        status = int(head[0].split()[1])
        headers = {k.strip().lower(): v.strip() for k, v in
                   (ln.split(":", 1) for ln in head[1:] if ":" in ln)}
        if "content-length" not in headers:
            raise WireError(f"HTTP response without Content-Length: "
                            f"{head[0]!r}")
        total = end + 4 + int(headers["content-length"])
        if len(self.buf) < total:
            return None
        body, self.buf = self.buf[end + 4:total], self.buf[total:]
        if status >= 300:
            raise WireError(f"HTTP {status}: {body[:300]!r}")
        return reduce_search(json.loads(body))

    def close(self):
        self.sock.close()


def reduce_search(resp: dict) -> dict:
    hits = resp["hits"]
    return {"total": hits["total"]["value"],
            "relation": hits["total"]["relation"],
            "hits": [(h["_id"], h["_score"]) for h in hits["hits"]]}
