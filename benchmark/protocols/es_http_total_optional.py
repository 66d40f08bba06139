"""Wire protocol `es_http_total_optional` of a traffic file: `es_http`
(HTTP/1.1 keep-alive, one JSON `_search` request at a time per
connection) for answers that may leave `hits.total` out, as
Elasticsearch does under `track_total_hits: false`. `es_http` reads
`hits.total` of every answer and cannot carry such a one; the framing
is its own, only the reduction differs: {"total": exact count or None,
"relation" or None, "hits": [(id, score)]}. A status of 300 or more is
a failed operation (`WireError`).
"""

from __future__ import annotations

import json

from ..harness.clients import WireError
from . import es_http

PORT = es_http.PORT


class Conn(es_http.Conn):
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


def reduce_search(resp: dict) -> dict:
    hits = resp["hits"]
    total = hits.get("total") or {}
    return {"total": total.get("value"), "relation": total.get("relation"),
            "hits": [(h["_id"], h["_score"]) for h in hits["hits"]]}
