"""The general load generator: N connections, closed loop, ONE thread.

Every connection sends its next operation only when the last was
answered. One selector loop drives them all, so the load comes from one
process with one busy thread whatever the client count. An operation's
latency runs from just before its first byte is sent to just after the
last byte of its answer is read.

What to send comes from a source (`benchmark/sources/<kind>.py`), how to
speak from a protocol module (`benchmark/protocols/<name>.py`); this file
knows nothing about wires, tables, queries or metrics.
"""

from __future__ import annotations

import importlib
import selectors
import time

from .clients import WireError


def connect(traffic: dict, ports: dict, n: int = None) -> list:
    """`n` (default: the mix's `clients`) connections of the traffic
    file's `protocol` (`benchmark/protocols/<protocol>.py`), each with
    the mix's `session` statements run."""
    proto = importlib.import_module(
        f"benchmark.protocols.{traffic['protocol']}")
    n = int(traffic["clients"]) if n is None else n
    return [proto.Conn(ports[proto.PORT], list(traffic.get("session", [])))
            for _ in range(n)]


def closed_loop(conns, source, seconds: float,
                drain_s: float = 60.0) -> tuple[float, list[dict]]:
    """Drive `conns` closed-loop from `source` for `seconds`; then stop
    sending and wait up to `drain_s` for what is in flight. Returns
    (t0, ops): every operation SENT inside the window, as
    {"client", "key", "sent", "done", "ok", "answer" | "error"}.
    An operation never answered has ok=False and error="no answer"."""
    sel = selectors.DefaultSelector()
    inflight: dict[int, dict] = {}
    ops: list[dict] = []
    for i, c in enumerate(conns):
        c.sock.setblocking(False)
        sel.register(c.sock, selectors.EVENT_READ, i)

    def issue(i: int) -> None:
        key, payload = source.next_op(i)
        op = {"client": i, "key": key, "sent": time.monotonic()}
        conns[i].sock.setblocking(True)
        try:
            conns[i].send(payload)
        finally:
            conns[i].sock.setblocking(False)
        inflight[i] = op
        ops.append(op)

    t0 = time.monotonic()
    end = t0 + seconds
    for i in range(len(conns)):
        issue(i)
    hard_stop = end + drain_s
    while inflight:
        now = time.monotonic()
        if now >= hard_stop:
            break
        for skey, _ in sel.select(timeout=min(0.05, hard_stop - now)):
            i = skey.data
            if i not in inflight:
                continue
            op = inflight[i]
            try:
                data = conns[i].sock.recv(1 << 20)
                if not data:
                    raise WireError("connection closed by server")
                answer = conns[i].feed(data)
            except BlockingIOError:
                continue
            except (WireError, OSError, ValueError) as e:
                op.update(done=time.monotonic(), ok=False,
                          error=f"{type(e).__name__}: {e}"[:300])
                del inflight[i]
                if isinstance(e, WireError) and "closed" not in str(e) and \
                        time.monotonic() < end:
                    issue(i)        # the session survives an SQL error
                continue
            if answer is None:
                continue
            op.update(done=time.monotonic(), ok=True, answer=answer)
            del inflight[i]
            if op["done"] < end:
                issue(i)
    for op in inflight.values():
        op.update(done=time.monotonic(), ok=False, error="no answer")
    sel.close()
    for c in conns:
        c.sock.setblocking(True)
    return t0, ops
