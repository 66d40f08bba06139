"""The server's own counts, read from the client's side of the socket:
`GET /metrics` (Prometheus text: every gauge, every histogram) and
`GET /device` (compile ledger per program family, per-device dispatches).
A per-layer metric reads DELTAS of these over the window.
"""

from __future__ import annotations

import re

from .clients import http_once

_CAMEL = re.compile(r"(?<=[a-z0-9])(?=[A-Z])")
_LINE = re.compile(r'^(serenedb_[a-z0-9_]+?)(?:\{le="([^"]+)"\})? (\S+)$')


def prom_name(gauge: str) -> str:
    """`SearchBatchQueries` -> `serenedb_search_batch_queries` (the
    program's own rule, obs/export.py)."""
    return "serenedb_" + _CAMEL.sub("_", gauge).lower()


def parse_metrics(text: str) -> dict:
    """{"gauges": {prom_name: value},
        "hists": {prom_base: {"bounds": [...s], "cum": [...], "sum": s}}}"""
    gauges, hists = {}, {}
    for line in text.splitlines():
        if not line or line[0] == "#" or 'queryid="' in line:
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, le, val = m.group(1), m.group(2), float(m.group(3))
        if le is not None:
            h = hists.setdefault(name[:-len("_bucket")],
                                 {"bounds": [], "cum": [], "sum": 0.0})
            if le != "+Inf":
                h["bounds"].append(float(le))
            h["cum"].append(val)
        else:
            gauges[name] = val
    for base, h in hists.items():
        h["sum"] = gauges.pop(base + "_sum", 0.0)
        gauges.pop(base + "_count", None)
    return {"gauges": gauges, "hists": hists}


def snapshot(http_port: int) -> dict:
    snap = parse_metrics(http_once(http_port, "GET", "/metrics", raw=True))
    dev = http_once(http_port, "GET", "/device")
    snap["programs"] = {p["family"]: p for p in dev["programs"]}
    snap["dispatches"] = sum(d["dispatches"] for d in dev["devices"])
    snap["fused_declines"] = dev.get("fused_declines", {})
    return snap


def delta(before: dict, after: dict) -> dict:
    """What moved between two snapshots."""
    g = {k: v - before["gauges"].get(k, 0.0)
         for k, v in after["gauges"].items()}
    hists = {}
    for base, h in after["hists"].items():
        b = before["hists"].get(base)
        cum = [a - (b["cum"][i] if b else 0.0)
               for i, a in enumerate(h["cum"])]
        counts = [cum[0]] + [cum[i] - cum[i - 1] for i in range(1, len(cum))]
        hists[base] = {"bounds": h["bounds"], "counts": counts,
                       "sum": h["sum"] - (b["sum"] if b else 0.0)}
    compiles = sum(p["compiles"] for p in after["programs"].values()) - \
        sum(p["compiles"] for p in before["programs"].values())
    return {"gauges": g, "hists": hists,
            "ledger_compiles": compiles,
            "ledger_dispatches": after["dispatches"] - before["dispatches"]}
