"""Per-layer metrics, each a file of its own (`benchmark/metrics/<name>.json`)
that declares its source as data:

    {"expr": "100 * ResultCacheHits / (ResultCacheHits + ResultCacheMisses)"}

An expression is arithmetic (+ - * /, parentheses, numbers) over:
  - `CamelCase` names: the window delta of that gauge of the server's
    `/metrics` (e.g. `SearchBatchQueries`);
  - `hist_q("QueryLatency", 0.5)`, `hist_sum("QueryLatency")`,
    `hist_count(...)`: quantile (seconds, interpolated inside the bucket),
    sum (seconds) and count of a histogram's window delta;
  - `lower_case` names: values the harness measured itself — client side
    (`ops`, `window_s`, `latency_p50_ms`, `client_latency_sum_s`, ...),
    the trace reduction (`trace_busy_s`, `trace_window_s`, ...), the
    workload's own counts (`ops_offloaded`, `scan_bytes_traced`, ...) and
    the device's peaks (`peak_hbm_bytes_per_s`, `peak_bf16_flops`);
  - `metric("other_name")`: another per-layer metric of this run.

A name that is not there, a histogram without samples or a division by
zero makes the metric return NOTHING, and the harness leaves it out of
the result line: it never reports 0 for something it could not read.
A new metric over a new counter therefore needs no new code.
"""

from __future__ import annotations

import ast
import operator

from . import gauges as _g
from .stats import hist_quantile


class Missing(Exception):
    pass


_BIN = {ast.Add: operator.add, ast.Sub: operator.sub,
        ast.Mult: operator.mul, ast.Div: operator.truediv}


class Evaluator:
    def __init__(self, gauge_delta: dict, values: dict, load_metric):
        self.d = gauge_delta          # gauges.delta(...) or None
        self.values = values          # harness-measured lower_case names
        self.load_metric = load_metric
        self._busy: set = set()
        self._done: dict = {}

    def metric(self, name: str):
        if name in self._done:
            return self._done[name]
        if name in self._busy:
            raise ValueError(f"metric {name!r} is defined through itself")
        self._busy.add(name)
        try:
            spec = self.load_metric(name)
            try:
                v = float(self._eval(ast.parse(spec["expr"],
                                               mode="eval").body))
            except (Missing, ZeroDivisionError):
                v = None
        finally:
            self._busy.discard(name)
        self._done[name] = v
        return v

    def _hist(self, name: str) -> dict:
        if self.d is None:
            raise Missing(name)
        h = self.d["hists"].get(_g.prom_name(name) + "_seconds") or \
            self.d["hists"].get(_g.prom_name(name))
        if h is None or sum(h["counts"]) <= 0:
            raise Missing(name)
        return h

    def _call(self, fn: str, args: list):
        if fn == "metric":
            v = self.metric(args[0])
            if v is None:
                raise Missing(args[0])
            return v
        h = self._hist(args[0])
        if fn == "hist_q":
            return hist_quantile(h["bounds"], h["counts"], float(args[1]))
        if fn == "hist_sum":
            return h["sum"]
        if fn == "hist_count":
            return sum(h["counts"])
        raise ValueError(f"unknown function {fn!r}")

    def _eval(self, node):
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, (int, float, str)):
            return node.value
        if isinstance(node, ast.BinOp) and type(node.op) in _BIN:
            return _BIN[type(node.op)](self._eval(node.left),
                                       self._eval(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -self._eval(node.operand)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and not node.keywords:
            return self._call(node.func.id,
                              [self._eval(a) for a in node.args])
        if isinstance(node, ast.Name):
            name = node.id
            if name[0].isupper():
                if self.d is None:
                    raise Missing(name)
                v = self.d["gauges"].get(_g.prom_name(name))
            else:
                v = self.values.get(name)
            if v is None:
                raise Missing(name)
            return v
        raise ValueError(f"not allowed in a metric expression: "
                         f"{ast.dump(node)[:80]}")
