"""The general traffic generator: a traffic file + the query set it names
+ the seed give the operations each client sends. Parameters are data
(`benchmark/traffic/*.json`, `benchmark/queries/*.json`); what turns a
kind of query set into operations is `benchmark/sources/<kind>.py`.

Traffic file keys:
  protocol   module under `benchmark/protocols/` ("pgwire")
  clients    connections, each a closed loop
  session    statements every connection runs once after connecting
  queries    name of the query-set file (benchmark/queries/<name>.json)
  pick       how a client chooses its next operation (see the source)
  warmup     {"each": n}  every distinct operation n times, serially
  check      "all": every answer of the window is compared
  trace_s    seconds of the window a --trace 1 run traces

Query-set file keys: `kind` (module under `benchmark/sources/`),
`reference` (module under `benchmark/references/`), and what the kind
reads (`statements`, `grid`, ...).
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def load_named(kind_dir: str, name: str, bench_dir: str = BENCH) -> dict:
    """`<bench_dir>/<kind_dir>/<name>.json`: how every traffic mix, query
    set and per-layer metric is found by its name."""
    path = os.path.join(bench_dir, kind_dir, name + ".json")
    with open(path) as f:
        return json.load(f)


def make_source(qset: dict, traffic: dict, dataset: dict, seed: int):
    mod = importlib.import_module(f"benchmark.sources.{qset['kind']}")
    return mod.Source(qset, traffic, dataset, seed)
