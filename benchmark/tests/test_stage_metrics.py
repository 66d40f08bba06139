"""The per-layer metrics that read the request timeline's histograms and
counters (`RequestLatency`, the `Stage*` histograms, `StatementsAnswered*`,
`DeviceCache{Hits,Misses}`), over recorded `/metrics` texts.

`data/metrics_text/pr24_{before,after}.txt` are two scrapes of a server of
the commit that added them, around a small window (four rounds of a device
aggregate, a host COUNT(DISTINCT) group-by and a filtered count over a
hits-shaped table, over pgwire, CPU backend: the times are not speeds);
`parent_{before,after}.txt` are the same two scrapes of its parent commit,
which has none of these series. Over the first every metric evaluates;
over the second every metric returns NOTHING (never 0), which is what the
driver's traced run of the parent relies on.
"""

import json
import os

import pytest

from benchmark.harness import gauges
from benchmark.harness.metric_eval import Evaluator

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
NEW = ["request_p50_ms", "plan_p50_ms", "device_prepare_p50_ms",
       "device_finalize_p50_ms", "device_wait_p50_ms",
       "device_cache_hit_pct", "host_operator_pct", "stmt_device_pct",
       "unattributed_pct", "unattributed_pct.light"]


def _load(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def _window(which):
    snaps = []
    for edge in ("before", "after"):
        path = os.path.join(HERE, "data", "metrics_text",
                            f"{which}_{edge}.txt")
        with open(path) as f:
            snap = gauges.parse_metrics(f.read())
        snap.update(programs={}, dispatches=0)
        snaps.append(snap)
    return gauges.delta(*snaps)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_evaluates_over_a_recorded_window(name):
    v = Evaluator(_window("pr24"), {}, _load).metric(name)
    assert v is not None
    if name.endswith("_ms"):
        assert 0 < v < 60_000
    else:
        assert 0 <= v <= 100


@pytest.mark.parametrize("name", NEW)
def test_new_metric_returns_nothing_over_the_parents_window(name):
    assert Evaluator(_window("parent"), {}, _load).metric(name) is None


def test_the_recorded_window_reads_what_was_sent():
    d = _window("pr24")
    ev = Evaluator(d, {}, _load)
    # 4 rounds x (2 device statements + 1 host statement)
    assert ev.metric("stmt_device_pct") == pytest.approx(100 * 8 / 12)
    # every column the device statements asked for was resident
    assert ev.metric("device_cache_hit_pct") == pytest.approx(100.0)
    assert ev.metric("unattributed_pct.light") == \
        ev.metric("unattributed_pct")
    # the stage histograms cut the requests' time without remainder:
    # their sums add up to RequestLatency's (ns exactly in the server;
    # the text holds seconds as decimals)
    stages = [h for k, h in d["hists"].items()
              if k.startswith("serenedb_stage_")]
    assert len(stages) >= 12
    total = d["hists"]["serenedb_request_latency_seconds"]["sum"]
    assert sum(h["sum"] for h in stages) == pytest.approx(total, rel=1e-6)
    # one StageOther and one RequestLatency observation per request
    n = sum(d["hists"]["serenedb_request_latency_seconds"]["counts"])
    assert n == 12
    assert sum(d["hists"]["serenedb_stage_other_seconds"]["counts"]) == n
