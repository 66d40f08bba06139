"""The four per-layer metrics of the program call (`device_upload_pct`,
`device_enqueue_pct`, `enqueue_call_p50_ms`, `device_busy_per_call_ms`),
over recorded `/metrics` texts.

`data/metrics_text/pr35_{before,after}.txt` are two scrapes of a process
of the commit that added them (the per-statement series and the `#` lines left out), around
a window of twelve `_search` requests (`match`, three words, top-10 and
the exact total) over 600 generated passages, through `Router.handle`, CPU
backend: the times are not speeds. Every question went to the dense steps:
one program call a request, its three numpy operands committed first.
`pr24_{before,after}.txt` are an older commit's, which has neither
`StageDeviceUpload` nor `DeviceEnqueueCall`: over them the three metrics
that read those return NOTHING (never 0), which is what the driver's traced
run of a parent without them relies on.
"""

import json
import os

import pytest

from benchmark.harness import gauges
from benchmark.harness.metric_eval import Evaluator

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
NEW = ["device_upload_pct", "device_enqueue_pct", "enqueue_call_p50_ms",
       "device_busy_per_call_ms"]
CELLS = ["msmarco.search_c1", "wiki.game_c1", "msmarco.search_c32",
         "vectors.knn_c8"]
#: what the harness measures itself in a traced run (run.py: read_trace)
TRACED = {"window_s": 45.0, "trace_busy_s": 8.0, "trace_window_s": 20.0}


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
def test_metric_evaluates_over_the_recorded_window(name):
    v = Evaluator(_window("pr35"), dict(TRACED), _load).metric(name)
    assert v is not None
    assert (0 < v < 100) if name.endswith("_pct") else (0 < v < 60_000)


@pytest.mark.parametrize("name", NEW)
def test_metric_returns_nothing_where_its_histogram_is_empty(name):
    """An older program's window: `StageDeviceEnqueue` is there (since
    PR 24), the split and the per-call histogram are not."""
    v = Evaluator(_window("pr24"), dict(TRACED), _load).metric(name)
    if name == "device_enqueue_pct":
        assert v is not None and 0 < v < 100
    else:
        assert v is None


def test_busy_per_call_needs_the_trace():
    """`--trace 0` has no busy seconds: the metric is left out."""
    ev = Evaluator(_window("pr35"), {"window_s": 45.0}, _load)
    assert ev.metric("device_busy_per_call_ms") is None
    assert ev.metric("enqueue_call_p50_ms") is not None


def test_the_recorded_window_reads_what_was_sent():
    d = _window("pr35")
    ev = Evaluator(d, dict(TRACED), _load)
    h = d["hists"]
    calls = sum(h["serenedb_device_enqueue_call_seconds"]["counts"])
    # twelve requests, one dense step each
    assert calls == 12
    assert sum(h["serenedb_stage_device_upload_seconds"]["counts"]) == 12
    # the share the two stages take together is their sums' share
    pair = ev.metric("device_upload_pct") + ev.metric("device_enqueue_pct")
    total = h["serenedb_request_latency_seconds"]["sum"]
    assert pair == pytest.approx(
        100 * (h["serenedb_stage_device_upload_seconds"]["sum"] +
               h["serenedb_stage_device_enqueue_seconds"]["sum"]) / total)
    # a call is its upload plus its enqueue: the per-call histogram's sum
    # covers both stages' (and the few clock reads between them)
    both = h["serenedb_stage_device_upload_seconds"]["sum"] + \
        h["serenedb_stage_device_enqueue_seconds"]["sum"]
    assert h["serenedb_device_enqueue_call_seconds"]["sum"] >= both
    # busy share 0.4 over 12 calls in 45 s
    assert ev.metric("device_busy_per_call_ms") == pytest.approx(
        1000 * (8.0 / 20.0) / (12 / 45.0))
    # the nineteen stage histograms still cut the requests' time whole
    stages = [x for k, x in h.items() if k.startswith("serenedb_stage_")]
    assert len(stages) == 19
    assert sum(x["sum"] for x in stages) == pytest.approx(total, rel=1e-6)


def test_manifest_lists_the_four_for_the_four_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-4:]] == NEW
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == CELLS
        assert (m["source"], m["layer"], m["moves"], m["better"]) == \
            ("program_span", "device dispatch", "ops_per_s", "lower")
        assert m["unit"] == ("%" if name.endswith("_pct") else "ms")
        assert _load(name)["name"] == name
