"""The trace reduction on a small recorded trace: busy union, idle share,
top operations, and the attribution of idle gaps to host events."""

import json
import os

import pytest

from benchmark.harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _planes():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return json.load(f)["planes"]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]


def test_busy_idle_and_gap_attribution():
    r = trace_reduce.reduce(_planes())
    # window: host event 0 .. 1000 ns; ops: [100,300) + [250,400) overlap
    # -> [100,400); [600,700) -> busy 400 ns of 1000
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["device_planes"] == 1 and r["n_device_events"] == 3
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(350e-9)]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # gaps: [0,100) and [700,1000) under "serve", [400,600) under "parse"
    assert gaps["serve"] == pytest.approx(400e-9)
    assert gaps["parse"] == pytest.approx(200e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_whole_program_lines_are_not_counted_as_operations():
    planes = _planes()
    dev = [p for p in planes if p["name"].startswith("/device:TPU")][0]
    assert any(ln["name"] == "XLA Modules" for ln in dev["lines"])
    assert trace_reduce.reduce(planes)["busy_s"] == pytest.approx(400e-9)


def test_no_device_plane_reads_nothing():
    host_only = [p for p in _planes()
                 if not p["name"].startswith("/device:")]
    r = trace_reduce.reduce(host_only)
    assert r["device_planes"] == 0 and r["busy_s"] == 0.0
