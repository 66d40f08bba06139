#!/usr/bin/env python3
"""From a `jax.profiler` trace of the serened process to numbers.

    python3 benchmark/harness/trace_reduce.py <trace_dir> <out.json>

Runs as a process of its own AFTER the server has gone (it imports jax
only to read the `.xplane.pb`, under JAX_PLATFORMS=cpu, and never touches
a device). The reduction itself (`reduce`) works on plain lists, so the
tests check it on a small recorded trace:

  planes = [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops",
                        "events": [[name, start_ns, duration_ns], ...]}]},
            {"name": "/host:CPU", "lines": [...]}]

- busy: the union of the intervals in which an operation ran on a device
  (the device plane's `XLA Ops` line; where a trace has no such line,
  every line of the device plane except whole-program and step lines),
  averaged over the device planes;
- window: from the first to the last event of any plane, one clock;
- device_ops: the operations that took most device time, by name;
- idle_gaps: the longest gaps between device operations, each given to
  the host event that covers most of it (host planes' thread lines),
  summed by that event's name.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
OP_LINE = "XLA Ops"
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
           "Framework Name Scope", "Source code", "Launch Stats")
TOP = 10
GAPS_NAMED = 200


def union(intervals):
    """Merged [start, end) intervals, ascending."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _op_lines(plane: dict) -> list:
    named = [ln for ln in plane["lines"] if ln["name"] == OP_LINE]
    if named:
        return named
    return [ln for ln in plane["lines"] if ln["name"] not in NOT_OPS]


def _covering_host_event(host_events, starts, gap):
    """Name of the host event that overlaps `gap` the longest; among
    those that cover it alike, the shortest (the innermost span)."""
    import bisect
    s, e = gap
    best, best_key = "(no host event)", (0, 0)
    i = bisect.bisect_left(starts, e)
    # events are sorted by start; look back over those that start before
    # the gap ends (bounded: host spans longer than 2 s are rare)
    for j in range(i - 1, max(-1, i - 4000), -1):
        name, hs, hd = host_events[j]
        cover = min(e, hs + hd) - max(s, hs)
        if cover > 0 and (cover, -hd) > best_key:
            best, best_key = name, (cover, -hd)
    return best


def reduce(planes: list) -> dict:
    dev_planes = [p for p in planes if p["name"].startswith(DEVICE_PREFIXES)]
    t_min, t_max = None, None
    for p in planes:
        for ln in p["lines"]:
            for _, s, d in ln["events"]:
                t_min = s if t_min is None or s < t_min else t_min
                t_max = s + d if t_max is None or s + d > t_max else t_max
    if t_min is None:
        return {"window_s": 0.0, "busy_s": 0.0, "device_planes": 0,
                "device_ops": [], "idle_gaps": [], "n_device_events": 0}
    host_events = sorted(
        ((name, s, d) for p in planes
         if not p["name"].startswith(DEVICE_PREFIXES)
         for ln in p["lines"] for name, s, d in ln["events"] if d > 0),
        key=lambda ev: ev[1])
    host_starts = [ev[1] for ev in host_events]
    busy_ns, by_op, gaps_by, n_ev = 0, {}, {}, 0
    for p in dev_planes:
        ivs = []
        for ln in _op_lines(p):
            for name, s, d in ln["events"]:
                ivs.append((s, s + d))
                by_op[name] = by_op.get(name, 0) + d
                n_ev += 1
        merged = union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [t_min] + [x for iv in merged for x in iv] + [t_max]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        for g in gaps[:GAPS_NAMED]:
            name = _covering_host_event(host_events, host_starts, g)
            gaps_by[name] = gaps_by.get(name, 0) + (g[1] - g[0])
        rest = sum(e - s for s, e in gaps[GAPS_NAMED:])
        if rest:
            gaps_by["(shorter gaps, unnamed)"] = \
                gaps_by.get("(shorter gaps, unnamed)", 0) + rest
    n_dev = max(len(dev_planes), 1)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (t_max - t_min) / 1e9,
        "busy_s": busy_ns / 1e9 / n_dev,
        "device_planes": len(dev_planes),
        "n_device_events": n_ev,
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in top],
        "idle_gaps": [[k, v / 1e9 / n_dev] for k, v in top_gaps],
        "plane_lines": {p["name"]: [ln["name"] for ln in p["lines"]][:40]
                        for p in planes},
    }


def read_xplane(trace_dir: str) -> list:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise SystemExit(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    from jax.profiler import ProfileData
    data = ProfileData.from_file(paths[0])
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            lines.append({"name": ln.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in ln.events]})
        planes.append({"name": p.name, "lines": lines})
    return planes


def main(argv) -> int:
    trace_dir, out = argv
    planes = read_xplane(trace_dir)
    with open(out, "w") as f:
        json.dump(reduce(planes), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
