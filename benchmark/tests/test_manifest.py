"""`BENCHMARK.json` keeps to the contract's letters, and every file a
cell names is there."""

import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
        names += [w["name"], w["traffic"]]
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in manifest[section]]
        assert len(got) == len(set(got))
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_every_named_file_exists_and_cells_are_covered(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in cells.values():
        conf = configs[w["config"]]
        used.add(w["config"])
        assert conf["file"].startswith(manifest["paths"][0] + "/")
        with open(os.path.join(ROOT, conf["file"])) as f:
            cfg = json.load(f)
        for key in conf["reduced"]:
            assert key in cfg and key in cfg["reduced"], key
        assert os.path.exists(os.path.join(
            bench, "datasets", cfg["dataset"] + ".py"))
        with open(os.path.join(bench, "traffic",
                               w["traffic"] + ".json")) as f:
            tr = json.load(f)
        with open(os.path.join(bench, "queries",
                               tr["queries"] + ".json")) as f:
            qs = json.load(f)
        assert os.path.exists(os.path.join(
            bench, "references", qs["reference"] + ".py"))
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(bench, "metrics",
                                           m["name"] + ".json"))
        for c in m.get("workloads", []):
            assert c in cells
    for m in manifest["end_to_end"]:
        for c in m.get("workloads", []):
            assert c in cells
    for name in cells:
        e2e = [m for m in manifest["end_to_end"]
               if name in m.get("workloads", [name])]
        layer = [m for m in manifest["per_layer"]
                 if name in m.get("workloads", [name])]
        assert {"setup_s"} < {m["name"] for m in e2e} and layer
        reported = {m["name"] for m in e2e}
        assert all(m["moves"] in reported for m in layer)
    for dirpath, _, files in os.walk(bench):
        for fn in files:
            if "__pycache__" not in dirpath:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", fn), fn
