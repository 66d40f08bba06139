"""The command and a whole run, here on the CPU backend:

- the command itself refuses to give a result without a TPU, and in a
  directory that holds only BENCHMARK.json and the benchmark's own files;
- a later PR's throw-away cell (a configuration, a traffic mix, a query
  set and a gauge-sourced metric, ALL added as files plus manifest
  entries, no file of the harness edited) runs and reports its metric;
- with the timed path broken underneath (faulty_child.py alters answers
  where they are produced) `correct` comes out false; a statement that
  errors gives no result.

These skip the harness's look for a chip (`require_tpu=False`) and drive
the rest of a run at a tiny size.
"""

import argparse
import json
import os
import shutil
import subprocess

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TIMEOUT = 600


def _command(cwd, env_extra=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cmd = json.load(f)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        cmd + ["--workload", "hits.analyst_c1", "--seed", "3000000019",
               "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=TIMEOUT)


def test_no_result_without_a_tpu():
    r = _command(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_no_result_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _command(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def _args(workload, seed=5, seconds=1.5, trace=0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, control=0)


@pytest.fixture()
def later_pr(tmp_path):
    """What a later PR would add for a cell of its own: files only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "clickbench-hits.json").read_text())
    cfg.update(name="hits-tiny", rows=40_000)
    (bench / "configs" / "hits-tiny.json").write_text(json.dumps(cfg))
    (bench / "queries" / "two_counts.json").write_text(json.dumps({
        "kind": "sql_statements", "reference": "sql_numpy",
        "grid": {"r": [1, 2, 3]},
        "statements": [
            {"id": "n", "sql": 'SELECT COUNT(*), SUM("ResolutionWidth") '
                               'FROM hits WHERE "RegionID" = {r}',
             "ref": {"where": [["RegionID", "=", "{r}"]],
                     "select": [["count", "*"],
                                ["sum", "ResolutionWidth"]]}}]}))
    (bench / "traffic" / "pair_c2.json").write_text(json.dumps({
        "protocol": "pgwire", "clients": 2, "loop": "closed", "session": [],
        "queries": "two_counts",
        "pick": {"statement": "uniform", "grid": {"r": {"zipf": 1.0}}},
        "warmup": {"each": 1}, "check": "all", "trace_s": 1}))
    (bench / "metrics" / "stmts_per_op.json").write_text(json.dumps({
        "name": "stmts_per_op",
        "expr": 'hist_count("QueryLatency") / ops'}))
    manifest["configs"].append({
        "name": "hits-tiny", "source": "test",
        "file": "benchmark/configs/hits-tiny.json",
        "reduced": ["rows"], "why": "throw-away"})
    manifest["workloads"].append({
        "name": "tiny.pair_c2", "config": "hits-tiny", "traffic": "pair_c2",
        "chips": 1, "why": "throw-away"})
    for m in manifest["end_to_end"]:       # the new cell reports them all
        if "workloads" in m:
            m["workloads"].append("tiny.pair_c2")
    manifest["per_layer"].append({
        "name": "stmts_per_op", "unit": "stmts", "better": "lower",
        "source": "program_counter", "layer": "front door",
        "moves": "ops_per_s", "workloads": ["tiny.pair_c2"]})
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(root), str(path)


def test_a_later_pr_adds_a_cell_and_a_metric_with_files_only(later_pr):
    from benchmark import run as bench_run
    root, manifest = later_pr
    res = bench_run.run(_args("tiny.pair_c2", trace=1), require_tpu=False,
                        manifest_path=manifest, root=root)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 4 and res["failed"] == 0
    assert res["metrics"]["stmts_per_op"]["value"] == pytest.approx(1.0)
    # no chip here: the trace holds no device plane, so the device-trace
    # metrics return nothing and are LEFT OUT (never reported as 0)
    assert "device_idle_pct" not in res["metrics"]
    assert list(res)[-1] == "compared"
    res0 = bench_run.run(_args("tiny.pair_c2", seed=2**31 + 11),
                         require_tpu=False, manifest_path=manifest,
                         root=root)
    assert set(res0["metrics"]) == {"ops_per_s", "latency_p50_ms", "setup_s"}
    assert res0["correct"] is True and "breakdown" not in res0


def test_an_altered_answer_makes_correct_false(later_pr):
    from benchmark import run as bench_run
    root, manifest = later_pr
    res = bench_run.run(_args("tiny.pair_c2"), require_tpu=False,
                        manifest_path=manifest, root=root,
                        child=os.path.join(HERE, "faulty_child.py"))
    assert res["correct"] is False
    assert res["compared"]["wrong_answers"]["value"] > 0


def test_a_statement_that_fails_makes_correct_false(later_pr):
    from benchmark import run as bench_run
    root, manifest = later_pr
    qpath = os.path.join(root, "benchmark", "queries", "two_counts.json")
    with open(qpath) as f:
        qset = json.load(f)
    qset["statements"][0]["sql"] = qset["statements"][0]["sql"].replace(
        "FROM hits", "FROM no_such_table")
    with open(qpath, "w") as f:
        json.dump(qset, f)
    # the warm-up meets the error first: no result at all
    with pytest.raises(bench_run.BenchError):
        bench_run.run(_args("tiny.pair_c2"), require_tpu=False,
                      manifest_path=manifest, root=root)
