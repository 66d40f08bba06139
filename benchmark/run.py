#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

One run of one cell of `BENCHMARK.json`, on the machine it is started on:

1. This process stays OFF jax. It starts ONE child (`harness/serve_child.py`
   = `serenedb_tpu.serened.main` unchanged + a control thread), makes the
   data from `--seed` while the child boots, and refuses to go on unless
   the child's ready line says `platform=tpu` with enough chips.
2. Load over pgwire, warm up the cell's own statement shapes: all of that
   is `setup_s`.
3. Measure for `--seconds`: the cell's traffic, closed loop, from one
   thread. With `--trace 1` the child traces part of the window with
   `jax.profiler`.
4. Read the server's counters and the device's memory peak, stop the
   server, THEN run the plain reference over what the clients read in the
   window, and (traced run) reduce the trace in a process of its own.
5. Print each number compared beside its limit (stderr), and as the last
   line of stdout ONE JSON object: correct, attempted, failed, metrics,
   device [, breakdown], ..., compared.

Everything a cell is made of is data found by name: see README.md here.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import correctness, gauges, loadgen, stats  # noqa: E402
from benchmark.harness import traffic as traffic_mod              # noqa: E402
from benchmark.harness import workbytes                           # noqa: E402
from benchmark.harness.clients import Pg, WireError               # noqa: E402
from benchmark.harness.metric_eval import Evaluator               # noqa: E402
from benchmark.harness.server import Server, ServerError          # noqa: E402


class BenchError(Exception):
    """The run cannot give a result: non-zero exit, no result line."""


def note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# -- the manifest and the files it names ---------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with every file it names resolved."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        self.manifest = manifest
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.bench_dir = os.path.join(root, manifest["paths"][0])
        conf = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(root, conf["file"]))
        self.traffic = self.named("traffic", self.entry["traffic"])
        self.queries = self.named("queries", self.traffic["queries"])

    def named(self, kind: str, name: str) -> dict:
        try:
            return traffic_mod.load_named(kind, name, self.bench_dir)
        except FileNotFoundError as e:
            raise BenchError(f"{kind} {name!r}: {e}")

    def metrics(self, section: str) -> list[dict]:
        """The metrics of `end_to_end` / `per_layer` this cell reports."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]


# -- phases --------------------------------------------------------------------


def load_data(srv: Server, dataset: dict) -> None:
    pg = Pg(srv.pg_port)
    try:
        for stmt in dataset["load"]:
            t0 = time.monotonic()
            pg.query(stmt)
            note(f"  {stmt[:60]}… {time.monotonic() - t0:.1f}s")
        sql, want = dataset["count"]
        got = int(pg.query(sql)[0][0])
        if got != want:
            raise BenchError(f"{sql} gave {got}, generated {want}")
    finally:
        pg.close()


def _dispatched(before: dict, after: dict) -> dict:
    """What one statement sent to the device, by the server's own counts:
    `batches` (the `DeviceOffloads` gauge) and `dispatches` (the ledger's
    per-device dispatches, `/device`)."""
    d = gauges.delta(before, after)
    return {"batches": d["gauges"].get(gauges.prom_name("DeviceOffloads"),
                                       0.0),
            "dispatches": float(d["ledger_dispatches"])}


def warm_up(cell: Cell, srv: Server, source) -> dict:
    """Every distinct operation of the window `warmup.each` times,
    serially, on a connection with the mix's session settings. Returns
    {key: what its last execution sent to the device (`_dispatched`)}."""
    conn = loadgen.connect(cell.traffic, srv.ports, 1)[0]
    rounds = int(cell.traffic["warmup"]["each"])
    profile: dict = {}
    try:
        for rnd in range(rounds):
            last = rnd == rounds - 1
            for key, payload in source.distinct_ops():
                before = gauges.snapshot(srv.http_port) if last else None
                t0 = time.monotonic()
                conn.send(payload)
                answer = None
                while answer is None:
                    data = conn.sock.recv(1 << 20)
                    if not data:
                        raise BenchError("server closed the warm-up "
                                         "connection")
                    answer = conn.feed(data)
                if last:
                    profile[key] = _dispatched(
                        before, gauges.snapshot(srv.http_port))
                if time.monotonic() - t0 > 5:
                    note(f"  warm-up {key}: {time.monotonic() - t0:.1f}s")
    finally:
        conn.close()
    return profile


class Tracer(threading.Thread):
    """Asks the child for a profiler trace of part of the window."""

    def __init__(self, srv: Server, trace_dir: str, start_after: float,
                 seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.srv, self.dir = srv, trace_dir
        self.start_after, self.seconds = start_after, seconds
        self.span = None
        self.error = None

    def run(self):
        try:
            time.sleep(self.start_after)
            self.srv.control(f"trace_start {self.dir}")
            t0 = time.monotonic()
            time.sleep(self.seconds)
            t1 = time.monotonic()
            rep = self.srv.control("trace_stop", timeout_s=280)
            self.span = (t0, t1)
            self.traced_s = rep["traced_s"]
        except Exception as e:  # noqa: BLE001 — re-raised by the run
            self.error = e


def reduce_trace(trace_dir: str, out_path: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
         trace_dir, out_path], env=env, cwd=ROOT, timeout=200,
        capture_output=True, text=True)
    if r.returncode != 0:
        raise BenchError(f"trace reduction failed:\n{r.stderr[-2000:]}")
    return load_json(out_path)


def read_trace(work: str, tracer: Tracer, device: dict,
               values: dict) -> dict:
    """Reduce the trace; put busy / window seconds into `device` and
    `values`; return the `breakdown`."""
    red = reduce_trace(os.path.join(work, "trace"),
                       os.path.join(work, "trace_reduced.json"))
    note(f"trace: {red['n_device_events']} device events on "
         f"{red['device_planes']} plane(s); lines: "
         f"{json.dumps(red['plane_lines'])[:1500]}")
    # the traced span by the child's own clock, from start_trace's return
    # to stop_trace's call: the trace's events need not reach to its edges
    # (a host-only statement leaves none)
    window_s = max(tracer.traced_s, red["window_s"])
    if red["device_planes"] and red["busy_s"] > 0:
        values["trace_busy_s"] = red["busy_s"]
        values["trace_window_s"] = window_s
    device["busy_s"], device["window_s"] = red["busy_s"], window_s
    gaps = red["idle_gaps"]
    if window_s > red["window_s"]:
        gaps = sorted(gaps + [["(before the first or after the last traced "
                               "event)", window_s - red["window_s"]]],
                      key=lambda kv: -kv[1])[:10]
    return {"device_ops": red["device_ops"], "idle_gaps": gaps}


def load_peaks(kind: str) -> dict:
    peaks = load_json(os.path.join(HERE, "harness", "peaks.json"))
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


# -- one run -------------------------------------------------------------------


def run(args, require_tpu: bool = True, child: str = None,
        manifest_path: str = None, root: str = ROOT,
        overrides: dict = None) -> dict:
    """One run. The keyword arguments are for the tests under tests/ only
    (no chip there: a tiny size through `overrides`, a faulty child, a
    throw-away manifest); the command never passes them."""
    if require_tpu and "jax" in sys.modules:
        # a parent that has touched jax holds the chip the child needs
        raise BenchError("the benchmark's parent must stay off jax")
    manifest = load_json(manifest_path or os.path.join(root,
                                                       "BENCHMARK.json"))
    cell = Cell(manifest, args.workload, root)
    cell.config.update(overrides or {})
    cfg, traffic = cell.config, cell.traffic
    seed, seconds = int(args.seed), float(args.seconds)
    work = os.path.join(ROOT, ".bench_work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    srv = Server(os.path.join(work, "datadir"),
                 os.path.join(work, "serened.log"),
                 dict(cfg.get("server_env", {})),
                 **({"child": child} if child else {}))
    tracer = None
    try:
        t_setup = time.monotonic()
        srv.launch()
        gen = importlib.import_module(f"benchmark.datasets.{cfg['dataset']}")
        dataset = gen.generate(cfg, seed, work)
        note(f"data generated ({time.monotonic() - t_setup:.1f}s)")
        srv.wait_ready()
        note(f"server ready ({time.monotonic() - t_setup:.1f}s): "
             f"{srv.backend}")
        chips = int(cell.entry["chips"])
        if require_tpu and (srv.backend["platform"] != "tpu"
                            or srv.backend["count"] < chips):
            raise BenchError(
                f"the cell needs {chips} TPU chip(s); serened initialised "
                f"{srv.backend['platform']!r} x {srv.backend['count']}")
        load_data(srv, dataset)
        note(f"loaded ({time.monotonic() - t_setup:.1f}s)")
        source = traffic_mod.make_source(cell.queries, traffic, dataset,
                                         seed)
        profile = warm_up(cell, srv, source)
        conns = loadgen.connect(traffic, srv.ports)
        snap0 = gauges.snapshot(srv.http_port)
        built0 = srv.control("programs")
        setup_s = time.monotonic() - t_setup
        note(f"warm ({setup_s:.1f}s); window of {seconds:g}s")

        if args.trace:
            trace_s = min(float(traffic.get("trace_s", 6)),
                          max(seconds - 2.0, 0.5))
            tracer = Tracer(srv, os.path.join(work, "trace"), 1.0, trace_s)
            tracer.start()
        t0, ops = loadgen.closed_loop(conns, source, seconds)
        if tracer is not None:
            tracer.join(timeout=300)
            if tracer.error is not None or tracer.span is None:
                raise BenchError(f"tracing failed: {tracer.error}")
        snap1 = gauges.snapshot(srv.http_port)
        built1 = srv.control("programs")
        mem = srv.control("memstats")
        for c in conns:
            c.close()
        srv.stop()
    except BaseException as e:
        shutil.rmtree(work, ignore_errors=True)
        if isinstance(e, (ServerError, WireError)):
            raise BenchError(str(e))
        raise
    finally:
        srv.kill()

    # -- the server is gone; the plain reference runs now ----------------------
    t_ref = time.monotonic()
    checker = importlib.import_module(
        f"benchmark.references.{cell.queries['reference']}").check
    own = correctness.window_numbers(ops, built1["built"] - built0["built"])
    numbers, n_compared = checker(ops, source, dataset, seed,
                                  traffic["check"], cfg=cfg)
    correct, compared = correctness.judge({**numbers, **own}, cfg["limits"])
    control = None
    if args.control:
        c_numbers, _ = checker(ops, source, dataset, seed, traffic["check"],
                               control=True, cfg=cfg)
        control = correctness.judge({**c_numbers, **own}, cfg["limits"])
    ref_s = time.monotonic() - t_ref

    failed = sum(1 for o in ops if not o["ok"])
    w = stats.window_metrics(
        ops, t0, seconds,
        stats.cycle_weights(ops) if traffic["pick"] == "file_order" else None)
    values = {"ops": float(len(ops) - failed), "window_s": seconds,
              "setup_s": setup_s, "ops_per_s": w["ops_per_s"],
              "latency_p50_ms": w.get("latency_p50_ms"),
              "latency_p95_ms": w.get("latency_p95_ms"),
              "client_latency_sum_s": w.get("latency_sum_s")}
    # what the warm-up saw each statement dispatch, summed over the
    # window's operations: a metric sets the window's own counter beside it
    offloads = {k: p["batches"] > 0 or p["dispatches"] > 0
                for k, p in profile.items()}
    done = [o for o in ops if o["ok"]]
    values["ops_offloaded"] = float(sum(1 for o in done
                                        if offloads[o["key"]]))
    values["offload_batches_expected"] = sum(
        profile[o["key"]]["batches"] for o in done)
    values["ledger_dispatches_expected"] = sum(
        profile[o["key"]]["dispatches"] for o in done)
    if require_tpu:
        values.update({"peak_" + k: float(v)
                       for k, v in load_peaks(srv.backend["kind"]).items()
                       if isinstance(v, (int, float))})
    device = {"platform": srv.backend["platform"],
              "kind": srv.backend["kind"], "count": srv.backend["count"],
              "memory_peak_bytes": max(mem["peak_bytes_in_use"])}
    breakdown = None
    if args.trace:
        breakdown = read_trace(work, tracer, device, values)
        in_span = [o for o in done if offloads[o["key"]]
                   and tracer.span[0] <= o["done"] <= tracer.span[1]]
        if "columns" in dataset:
            widths = workbytes.table_widths(dataset["columns"])
            values["scan_bytes_traced"] = float(sum(
                workbytes.scan_bytes(source.by_key[o["key"]][1],
                                     int(cfg["rows"]), widths)
                for o in in_span))
    moved = gauges.delta(snap0, snap1)
    values["ledger_dispatches"] = float(moved["ledger_dispatches"])
    section = "per_layer" if args.trace else "end_to_end"
    ev = Evaluator(moved, values, lambda name: cell.named("metrics", name))
    metrics = {}
    for m in cell.metrics(section):
        v = values.get(m["name"]) if section == "end_to_end" else \
            ev.metric(m["name"])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(ops), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {
        "programs_built": built1["built"] - built0["built"],
        "programs_compiled": built1["compiled"] - built0["compiled"],
        "setup_programs_built": built0["built"],
        "setup_programs_compiled": built0["compiled"],
        "ledger_compiles": moved["ledger_compiles"],
        "answers_compared": n_compared, "reference_s": ref_s,
        "n_latencies": w["n_latencies"],
        "ops_per_s_plain": w["ops_per_s_plain"],
        "latency_p50_ms_plain": w.get("latency_p50_ms_plain")}
    off = sorted(k for k, v in offloads.items() if v)
    result["window"]["offloaded_statements"] = \
        f"{len(off)} of {len(profile)}: " + " ".join(off[:20])
    result["window"]["by_statement"] = stats.by_key(ops)
    if control is not None:
        result["control"] = {"correct": control[0], "compared": control[1]}
    errors = sorted({o.get("error", "") for o in ops if not o["ok"]})
    if errors:
        result["errors"] = errors[:5]
    result["compared"] = compared
    shutil.rmtree(work, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the control (the reference in lower "
                         "precision put in the program's place); the "
                         "result is unchanged")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        print(f"benchmark: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    if "control" in result:
        print("control: " + json.dumps(result["control"]), file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
