#!/usr/bin/env python3
"""chip_smoke.py — serened end to end on one accelerator, checked.

    python3 chip_smoke.py            # full size, no arguments

Drives the system the way a user does and proves where the work ran:

1. This process stays OFF jax. It starts ONE child,
   `python -m serenedb_tpu.serened <datadir>` (the only process that
   touches the chip), reads the backend off its `serened ready` line and
   stops — non-zero, nothing on stdout — unless that backend is a TPU.
2. It writes the data files from `--seed`, loads them over raw pgwire
   (`COPY … (FORMAT parquet)`, `CREATE INDEX … USING inverted|ivf`):
   a ClickBench-`hits`-shaped table (10M rows; its first 1M rows again
   as `hits_1m`, the fused join tier's own flagship size), a 1M-document
   corpus (30k-word vocabulary, zipf terms), 100k × 256-d vectors on a
   clustered grid. `--scale` cuts ROWS only and every cut is
   printed under `reduced`.
3. It runs a few queries of each class over pgwire, HTTP `/_sql` and the
   ES API and compares every answer with a plain reference:
   - relational (Q1 count, filtered sum, GROUP BY, filtered and
     unfiltered top-N over 10M rows;
     join→aggregate and aggregate→ORDER BY…LIMIT over `hits_1m`; the
     10M-row join, which the fused tier must decline — EXPECTED_HOST):
     the same statement under `SET serene_device='cpu'` in the same
     session, plus numpy on the generated arrays. Integer results must
     be EQUAL.
   - BM25 (`_search` and `@@`/`bm25()`; single term, 2-term OR, 2-term
     AND; then eight at once): float64 numpy BM25 over the generated
     token arrays. Rule: every returned document's score is within
     BM25_RTOL/BM25_ATOL of its reference score, and no document left
     out scores above the lowest returned one by more than that
     tolerance — the same top-k set, order free only inside the
     tolerance.
   - kNN (`<->` and the ES `knn` DSL; hybrid RRF): numpy brute force on
     the generated matrix. The corpus is grid-quantized (entries k/16),
     so every squared distance is exact in f32 in any summation order:
     at `nprobe = lists` ids and distances must be EQUAL to the
     reference, (distance asc, row asc); at the default nprobe
     recall@10 must reach KNN_MIN_RECALL.
4. Guarantee: `_bulk` 1k documents, read every acknowledged id back;
   SIGTERM; start serened again on the same datadir; the same reads and
   the same queries give the same answers.
5. Compile cache: non-empty after the first server, and the restarted
   server — boot recovery included — adds no entry while it repeats
   the same statements.

Evidence is printed as JSON lines: per phase the server's platform /
device_kind / count (`sdb_device()` over the wire), the program families
that compiled and dispatched (`sdb_programs()` deltas), DeviceOffloads,
fused declines, pool counters, the HBM estimate beside the backend's own
bytes-in-use. `ran_on` is "tpu" only where the compile ledger shows the
phase's programs dispatching with no decline; a BM25 phase also prints
`scored`: how many of its questions a device scoring program answered
and how many a host tier (SearchQueriesScored{Device,Host}), and says
"host (...)" where the host tier took them all; where no serving surface
can tell (the module-level `@jax.jit` kernels of ops/agg.py bypass the
ledger) it says "not observable".

Any failed phase, any exception, a server that does not exit on
SIGTERM, or a non-TPU backend: non-zero exit. The last stdout line of a
passing run is {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# -- deployment sizes (the repo's flagship shapes; --scale cuts rows only) ----

HITS_ROWS = 10_000_000
DOCS = 1_000_000
VOCAB = 30_000
VECTORS = 100_000
DIM = 256
LISTS = 64
BULK_DOCS = 1_000

# -- comparison rules ---------------------------------------------------------

#: BM25 scores: device f32 vs the float64 reference. r02 passed at
#: rtol=2e-3/atol=1e-3 through the kernels of its day; today's plane
#: kernel and dense path are f32 elementwise arithmetic with no MXU dot,
#: and the v5e showed a largest relative error of 2.3e-7 at full size
#: (PR 21), so the rule is two orders tighter than r02's and ~40x above
#: what was seen
BM25_RTOL = 1e-5
BM25_ATOL = 1e-8
#: recall@10 of the default nprobe (8 of 64 lists) against brute force
KNN_MIN_RECALL = 0.3

#: deployment settings handed to serened through its environment (the
#: documented surface for global knobs): the vector pool must hold the
#: 100k × 256-d corpus (6250 pages; default budget is 4096) and the
#: column cache the touched `hits` columns
SERVER_ENV = {"SERENE_VECTOR_PAGES": "8192",
              "SERENE_DEVICE_CACHE_MB": "1024"}

#: paths this smoke EXPECTS to be answered off the device, with the
#: reason — anything else that lands on the host fails the run
EXPECTED_HOST = {
    "bm25:maxscore": "a disjunction whose MaxScore-essential postings "
                     "number <= 4096 is scored on the host by _cpu_score "
                     "by design (searcher.MAXSCORE_CAND_CAP); an "
                     "in-process probe on the v5e saw it take 5 of the 8 "
                     "coalesced queries (PR 21). The phase's `scored` "
                     "line counts them (SearchQueriesScoredHost)",
    "sql:join_agg_10m": "the fused join tier admits a plan only while its "
                        "worst-case pair count keeps every int32 limb "
                        "scatter exact (MAX_PAIRS_EXACT = 2^23, "
                        "exec/device_pipeline.py); a 10M-row probe side "
                        "exceeds it, so the host path answers (declined="
                        "not_compilable)",
}

#: rows of `hits` that also load as `hits_1m`: the fused join tier's own
#: flagship size, under its 2^23-pair wall
FUSED_ROWS = 1_000_000

BM25_K1, BM25_B = 1.2, 0.75


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# -- output -------------------------------------------------------------------

_OUT_DIR = os.path.join(HERE, "chiprun_out")
_evidence_file = None


def emit(rec: dict) -> None:
    """One evidence line: stdout + chiprun_out/chip_smoke.jsonl."""
    line = json.dumps(rec, sort_keys=True, default=str)
    print(line, flush=True)
    if _evidence_file is not None:
        _evidence_file.write(line + "\n")
        _evidence_file.flush()


def note(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# -- the server child ---------------------------------------------------------


class Server:
    """One `python -m serenedb_tpu.serened` child on free ports."""

    def __init__(self, datadir: str, log_path: str):
        self.datadir = datadir
        self.log_path = log_path
        self.proc = None
        self.pg_port = self.http_port = 0
        self.backend: dict = {}
        self.cache_dir = ""
        self.ready_s = 0.0

    def start(self, timeout_s: float = 600.0) -> None:
        env = dict(os.environ, **SERVER_ENV)
        env["PYTHONUNBUFFERED"] = "1"
        self._log = open(self.log_path, "w")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "serenedb_tpu.serened", self.datadir,
             "--pg-port", "0", "--http-port", "0"],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT)
        deadline = t0 + timeout_s
        while True:
            ready = None
            with open(self.log_path, errors="replace") as f:
                for line in f:
                    if "compile_cache=" in line and not self.cache_dir:
                        self.cache_dir = line.rsplit(
                            "compile_cache=", 1)[1].strip()
                    if line.startswith("serened ready:"):
                        ready = line.strip()
            if ready is not None:
                break
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"serened exited with {self.proc.returncode} before "
                    f"it was ready:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"serened not ready after {timeout_s:.0f}s:\n"
                    f"{self.log_tail()}")
            time.sleep(0.2)
        self.ready_s = time.monotonic() - t0
        # serened ready: pg=P http=H platform=X devices=N device_kind=K…
        body = ready.split(":", 1)[1].strip()
        head, _, kind = body.partition(" device_kind=")
        fields = dict(kv.split("=", 1) for kv in head.split())
        self.pg_port = int(fields["pg"])
        self.http_port = int(fields["http"])
        self.backend = {"platform": fields["platform"],
                        "kind": kind.strip(),
                        "count": int(fields["devices"])}

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop(self, timeout_s: float = 120.0) -> float:
        """SIGTERM and wait; a server that does not exit cleanly on
        SIGTERM is a failure (it is killed so nothing is left behind)."""
        if self.proc is None or self.proc.poll() is not None:
            rc = None if self.proc is None else self.proc.returncode
            raise SmokeFailure(f"serened was not running at stop (rc={rc})"
                               f":\n{self.log_tail()}")
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(
                f"serened did not exit within {timeout_s:.0f}s of SIGTERM"
                f":\n{self.log_tail()}")
        self._log.close()
        check(rc == 0, f"serened exited {rc} on SIGTERM:\n{self.log_tail()}")
        return time.monotonic() - t0

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)


# -- wire clients -------------------------------------------------------------


class Pg:
    """Raw PG v3 simple-query client (no driver is installed)."""

    def __init__(self, port: int, timeout: float = 1200.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.buf = b""
        body = struct.pack("!I", 196608) + b"user\x00smoke\x00\x00"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        while True:
            kind, payload = self._msg()
            if kind == b"E":
                raise SmokeFailure(f"pg startup refused: {payload!r}")
            if kind == b"R":
                (code,) = struct.unpack("!I", payload[:4])
                check(code == 0, f"pg demands auth method {code}")
            if kind == b"Z":
                return

    def _msg(self):
        while len(self.buf) < 5:
            self._fill()
        kind = self.buf[:1]
        (ln,) = struct.unpack("!I", self.buf[1:5])
        while len(self.buf) < 1 + ln:
            self._fill()
        payload = self.buf[5:1 + ln]
        self.buf = self.buf[1 + ln:]
        return kind, payload

    def _fill(self):
        data = self.sock.recv(1 << 20)
        if not data:
            raise SmokeFailure("pg connection closed by server")
        self.buf += data

    def query(self, sql: str) -> list[tuple]:
        """Rows of the LAST result set as text tuples; raises on any
        ErrorResponse."""
        self.sock.sendall(b"Q" + struct.pack("!I", len(sql.encode()) + 5)
                          + sql.encode() + b"\x00")
        rows: list[tuple] = []
        err = None
        while True:
            kind, payload = self._msg()
            if kind == b"T":
                rows = []
            elif kind == b"D":
                (n,) = struct.unpack("!H", payload[:2])
                off, row = 2, []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", payload[off:off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif kind == b"E":
                err = payload.replace(b"\x00", b" ").decode(
                    errors="replace")
            elif kind == b"Z":
                if err is not None:
                    raise SmokeFailure(f"SQL error for {sql[:120]!r}: "
                                       f"{err}")
                return rows

    def close(self):
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        except OSError:
            pass
        self.sock.close()


def http_json(port: int, method: str, path: str, body=None,
              timeout: float = 1200.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = None
        if body is not None:
            data = body if isinstance(body, (bytes, str)) \
                else json.dumps(body)
        conn.request(method, path, data,
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        raw = r.read()
    finally:
        conn.close()
    check(r.status < 300, f"HTTP {method} {path} → {r.status}: "
                          f"{raw[:400]!r}")
    return json.loads(raw)


# -- evidence -----------------------------------------------------------------

_GAUGES = ("DeviceOffloads", "DeviceTransfersUp", "SearchBatchDispatches",
           "SearchBatchQueries", "SearchBatchCoalesced",
           "FragmentCacheHits", "ResultCacheHits",
           "VectorSearchDispatches",
           "VectorSearchQueries", "NativeIndexBuilds",
           "NativeIndexFallbacks", "SegmentBuilds",
           "SearchQueriesScoredDevice", "SearchQueriesScoredHost",
           "SearchPostingsDispatched", "SearchProgramsPrebuilt",
           "VectorQueriesScoredFlat", "VectorQueriesScoredProbe",
           "VectorRowsScanned")


def snapshot(srv: Server, pg: Pg) -> dict:
    """The server's own account of itself, over the wire."""
    dev = http_json(srv.http_port, "GET", "/device")
    rows = pg.query(
        "SELECT device, platform, kind, dispatches, bytes_up, bytes_down, "
        "hbm_bytes_est, hbm_bytes_in_use, hbm_bytes_limit "
        "FROM sdb_device() ORDER BY device")
    lits = ", ".join(f"'{g}'" for g in _GAUGES)
    gauges = {n: int(float(v)) for n, v in pg.query(
        f"SELECT metric, value FROM sdb_metrics WHERE metric IN ({lits})")}
    return {
        "devices": [{"device": int(r[0]), "platform": r[1], "kind": r[2],
                     "dispatches": int(r[3]), "bytes_up": int(r[4]),
                     "bytes_down": int(r[5]), "hbm_bytes_est": int(r[6]),
                     "hbm_bytes_in_use": None if r[7] is None
                     else int(r[7]),
                     "hbm_bytes_limit": None if r[8] is None
                     else int(r[8])} for r in rows],
        "programs": {p["family"]: p for p in dev["programs"]},
        "fused_declines": dev["fused_declines"],
        "vector_pool": dev["vector_pool"],
        "gauges": gauges,
    }


def phase_evidence(before: dict, after: dict) -> dict:
    """What moved between two snapshots, and where the phase ran."""
    fams = {}
    for fam, p in after["programs"].items():
        b = before["programs"].get(fam, {})
        compiled = p["compiles"] - b.get("compiles", 0)
        looked_up = (p["hits"] + p["misses"]) - \
            (b.get("hits", 0) + b.get("misses", 0))
        if compiled or looked_up:
            fams[fam] = {"compiled": compiled, "dispatched": looked_up,
                         "compile_ms": round(
                             p["compile_ms_total"] -
                             b.get("compile_ms_total", 0.0), 1)}
    declines = {k: v - before["fused_declines"].get(k, 0)
                for k, v in after["fused_declines"].items()
                if v != before["fused_declines"].get(k, 0)}
    gauges = {k: v - before["gauges"].get(k, 0)
              for k, v in after["gauges"].items()
              if v != before["gauges"].get(k, 0)}
    d0 = after["devices"][0] if after["devices"] else {}
    b0 = before["devices"][0] if before["devices"] else {}
    dev_dispatches = sum(d["dispatches"] for d in after["devices"]) - \
        sum(d["dispatches"] for d in before["devices"])
    ev = {
        "server": {"platform": d0.get("platform"),
                   "device_kind": d0.get("kind"),
                   "count": len(after["devices"])},
        "families": fams, "fused_declines": declines, "gauges": gauges,
        "ledger_dispatches": dev_dispatches,
        "bytes_up": d0.get("bytes_up", 0) - b0.get("bytes_up", 0),
        "hbm_bytes_est": d0.get("hbm_bytes_est"),
        "hbm_bytes_in_use": d0.get("hbm_bytes_in_use"),
    }
    # a search says itself which tier scored it (the counters partition
    # the questions scored; a fragment-cache hit moves neither)
    on_dev = gauges.get("SearchQueriesScoredDevice", 0)
    on_host = gauges.get("SearchQueriesScoredHost", 0)
    if on_dev or on_host:
        ev["scored"] = {"device": on_dev, "host": on_host}
    # a knn question likewise: the exact flat scan or the IVF probe
    flat = gauges.get("VectorQueriesScoredFlat", 0)
    probe = gauges.get("VectorQueriesScoredProbe", 0)
    if flat or probe:
        ev.setdefault("scored", {}).update(flat=flat, probe=probe)
    if fams and dev_dispatches and not declines:
        ev["ran_on"] = d0.get("platform")
    elif declines:
        ev["ran_on"] = "host (fused tier declined)"
    elif on_host and not on_dev:
        ev["ran_on"] = "host (search host tier scored every question)"
    else:
        ev["ran_on"] = "not observable"
    return ev


# -- data ---------------------------------------------------------------------


def gen_hits(seed: int, n: int, path: str, regions_path: str,
             prefix_path: str = None) -> dict:
    """A `hits`-shaped table: full-range int64 UserID (zipf-skewed user
    activity), skewed RegionID, mostly-zero AdvEngineID, mostly-empty
    SearchPhrase, SearchEngineID, ResolutionWidth — plus the 9000-row
    `regions` dimension the join queries use."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng([seed, 3])
    n_users = 500_000
    user_hashes = rng.integers(0, 1 << 62, n_users, dtype=np.int64)
    uid = user_hashes[rng.zipf(1.4, n).astype(np.int64) % n_users]
    region = (rng.zipf(1.5, n) % 9000).astype(np.int32)
    adv = np.where(rng.random(n) < 0.96, 0,
                   rng.integers(1, 64, n)).astype(np.int32)
    n_phrases = 100_000
    phrase_pool = [""] + [f"phrase {i}" for i in range(n_phrases)]
    pid = np.where(rng.random(n) < 0.7, 0,
                   1 + rng.zipf(1.3, n) % n_phrases).astype(np.int32)
    seid = (rng.zipf(1.6, n) % 100).astype(np.int32)
    width = rng.integers(0, 4000, n).astype(np.int32)
    phrases = pa.DictionaryArray.from_arrays(
        pa.array(pid), pa.array(phrase_pool, pa.string()))
    tbl = pa.table({
        "UserID": uid, "RegionID": region, "AdvEngineID": adv,
        "SearchPhrase": phrases.cast(pa.string()),
        "SearchEngineID": seid, "ResolutionWidth": width})
    pq.write_table(tbl, path, compression="snappy")
    if prefix_path is not None:
        pq.write_table(tbl.slice(0, min(n, FUSED_ROWS)), prefix_path,
                       compression="snappy")
    zone = (np.arange(9000, dtype=np.int32) * 7919) % 16
    pq.write_table(pa.table({
        "RegionID": np.arange(9000, dtype=np.int32),
        "Zone": zone.astype(np.int32)}), regions_path)
    return {"uid": uid, "region": region, "adv": adv, "seid": seid,
            "width": width, "zone": zone}


def gen_docs(seed: int, n_docs: int, path: str) -> dict:
    """The document corpus: 30k-word vocabulary, zipf(1.25) terms,
    8–39 tokens per document; an ES-shaped table (_id, _source, body)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    rng = np.random.default_rng([seed, 5])
    lens = rng.integers(8, 40, n_docs)
    toks = (rng.zipf(1.25, size=int(lens.sum())) % VOCAB).astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    vocab = pa.array([f"w{i}" for i in range(VOCAB)], pa.large_string())
    words = vocab.take(pa.array(toks))
    lists = pa.LargeListArray.from_arrays(pa.array(bounds), words)
    body = pc.binary_join(lists, pa.scalar(" ", pa.large_string()))
    ids = pa.array([str(i) for i in range(n_docs)], pa.string())
    src = pa.array([f'{{"n": {i}}}' for i in range(n_docs)], pa.string())
    pq.write_table(pa.table({"_id": ids, "_source": src,
                             "body": body.cast(pa.large_string())}),
                   path, compression="snappy")
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int32), lens)
    return {"toks": toks, "doc_of": doc_of, "lens": lens,
            "n_docs": n_docs}


def gen_vectors(seed: int, n: int, path: str) -> dict:
    """A clustered grid corpus: centers k/16
    (|k|<48) + noise k/16 (|k|<16) — every coordinate a multiple of
    2^-4 with |v| < 4, so squared distances are exact in f32 whatever
    the summation order. Vectors travel as JSON-array text (the
    engine's vector column format); `title` feeds the hybrid query."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    rng = np.random.default_rng([seed, 7])
    centers = rng.integers(-48, 48, (LISTS, DIM)).astype(np.int32)
    assign = rng.integers(0, LISTS, n)
    grid = centers[assign] + rng.integers(-16, 16, (n, DIM)).astype(
        np.int32)                                     # k in [-64, 64)
    table = pa.array([repr(k / 16.0) for k in range(-64, 64)],
                     pa.large_string())
    flat = table.take(pa.array((grid + 64).reshape(-1)))
    offs = pa.array(np.arange(0, (n + 1) * DIM, DIM, dtype=np.int64))
    ls = pa.large_string()
    joined = pc.binary_join(pa.LargeListArray.from_arrays(offs, flat),
                            pa.scalar(",", ls))
    emb = pc.binary_join_element_wise(
        pa.scalar("[", ls), joined, pa.scalar("]", ls), pa.scalar("", ls))
    ids = pa.array([str(i) for i in range(n)], pa.string())
    src = pa.array([f'{{"n": {i}}}' for i in range(n)], pa.string())
    # title: the row's cluster word + a row-unique word (hybrid query
    # text matches one cluster's rows)
    title = pa.array([f"cluster{int(c)} item{i}"
                      for i, c in enumerate(assign)], pa.string())
    pq.write_table(pa.table({"_id": ids, "_source": src, "title": title,
                             "emb": emb}), path, compression="snappy")
    mat = grid.astype(np.float32) / np.float32(16.0)
    qrng = np.random.default_rng([seed, 8])
    nq = 8
    queries = (centers[qrng.integers(0, LISTS, nq)] +
               qrng.integers(-16, 16, (nq, DIM))).astype(np.float32) \
        / np.float32(16.0)
    return {"mat": mat, "queries": queries, "assign": assign}


# -- plain references ---------------------------------------------------------


def bm25_reference(docs: dict, terms: list[int], require_all: bool):
    """Float64 BM25 over the generated token arrays (Lucene idf, k1=1.2,
    b=0.75 — the engine's documented defaults). Returns the full score
    vector (0 where the query does not match)."""
    import numpy as np
    n = docs["n_docs"]
    dl = docs["lens"].astype(np.float64)
    avgdl = float(docs["lens"].sum()) / n
    scores = np.zeros(n, np.float64)
    matched = np.ones(n, bool) if require_all else np.zeros(n, bool)
    for t in terms:
        tf = np.bincount(docs["doc_of"][docs["toks"] == t],
                         minlength=n).astype(np.float64)
        df = float(np.count_nonzero(tf))
        idf = float(np.float32(np.log(1.0 + (n - df + 0.5) / (df + 0.5))))
        denom = tf + BM25_K1 * (1.0 - BM25_B + BM25_B * dl / avgdl)
        scores += idf * (BM25_K1 + 1.0) * tf / denom
        matched = (matched & (tf > 0)) if require_all else \
            (matched | (tf > 0))
    return np.where(matched, scores, 0.0)


def check_bm25(label: str, got: list[tuple[int, float]], ref, k: int):
    """The BM25 rule from the module docstring. Returns the largest
    relative score error seen."""
    import numpy as np
    n_match = int(np.count_nonzero(ref > 0))
    check(len(got) == min(k, n_match),
          f"{label}: {len(got)} hits, reference has {min(k, n_match)}")
    docs = [d for d, _ in got]
    check(len(set(docs)) == len(docs), f"{label}: duplicate docs {docs}")
    worst = 0.0
    lowest = float("inf")
    for d, s in got:
        r = float(ref[d])
        tol = BM25_ATOL + BM25_RTOL * abs(r)
        check(abs(s - r) <= tol,
              f"{label}: doc {d} scored {s!r}, reference {r!r}")
        worst = max(worst, abs(s - r) / max(abs(r), 1e-30))
        lowest = min(lowest, r)
    scores = [s for _, s in got]
    for a, b in zip(scores, scores[1:]):
        check(b <= a + BM25_ATOL + BM25_RTOL * abs(a),
              f"{label}: not sorted by score desc: {scores}")
    if got:
        mask = np.ones(len(ref), bool)
        mask[docs] = False
        best_out = float(ref[mask].max()) if mask.any() else 0.0
        check(best_out <= lowest + BM25_ATOL + BM25_RTOL * abs(lowest),
              f"{label}: a document scoring {best_out!r} was left out "
              f"(lowest returned {lowest!r})")
    return worst


def knn_reference(vec: dict, qi: int, k: int):
    """Exact f32 brute force, (distance asc, row asc)."""
    import numpy as np
    dv = vec["mat"] - vec["queries"][qi]
    d = (dv * dv).sum(axis=1, dtype=np.float32)
    order = np.lexsort((np.arange(len(d)), d))[:k]
    return [(int(i), float(d[i])) for i in order]


def knn_distance(vec: dict, qi: int, row: int) -> float:
    import numpy as np
    dv = vec["mat"][row] - vec["queries"][qi]
    return float((dv * dv).sum(dtype=np.float32))


def vec_literal(q) -> str:
    return "[" + ",".join(repr(float(x)) for x in q) + "]"


# -- phases -------------------------------------------------------------------


class Run:
    """Everything one server lifetime is asked, recorded so the second
    lifetime can be held to the first."""

    def __init__(self, srv: Server, data: dict, tag: str):
        self.srv = srv
        self.data = data
        self.tag = tag
        self.pg = Pg(srv.pg_port)
        self.answers: dict = {}
        self.failed: list[str] = []
        self.worst_bm25 = 0.0

    def phase(self, name: str, fn, expect_device: bool = False,
              verify=None):
        """Run one phase between two evidence snapshots; a failure is
        recorded (and fails the run) but later phases still report.
        A phase named in EXPECTED_HOST may be answered by the host path
        for the listed reason; any other expect_device phase may not.
        `verify(rec)` checks the phase's evidence (raises SmokeFailure)."""
        before = snapshot(self.srv, self.pg)
        t0 = time.monotonic()
        rec = {"phase": f"{self.tag}:{name}"}
        try:
            extra = fn() or {}
            rec.update(extra)
            rec["ok"] = True
        except SmokeFailure as e:
            rec["ok"] = False
            rec["error"] = str(e)[:2000]
        except Exception as e:  # noqa: BLE001 — reported, then fails the run
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-2000:]
        rec["seconds"] = round(time.monotonic() - t0, 3)
        try:
            after = snapshot(self.srv, self.pg)
            rec.update(phase_evidence(before, after))
        except Exception as e:  # noqa: BLE001
            rec["ok"] = False
            rec["error"] = rec.get("error", "") + \
                f" | evidence snapshot failed: {type(e).__name__}: {e}"
            # the session may be wedged mid-protocol: take a fresh one
            try:
                self.pg = Pg(self.srv.pg_port)
            except Exception:  # noqa: BLE001
                pass
        if rec["ok"] and name in EXPECTED_HOST and \
                str(rec.get("ran_on")).startswith("host"):
            rec["ran_on"] = f"host (expected: {EXPECTED_HOST[name]})"
        elif rec["ok"] and expect_device:
            plat = self.srv.backend["platform"]
            if rec.get("ran_on") != plat:
                rec["ok"] = False
                rec["error"] = (
                    f"phase was expected on the device ({plat}) but the "
                    f"server's ledger says ran_on={rec.get('ran_on')!r} "
                    f"(families={rec.get('families')}, "
                    f"declines={rec.get('fused_declines')})")
        if rec["ok"] and verify is not None:
            try:
                verify(rec)
            except SmokeFailure as e:
                rec["ok"] = False
                rec["error"] = str(e)
        if not rec["ok"]:
            self.failed.append(rec["phase"])
        emit(rec)
        return rec

    # -- load -------------------------------------------------------------

    def load(self, paths: dict):
        pg = self.pg

        def load_hits():
            pg.query('CREATE TABLE hits ("UserID" BIGINT, "RegionID" INT, '
                     '"AdvEngineID" INT, "SearchPhrase" VARCHAR, '
                     '"SearchEngineID" INT, "ResolutionWidth" INT)')
            pg.query(f"COPY hits FROM '{paths['hits']}' (FORMAT parquet)")
            pg.query('CREATE TABLE hits_1m ("UserID" BIGINT, '
                     '"RegionID" INT, "AdvEngineID" INT, '
                     '"SearchPhrase" VARCHAR, "SearchEngineID" INT, '
                     '"ResolutionWidth" INT)')
            pg.query(f"COPY hits_1m FROM '{paths['hits_1m']}' "
                     "(FORMAT parquet)")
            pg.query('CREATE TABLE regions ("RegionID" INT, "Zone" INT)')
            pg.query(f"COPY regions FROM '{paths['regions']}' "
                     "(FORMAT parquet)")
            n = int(pg.query("SELECT count(*) FROM hits")[0][0])
            check(n == len(self.data["hits"]["adv"]),
                  f"hits holds {n} rows")
            return {"rows": n}

        def load_docs():
            pg.query('CREATE TABLE docs ("_id" VARCHAR, "_source" VARCHAR, '
                     'body VARCHAR)')
            pg.query(f"COPY docs FROM '{paths['docs']}' (FORMAT parquet)")
            pg.query("CREATE INDEX docs_body ON docs USING inverted (body) "
                     "WITH (tokenizer = 'simple')")
            n = int(pg.query("SELECT count(*) FROM docs")[0][0])
            check(n == self.data["docs"]["n_docs"], f"docs holds {n} rows")
            return {"rows": n}

        def load_vecs():
            pg.query('CREATE TABLE vecs ("_id" VARCHAR, "_source" VARCHAR, '
                     'title VARCHAR, emb VARCHAR)')
            pg.query(f"COPY vecs FROM '{paths['vecs']}' (FORMAT parquet)")
            pg.query("CREATE INDEX vecs_emb ON vecs USING ivf (emb) "
                     f"WITH (lists = {LISTS}, dim = {DIM})")
            pg.query("CREATE INDEX vecs_title ON vecs USING inverted "
                     "(title) WITH (tokenizer = 'simple')")
            n = int(pg.query("SELECT count(*) FROM vecs")[0][0])
            check(n == len(self.data["vecs"]["mat"]), f"vecs holds {n} rows")
            return {"rows": n}

        def native_indexer_built(rec):
            # the Python tokenizer answering a 'simple' ASCII corpus means
            # the native indexer did not build: an error here, not a warning
            g = rec.get("gauges", {})
            check(g.get("NativeIndexBuilds") and
                  not g.get("NativeIndexFallbacks"),
                  "inverted index was built by the Python tokenizer, not "
                  f"the native indexer: {g}")

        self.phase("load_hits", load_hits)
        self.phase("load_docs", load_docs, verify=native_indexer_built)
        self.phase("load_vecs", load_vecs)

    # -- relational -------------------------------------------------------

    SQL = {
        "q1": 'SELECT count(*) FROM hits WHERE "AdvEngineID" <> 0',
        "filtered_sum":
            'SELECT count(*), sum("ResolutionWidth") FROM hits '
            'WHERE "AdvEngineID" <> 0 AND "ResolutionWidth" < 3000',
        "group_by":
            'SELECT "RegionID", count(*), sum("ResolutionWidth") FROM hits '
            'GROUP BY "RegionID" ORDER BY "RegionID"',
        "top_n":
            'SELECT "ResolutionWidth", "RegionID" FROM hits '
            'WHERE "AdvEngineID" <> 0 '
            'ORDER BY "ResolutionWidth" DESC LIMIT 10',
        "top_n_unfiltered":
            'SELECT "ResolutionWidth", "RegionID" FROM hits '
            'ORDER BY "ResolutionWidth" DESC LIMIT 10',
        "join_agg":
            'SELECT h."SearchEngineID", count(*), sum(h."ResolutionWidth"), '
            'max(r."Zone") FROM hits_1m h JOIN regions r '
            'ON h."RegionID" = r."RegionID" WHERE h."AdvEngineID" <> 0 '
            'GROUP BY h."SearchEngineID" ORDER BY h."SearchEngineID"',
        "agg_topn_chain":
            'SELECT h."SearchEngineID", count(*) AS c FROM hits_1m h '
            'JOIN regions r ON h."RegionID" = r."RegionID" '
            'WHERE r."Zone" < 8 GROUP BY h."SearchEngineID" '
            'ORDER BY c DESC LIMIT 10',
        "join_agg_10m":
            'SELECT h."SearchEngineID", count(*), sum(h."ResolutionWidth"), '
            'max(r."Zone") FROM hits h JOIN regions r '
            'ON h."RegionID" = r."RegionID" WHERE h."AdvEngineID" <> 0 '
            'GROUP BY h."SearchEngineID" ORDER BY h."SearchEngineID"',
    }

    #: program families each statement must show in the compile ledger
    SQL_FAMILIES = {
        "q1": {"device_agg"}, "filtered_sum": {"device_agg"},
        "group_by": {"device_agg"}, "top_n": {"fused_topn"},
        "top_n_unfiltered": {"device_topn"},
        "join_agg": {"fused"}, "agg_topn_chain": {"fused", "fused_chain"},
        "join_agg_10m": set(),
    }

    def numpy_sql_reference(self, name: str):
        import numpy as np
        h = self.data["hits"]
        if name == "q1":
            return [(str(int((h["adv"] != 0).sum())),)]
        if name == "filtered_sum":
            m = (h["adv"] != 0) & (h["width"] < 3000)
            return [(str(int(m.sum())),
                     str(int(h["width"][m].astype(np.int64).sum())))]
        if name == "group_by":
            cnt = np.bincount(h["region"], minlength=9000)
            sm = np.bincount(h["region"], weights=h["width"].astype(
                np.float64), minlength=9000)
            return [(str(r), str(int(cnt[r])), str(int(sm[r])))
                    for r in np.flatnonzero(cnt)]
        if name in ("join_agg", "join_agg_10m"):
            n = FUSED_ROWS if name == "join_agg" else len(h["adv"])
            m = h["adv"][:n] != 0
            se = h["seid"][:n][m]
            w = h["width"][:n][m].astype(np.int64)
            z = h["zone"][h["region"][:n][m]]
            out = []
            for s in np.unique(se):
                mm = se == s
                out.append((str(int(s)), str(int(mm.sum())),
                            str(int(w[mm].sum())), str(int(z[mm].max()))))
            return out
        return None

    def relational(self):
        pg = self.pg
        for name, sql in self.SQL.items():
            def run(name=name, sql=sql):
                pg.query("SET serene_device = 'cpu'")
                ref = pg.query(sql)
                pg.query("SET serene_device = 'tpu'")
                got = pg.query(sql)
                check(len(got) > 0, f"{name}: empty result")
                if name.startswith("top_n"):
                    # LIMIT under a non-unique key: the key column is
                    # determined, the tie-broken passenger column is not
                    check([r[0] for r in got] == [r[0] for r in ref],
                          f"{name}: device {got} != host {ref}")
                else:
                    check(got == ref,
                          f"{name}: device {got[:5]} != host {ref[:5]}")
                npref = self.numpy_sql_reference(name)
                if npref is not None:
                    check(got == npref,
                          f"{name}: device {got[:5]} != numpy {npref[:5]}")
                self.answers[f"sql:{name}"] = got
                return {"rows_out": len(got), "first_row": got[0],
                        "numpy_checked": npref is not None}

            def families_dispatched(rec, name=name):
                if str(rec.get("ran_on")).startswith("host"):
                    return              # an EXPECTED_HOST decline
                missing = self.SQL_FAMILIES[name] - set(rec["families"])
                check(not missing,
                      f"expected program families {sorted(missing)} did "
                      f"not dispatch: {rec['families']}")

            self.phase(f"sql:{name}", run, expect_device=True,
                       verify=families_dispatched)

        def http_sql():
            # the same Q1 through HTTP /_sql (default settings: auto)
            out = http_json(self.srv.http_port, "POST", "/_sql",
                            {"query": self.SQL["q1"]})
            got = [tuple(str(v) for v in row) for row in out["rows"]]
            check(got == self.answers["sql:q1"],
                  f"/_sql Q1 {got} != pgwire {self.answers['sql:q1']}")
            return {"rows_out": len(got)}

        self.phase("http_sql:q1", http_sql)

    # -- BM25 -------------------------------------------------------------

    #: (label, surface, term indices, require_all); distinct terms per
    #: query so no fragment or result cache can answer for the scorer
    BM25_QUERIES = [
        ("term", "es", [10], False), ("or", "es", [19, 208], False),
        ("and", "es", [28, 1], False),
        ("term", "sql", [37], False), ("or", "sql", [46, 217], False),
        ("and", "sql", [55, 2], False),
    ]
    BM25_CONCURRENT = [[64 + 9 * i, 300 + 7 * i] for i in range(8)]

    def _bm25_es(self, terms, require_all, k=10):
        spec = {"query": " ".join(f"w{t}" for t in terms)}
        if require_all:
            spec["operator"] = "and"
        out = http_json(self.srv.http_port, "POST", "/docs/_search",
                        {"query": {"match": {"body": spec}}, "size": k})
        return [(int(h["_id"]), float(h["_score"]))
                for h in out["hits"]["hits"]]

    def _bm25_sql(self, terms, require_all, k=10):
        q = (" & " if require_all else " | ").join(f"w{t}" for t in terms)
        rows = self.pg.query(
            f'SELECT "_id", bm25(body) AS s FROM docs WHERE body @@ '
            f"'{q}' ORDER BY s DESC LIMIT {k}")
        return [(int(r[0]), float(r[1])) for r in rows]

    def bm25_serial(self):
        docs = self.data["docs"]
        for label, surface, terms, _ in self.BM25_QUERIES:
            require_all = label == "and"

            def run(label=label, surface=surface, terms=terms,
                    require_all=require_all):
                fn = self._bm25_es if surface == "es" else self._bm25_sql
                got = fn(terms, require_all)
                ref = bm25_reference(docs, terms, require_all)
                worst = check_bm25(f"bm25 {surface} {label} {terms}", got,
                                   ref, 10)
                self.worst_bm25 = max(self.worst_bm25, worst)
                self.answers[f"bm25:{surface}:{label}"] = got
                return {"hits": len(got), "max_rel_err": worst,
                        "top": got[:2]}

            self.phase(f"bm25:{surface}:{label}", run)

    def bm25_concurrent(self):
        docs = self.data["docs"]

        def concurrent():
            # eight _search requests released together: the batcher
            # coalesces whatever arrives inside its window
            results: list = [None] * len(self.BM25_CONCURRENT)
            gate = threading.Barrier(len(self.BM25_CONCURRENT))

            def one(i, terms):
                try:
                    gate.wait(timeout=60)
                    results[i] = self._bm25_es(terms, False)
                except Exception as e:  # noqa: BLE001 — checked below
                    results[i] = e

            ts = [threading.Thread(target=one, args=(i, t))
                  for i, t in enumerate(self.BM25_CONCURRENT)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=900)
            worst = 0.0
            for i, terms in enumerate(self.BM25_CONCURRENT):
                got = results[i]
                check(not isinstance(got, Exception) and got is not None,
                      f"concurrent _search {terms} failed: {got!r}")
                ref = bm25_reference(docs, terms, False)
                worst = max(worst, check_bm25(
                    f"bm25 concurrent {terms}", got, ref, 10))
                self.answers[f"bm25:concurrent:{i}"] = got
            self.worst_bm25 = max(self.worst_bm25, worst)
            return {"queries": len(ts), "max_rel_err": worst}

        self.phase("bm25:concurrent8", concurrent)

    # -- vectors ----------------------------------------------------------

    def vectors(self):
        vec = self.data["vecs"]
        pg = self.pg

        def sql_exact():
            pg.query(f"SET serene_nprobe = {LISTS}")
            for qi in range(4):
                lit = vec_literal(vec["queries"][qi])
                rows = pg.query(
                    f'SELECT "_id", emb <-> \'{lit}\' AS d FROM vecs '
                    "ORDER BY d LIMIT 10")
                got = [(int(r[0]), float(r[1])) for r in rows]
                ref = knn_reference(vec, qi, 10)
                check(got == ref, f"knn <-> q{qi} at nprobe=lists: "
                                  f"{got[:3]} != brute force {ref[:3]}")
                self.answers[f"knn:sql_exact:{qi}"] = got
            pg.query("SET serene_nprobe = 0")
            return {"queries": 4, "exact": True}

        def sql_recall():
            hit = tot = 0
            for qi in range(len(vec["queries"])):
                lit = vec_literal(vec["queries"][qi])
                rows = pg.query(
                    f'SELECT "_id", emb <-> \'{lit}\' AS d FROM vecs '
                    "ORDER BY d LIMIT 10")
                got = [(int(r[0]), float(r[1])) for r in rows]
                ref = knn_reference(vec, qi, 10)
                check(len(got) == 10, f"knn q{qi}: {len(got)} rows")
                for rid, d in got:   # whatever is returned is scored right
                    exact = knn_distance(vec, qi, rid)
                    check(d == exact, f"knn q{qi}: row {rid} at distance "
                                      f"{d}, exact {exact}")
                hit += len({r for r, _ in got} & {r for r, _ in ref})
                tot += 10
                self.answers[f"knn:sql_recall:{qi}"] = got
            recall = hit / tot
            check(recall >= KNN_MIN_RECALL,
                  f"recall@10 at default nprobe = {recall:.3f} "
                  f"< {KNN_MIN_RECALL}")
            return {"queries": tot // 10, "recall_at_10": recall}

        def es_knn():
            for qi in range(2):
                out = http_json(self.srv.http_port, "POST", "/vecs/_search", {
                    "knn": {"field": "emb", "k": 10, "nprobe": LISTS,
                            "query_vector": [float(x) for x in
                                             vec["queries"][qi]]},
                    "size": 10})
                got = [int(h["_id"]) for h in out["hits"]["hits"]]
                ref = [r for r, _ in knn_reference(vec, qi, 10)]
                check(got == ref, f"ES knn q{qi}: {got} != {ref}")
                self.answers[f"knn:es:{qi}"] = got
            return {"queries": 2, "exact": True}

        def hybrid():
            qi = 5      # a vector no earlier phase sent: no cache answers
            cluster = int(vec["assign"][
                knn_reference(vec, qi, 1)[0][0]])
            out = http_json(self.srv.http_port, "POST", "/vecs/_search", {
                "knn": {"field": "emb", "k": 10, "nprobe": LISTS,
                        "num_candidates": 40,
                        "query_vector": [float(x) for x in
                                         vec["queries"][qi]]},
                "query": {"match": {"title": f"cluster{cluster}"}},
                "size": 10})
            hits = out["hits"]["hits"]
            check(len(hits) == 10, f"hybrid returned {len(hits)} hits")
            # RRF: a document in BOTH rankings outranks any document in
            # one; the knn side is exact, so its 40 candidates are known
            knn40 = [r for r, _ in knn_reference(vec, qi, 40)]
            scores = [float(h["_score"]) for h in hits]
            check(scores == sorted(scores, reverse=True),
                  f"hybrid not sorted by fused score: {scores}")
            for h in hits:
                rid = int(h["_id"])
                in_knn = rid in knn40
                in_text = int(vec["assign"][rid]) == cluster
                check(in_knn or in_text,
                      f"hybrid hit {rid} is in neither ranking")
                if in_knn:
                    # its fused score holds at least the knn side's share
                    share = 1.0 / (60 + knn40.index(rid) + 1)
                    check(float(h["_score"]) >= share - 1e-12,
                          f"hybrid hit {rid}: fused score "
                          f"{h['_score']} < its knn share {share}")
            self.answers["knn:hybrid"] = [int(h["_id"]) for h in hits]
            return {"hits": len(hits), "top": self.answers["knn:hybrid"][:3]}

        self.phase("knn:sql_exact", sql_exact, expect_device=True)
        self.phase("knn:sql_recall", sql_recall, expect_device=True)
        self.phase("knn:es", es_knn, expect_device=True)
        self.phase("knn:hybrid_rrf", hybrid, expect_device=True)

    # -- guarantee --------------------------------------------------------

    def bulk_write(self):
        def run():
            lines = []
            for i in range(BULK_DOCS):
                lines.append(json.dumps(
                    {"index": {"_index": "smoke_bulk", "_id": f"b{i}"}}))
                lines.append(json.dumps(
                    {"msg": f"bulk document number{i} common", "n": i}))
            out = http_json(self.srv.http_port, "POST", "/_bulk",
                            "\n".join(lines) + "\n")
            check(not out["errors"], f"_bulk reported errors: "
                                     f"{str(out)[:400]}")
            acked = [it["index"]["_id"] for it in out["items"]
                     if it["index"]["status"] in (200, 201)]
            check(len(acked) == BULK_DOCS,
                  f"{len(acked)} of {BULK_DOCS} writes acknowledged")
            self.answers["bulk:acked"] = acked
            return {"acknowledged": len(acked)}

        self.phase("bulk:write", run)

    def bulk_read(self, acked: list[str]):
        def run():
            out = http_json(self.srv.http_port, "POST", "/_mget",
                            {"index": "smoke_bulk", "ids": acked})
            found = {d["_id"]: d["_source"] for d in out["docs"]
                     if d.get("found")}
            missing = [i for i in acked if i not in found]
            check(not missing, f"{len(missing)} acknowledged writes not "
                               f"readable: {missing[:5]}")
            for i in acked:
                n = int(i[1:])
                check(found[i] == {"msg": f"bulk document number{n} common",
                                   "n": n},
                      f"doc {i} read back as {found[i]}")
            cnt = int(self.pg.query(
                'SELECT count(*) FROM "smoke_bulk"')[0][0])
            check(cnt == len(acked), f"smoke_bulk holds {cnt} rows")
            http_json(self.srv.http_port, "POST", "/smoke_bulk/_refresh")
            res = http_json(self.srv.http_port, "POST", "/smoke_bulk/_search", {
                "query": {"match": {"msg": "number7"}}, "size": 5})
            ids = [h["_id"] for h in res["hits"]["hits"]]
            check(ids == ["b7"], f"_search for number7 returned {ids}")
            # the small-corpus DENSE scoring path, scored: every document
            # is 4 tokens (dl == avgdl, so the tf=1 saturation is exactly
            # 1.0 and a score is the sum of its terms' idf); all but b7
            # tie, and ties — across however many segments the writes
            # left — come back in ascending row order
            import math
            n = len(acked)
            idf_rare = math.log(1.0 + (n - 1 + 0.5) / 1.5)
            idf_all = math.log(1.0 + 0.5 / (n + 0.5))
            res = http_json(self.srv.http_port, "POST", "/smoke_bulk/_search", {
                "query": {"match": {"msg": "number7 common"}}, "size": 5})
            hits = [(h["_id"], float(h["_score"]))
                    for h in res["hits"]["hits"]]
            want = [("b7", idf_rare + idf_all)] + \
                [(f"b{i}", idf_all) for i in (0, 1, 2, 3)]
            check([h[0] for h in hits] == [w[0] for w in want],
                  f"dense-path _search returned {hits}, expected {want}")
            worst = 0.0
            for (_, s), (_, r) in zip(hits, want):
                check(abs(s - r) <= BM25_ATOL + BM25_RTOL * r,
                      f"dense-path scores {hits} != reference {want}")
                worst = max(worst, abs(s - r) / r)
            self.worst_bm25 = max(self.worst_bm25, worst)
            self.answers["bulk:search"] = ids + hits
            return {"read_back": len(found), "dense_max_rel_err": worst}

        self.phase("bulk:read", run)

    def close(self):
        self.pg.close()


# -- server log ---------------------------------------------------------------

#: lines in serened's output that mean a device path misbehaved even
#: though every answer was right
_LOG_ALARMS = ("donated buffer", "has been deleted", "DeprecationWarning",
               "recompile storm", "Traceback (most recent call last)")


def log_alarms(path: str) -> list[str]:
    try:
        with open(path, errors="replace") as f:
            return [ln.strip()[:300] for ln in f
                    if any(a in ln for a in _LOG_ALARMS)]
    except OSError as e:
        return [f"could not read {path}: {e}"]


# -- compile cache ------------------------------------------------------------


def cache_entries(cache_dir: str) -> set:
    try:
        return {f for f in os.listdir(cache_dir) if not f.endswith("-atime")}
    except OSError:
        return set()


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="fraction of the deployment's ROWS to load "
                         "(shapes never change); each cut is printed "
                         "under `reduced`")
    ap.add_argument("--workdir", default=os.path.join(HERE, ".smoke_work"))
    ap.add_argument("--debug-any-platform", action="store_true",
                    help="keep going on a non-TPU backend to debug the "
                         "script itself; the run still FAILS and prints "
                         "no result")
    args = ap.parse_args(argv)
    assert "jax" not in sys.modules, "the smoke's parent must stay off jax"

    global _evidence_file
    t_start = time.monotonic()
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    os.makedirs(_OUT_DIR, exist_ok=True)
    datadir = os.path.join(args.workdir, "datadir")
    servers: list[Server] = []
    failed: list[str] = []
    try:
        # 1. the one process that owns the chip — BEFORE any data is made
        srv = Server(datadir, os.path.join(_OUT_DIR, "serened_1.log"))
        servers.append(srv)
        try:
            srv.start()
        except SmokeFailure as e:
            # nothing is established yet: stderr only, stdout stays empty
            print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
            return 1
        assert "jax" not in sys.modules
        if srv.backend["platform"] != "tpu":
            msg = (f"serened initialised platform "
                   f"{srv.backend['platform']!r} ({srv.backend['kind']}, "
                   f"{srv.backend['count']} device(s)); chip_smoke needs "
                   "a TPU")
            if not args.debug_any_platform:
                print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
                return 1
            failed.append("platform")
            note(f"DEBUG RUN, WILL FAIL: {msg}")
        _evidence_file = open(os.path.join(_OUT_DIR, "chip_smoke.jsonl"),
                              "w")
        sizes = {"hits_rows": max(int(HITS_ROWS * args.scale), 50_000),
                 "docs": max(int(DOCS * args.scale), 5_000),
                 "vectors": max(int(VECTORS * args.scale), 2_000)}
        full = {"hits_rows": HITS_ROWS, "docs": DOCS, "vectors": VECTORS}
        reduced = {k: {"full": full[k], "run": v}
                   for k, v in sizes.items() if v != full[k]}
        emit({"phase": "start", "ok": True, "server": srv.backend,
              "ready_seconds": round(srv.ready_s, 1),
              "compile_cache": srv.cache_dir, "sizes": sizes,
              "reduced": reduced, "seed": args.seed,
              "server_env": SERVER_ENV,
              "expected_off_device": EXPECTED_HOST})

        # 2. data, from the seed
        t0 = time.monotonic()
        paths = {k: os.path.join(args.workdir, f"{k}.parquet")
                 for k in ("hits", "hits_1m", "regions", "docs", "vecs")}
        data = {"hits": gen_hits(args.seed, sizes["hits_rows"],
                                 paths["hits"], paths["regions"],
                                 paths["hits_1m"])}
        note(f"hits generated ({time.monotonic() - t0:.1f}s)")
        data["docs"] = gen_docs(args.seed, sizes["docs"], paths["docs"])
        note(f"docs generated ({time.monotonic() - t0:.1f}s)")
        data["vecs"] = gen_vectors(args.seed, sizes["vectors"],
                                   paths["vecs"])
        emit({"phase": "generate", "ok": True,
              "seconds": round(time.monotonic() - t0, 1),
              "bytes": {k: os.path.getsize(p) for k, p in paths.items()}})
        assert "jax" not in sys.modules

        # 3. first server lifetime: load, query, write
        cache_before = cache_entries(srv.cache_dir)
        run1 = Run(srv, data, "run1")
        run1.load(paths)
        run1.relational()
        run1.bm25_serial()
        run1.vectors()
        run1.bulk_write()
        run1.bulk_read(run1.answers.get("bulk:acked", []))
        run1.bm25_concurrent()
        final = snapshot(srv, run1.pg)
        run1.close()
        failed += run1.failed
        stop_s = srv.stop()
        cache_after_1 = cache_entries(srv.cache_dir)
        emit({"phase": "run1:shutdown", "ok": True,
              "sigterm_to_exit_seconds": round(stop_s, 2),
              "hbm": final["devices"], "gauges_total": final["gauges"],
              "vector_pool": final["vector_pool"],
              "bm25_max_rel_err": run1.worst_bm25,
              "compile_cache_entries": len(cache_after_1),
              "compile_cache_new": len(cache_after_1 - cache_before)})
        alarms = log_alarms(srv.log_path)
        if alarms:
            failed.append("run1:server_log")
            emit({"phase": "run1:server_log", "ok": False,
                  "error": "serened logged device-path alarms",
                  "lines": alarms[:20]})
        if not cache_after_1:
            failed.append("compile_cache_empty")
            emit({"phase": "compile_cache", "ok": False,
                  "error": f"no compile cache entry under {srv.cache_dir} "
                           "after the first server"})

        # 4. restart on the same datadir: same reads, same answers
        srv2 = Server(datadir, os.path.join(_OUT_DIR, "serened_2.log"))
        servers.append(srv2)
        srv2.start()
        check(srv2.backend == srv.backend,
              f"restarted server came up on {srv2.backend}")
        run2 = Run(srv2, data, "run2")
        run2.bulk_read(run1.answers.get("bulk:acked", []))
        run2.relational()
        run2.bm25_serial()
        run2.vectors()
        # the concurrent phase coalesces by arrival time, so its batch
        # shapes are not repeatable: the cache is read before it
        cache_after_2 = cache_entries(srv2.cache_dir)
        run2.bm25_concurrent()
        run2.close()
        failed += run2.failed
        diverged = sorted(k for k in run1.answers
                          if k in run2.answers and
                          run1.answers[k] != run2.answers[k])
        missing = sorted(k for k in run1.answers
                         if k not in run2.answers and k != "bulk:acked")
        new_entries = sorted(cache_after_2 - cache_after_1)
        ok = not diverged and not missing and not new_entries
        emit({"phase": "run2:same_answers_and_cached_compiles", "ok": ok,
              "restart_ready_seconds": round(srv2.ready_s, 1),
              "first_ready_seconds": round(srv.ready_s, 1),
              "answers_compared": len(run1.answers) - 1,
              "diverged": diverged, "not_repeated": missing,
              "compile_cache_new_entries": new_entries[:20],
              "compile_cache_new_count": len(new_entries)})
        if not ok:
            failed.append("run2:same_answers_and_cached_compiles")
        stop2 = srv2.stop()
        alarms = log_alarms(srv2.log_path)
        emit({"phase": "run2:shutdown", "ok": not alarms,
              "sigterm_to_exit_seconds": round(stop2, 2),
              "server_log_alarms": alarms[:20]})
        if alarms:
            failed.append("run2:server_log")
    except SmokeFailure as e:
        failed.append("fatal")
        emit({"phase": "fatal", "ok": False, "error": str(e)[:4000]})
    except Exception as e:  # noqa: BLE001 — reported, then a non-zero exit
        failed.append("fatal")
        emit({"phase": "fatal", "ok": False,
              "error": f"{type(e).__name__}: {e}",
              "traceback": traceback.format_exc()[-4000:]})
    finally:
        for s in servers:
            s.kill()
        shutil.rmtree(args.workdir, ignore_errors=True)
        if _evidence_file is not None:
            _evidence_file.close()

    total = round(time.monotonic() - t_start, 1)
    if failed:
        print(f"chip_smoke: FAIL after {total}s: {failed}", file=sys.stderr)
        return 1
    b = servers[0].backend
    note(f"all phases passed in {total}s")
    print(json.dumps({"ok": True, "device": {
        "platform": b["platform"], "kind": b["kind"],
        "count": b["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
