"""Device telemetry: XLA compile ledger, transfer accounting, and
per-device HBM attribution (`serene_device_telemetry`, ISSUE 15).

PRs 7/9/11 made the device tier the execution flagship — one jitted
dispatch per query over publication-cached HBM columns — but it was the
only tier with no observability of its own: the program cache was an
unbounded bare dict, compiles were invisible, and nothing said which
physical device ran a dispatch or what occupied HBM. This module is the
device tier's nervous system, three ledgers behind one switch:

- **Compile ledger** (`compiled(family, key, builder)`): THE single
  entry point every `jax.jit` site routes through — `device_agg`,
  `device_topn`, `device_pipeline`'s single/build/probe/collective/
  top-N programs, plus the mesh/search/scoring programs — so a grep
  for bare `jax.jit(` outside this file comes back empty. It owns the program
  cache as a BOUNDED LRU (`serene_program_cache_entries`, default 256;
  the PR 7 dict leaked one compiled executable per novel query shape
  for process lifetime) and records per-family compile counts, compile
  wall time (first-call trace: the first invocation of a jitted
  program IS its compile, stamped into the `DeviceCompile` histogram
  and a `device_compile` trace span), hit/miss gauges, and
  recompile-storm detection (one family compiling
  > RECOMPILE_STORM_PER_MIN new shapes per minute → a `device`-topic
  warning + the `DeviceRecompileStorms` gauge — the "your cache key
  churns every query" alarm an ML serving stack fires on retrace
  storms).

- **Transfer + dispatch ledger**: byte/time accounting at every
  host→device commit (`columnar.device.to_device_column`, the
  DEVICE_CACHE typed helpers, the collective stacked-tile commits) and
  device→host fetch (`fetch_all` at the program-output readbacks),
  attributed per physical jax device id, plus per-device dispatch
  counts (stamped from each program invocation's output placement).

- **HBM attribution**: DEVICE_CACHE occupancy split per device (entry
  bytes divided across the devices holding them) — the live-bytes
  estimate `sdb_device()` reports.

Surfaces: `sdb_device()` / `sdb_programs()` / `sdb_device_cache()`
relations (pgcatalog), `GET /device`, the `/_stats` `device` section,
Prometheus gauges + the `DeviceCompile` histogram in `/metrics`, and
the EXPLAIN ANALYZE `Device:` line's `compile=hit|miss` key.

Observe-only contract (the serene_profile/serene_trace discipline):
telemetry NEVER changes which program runs — the LRU is keyed
identically on or off, `compiled()` returns the same executable either
way, and every note_* call is a counter bump. Results are bit-identical
with telemetry on or off at any worker/shard/combine setting
(tests/test_device_obs.py parity matrix; the only behavioral change is
the cache BOUND itself, which can only cause a re-compile of the same
program, never a different one).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Optional

import numpy as np

from ..utils import log, metrics
from ..utils.config import REGISTRY as _settings
from .trace import current_trace, stage

#: new compiles of ONE family within a 60s window that trip the
#: recompile-storm warning (a healthy steady state compiles each query
#: shape once and hits forever after)
RECOMPILE_STORM_PER_MIN = 8
_STORM_WINDOW_S = 60.0
#: storms re-warn at most this often per family (the log is a signal,
#: not a flood)
_STORM_RELOG_S = 30.0


def enabled() -> bool:
    """One registry read — the whole module keys off this switch."""
    try:
        return bool(_settings.get_global("serene_device_telemetry"))
    except KeyError:  # pragma: no cover — registry declares it
        return True


def _cap() -> int:
    try:
        return max(1, int(_settings.get_global(
            "serene_program_cache_entries")))
    except KeyError:  # pragma: no cover — registry declares it
        return 256


# -- device-id helpers --------------------------------------------------------


def array_device_ids(arr) -> tuple:
    """Physical device ids holding a jax array (sorted; () when the
    placement cannot be read — accounting degrades, never raises)."""
    try:
        devs = arr.devices()                  # jax.Array: set of Device
        return tuple(sorted(d.id for d in devs))
    except Exception:  # noqa: BLE001 — older array types / numpy
        dev = getattr(arr, "device", None)
        if dev is not None and not callable(dev) and hasattr(dev, "id"):
            return (int(dev.id),)
    return ()


def value_device_ids(value) -> tuple:
    """Device ids of a cached value: a DeviceColumn (its data tiles), a
    tuple of arrays (union), or one array."""
    data = getattr(value, "data", None)
    if data is not None and hasattr(value, "mask"):    # DeviceColumn
        return array_device_ids(data)
    if isinstance(value, (tuple, list)):
        ids: set = set()
        for v in value:
            ids.update(value_device_ids(v))
        return tuple(sorted(ids))
    return array_device_ids(value)


def _first_jax_leaf(out):
    if isinstance(out, (tuple, list)):
        for e in out:
            leaf = _first_jax_leaf(e)
            if leaf is not None:
                return leaf
        return None
    return out if hasattr(out, "devices") or hasattr(out, "device") \
        else None


# -- transfer + dispatch ledger ----------------------------------------------


_DEV_FIELDS = ("dispatches", "bytes_up", "transfers_up", "up_ns",
               "bytes_down", "transfers_down", "down_ns")


class DeviceLedger:
    """Per-physical-device counters: dispatches executed, bytes/time
    moved host→device (uploads + stacked commits) and device→host
    (result fetches). Multi-device commits (mesh shardings, replicated
    build outputs) split bytes evenly across the participating devices
    — an attribution, not a wire measurement."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dev: dict[int, dict] = {}

    def _slot(self, did: int) -> dict:
        d = self._dev.get(did)
        if d is None:
            d = self._dev[did] = {f: 0 for f in _DEV_FIELDS}
        return d

    def note_dispatch(self, ids) -> None:
        with self._lock:
            for i in (ids or (0,)):
                self._slot(int(i))["dispatches"] += 1

    def note_upload(self, nbytes: int, ids, ns: int = 0) -> None:
        ids = ids or (0,)
        share = len(ids)
        with self._lock:
            for i in ids:
                s = self._slot(int(i))
                s["bytes_up"] += int(nbytes) // share
                s["transfers_up"] += 1
                s["up_ns"] += int(ns) // share

    def note_fetch(self, nbytes: int, ids, ns: int = 0) -> None:
        ids = ids or (0,)
        share = len(ids)
        with self._lock:
            for i in ids:
                s = self._slot(int(i))
                s["bytes_down"] += int(nbytes) // share
                s["transfers_down"] += 1
                s["down_ns"] += int(ns) // share

    def snapshot(self) -> dict[int, dict]:
        with self._lock:
            return {i: dict(v) for i, v in self._dev.items()}

    def reset(self) -> None:
        with self._lock:
            self._dev.clear()


LEDGER = DeviceLedger()


def note_upload(nbytes: int, ids, ns: int = 0) -> None:
    """Host→device transfer accounting choke point (observe-only; no-op
    when telemetry is off)."""
    if enabled():
        LEDGER.note_upload(nbytes, ids, ns)
        metrics.DEVICE_TRANSFERS_UP.add()


def note_fetch(nbytes: int, ids, ns: int = 0) -> None:
    if enabled():
        LEDGER.note_fetch(nbytes, ids, ns)
        metrics.DEVICE_FETCH_BYTES.add(int(nbytes))


def fetch_all(outs) -> list:
    """Device→host readback of a program's outputs (the np.asarray
    choke point): returns numpy arrays, accounting bytes/time per
    device. This is where the host blocks until the device is done, so
    it is the request's `device_wait` stage (device execution + D2H).
    Conversion is what every call site did anyway — telemetry adds only
    the clock reads and one ledger bump."""
    with stage("device_wait"):
        if not enabled():
            return [np.asarray(o) for o in outs]
        leaf = _first_jax_leaf(outs)
        ids = array_device_ids(leaf) if leaf is not None else ()
        t0 = time.perf_counter_ns()
        arrs = [np.asarray(o) for o in outs]
        # direct ledger calls — the enabled() gate already ran above, and
        # re-checking inside note_fetch would take the settings-registry
        # lock a second time on the per-dispatch hot path
        nbytes = sum(int(a.nbytes) for a in arrs)
        LEDGER.note_fetch(nbytes, ids, time.perf_counter_ns() - t0)
        metrics.DEVICE_FETCH_BYTES.add(nbytes)
        return arrs


def dispatch(prog, args, profile=None, node_key=None,
             t0_ns: Optional[int] = None) -> list:
    """One program call and the blocking readback of its outputs —
    what `DeviceDispatch` and `QueryProfile.device_ns` (EXPLAIN
    ANALYZE's `Device: time=`) mean at every site: enqueue start →
    readback done. A chain whose first stage left its outputs in HBM
    passes that stage's enqueue start as `t0_ns`."""
    t0 = time.perf_counter_ns() if t0_ns is None else t0_ns
    arrs = fetch_all(prog(*args))
    ns = time.perf_counter_ns() - t0
    # unconditional: the device latency signal survives profiling and
    # tracing being off (two clock reads per ms-scale offload)
    metrics.DEVICE_DISPATCH_HIST.observe_ns(ns)
    if profile is not None and node_key is not None:
        profile.add_device_ns(node_key, ns)
    return arrs


def commit(x, target=None):
    """`jax.device_put` with upload accounting — the direct-commit
    sites that bypass DEVICE_CACHE (the sharded search merge's
    candidate planes, a program call's host operands). `x` is one
    array or a tuple of them, put in one call and accounted as one
    ledger entry of their summed bytes."""
    import jax
    if not enabled():
        return jax.device_put(x, target)
    t0 = time.perf_counter_ns()
    out = jax.device_put(x, target)
    arrs = out if isinstance(out, tuple) else (out,)
    LEDGER.note_upload(
        sum(int(a.size * a.dtype.itemsize) for a in arrs),
        array_device_ids(arrs[0]),
        time.perf_counter_ns() - t0)
    metrics.DEVICE_TRANSFERS_UP.add(len(arrs))
    return out


#: what `CompiledProgram.__call__` commits before the call; anything
#: else (a `jax.Array`, None, a pytree) is the jitted function's own
_HOST_OPERAND = (np.ndarray, np.generic, float, int)

#: a Python scalar operand (k1, b, avgdl: the same few values call after
#: call) is committed ONCE per value and kept: on the chip every
#: transfer costs the host about 0.2 ms whatever its size (PERF.md §6,
#: PR 35), so three floats a call were most of a call. Weak-typed like
#: the Python value, so the program traced with one is the program
#: called with the other.
_SCALARS: dict = {}
_SCALARS_MAX = 256


# -- provider-token naming (sdb_device_cache's table column) ------------------

_TOKEN_NAMES: dict[int, str] = {}
_TOKEN_NAMES_MAX = 1024
_token_names_lock = threading.Lock()


def note_provider(token: int, name: str) -> None:
    """Remember a publication token's table name (DEVICE_CACHE keys
    carry only the token; the relation surface wants the name). Bounded
    FIFO — tokens are minted per provider OBJECT, so DROP+CREATE churn
    would otherwise grow this for process lifetime (the exact
    leak-per-novel-key shape this PR fixes in the program cache)."""
    if _TOKEN_NAMES.get(token) != name:
        with _token_names_lock:
            while len(_TOKEN_NAMES) >= _TOKEN_NAMES_MAX:
                _TOKEN_NAMES.pop(next(iter(_TOKEN_NAMES)))
            _TOKEN_NAMES[token] = str(name)


def provider_name(token: int) -> str:
    return _TOKEN_NAMES.get(token, "")


# -- compile ledger -----------------------------------------------------------


class CompiledProgram:
    """One ledger-owned jitted program. The FIRST invocation of a jit
    wrapper is its trace+compile; this wrapper times it (the tiny-input
    warm-call school of compile measurement: wall time of call #1),
    feeds the `DeviceCompile` histogram + family stats, stamps a
    `device_compile` trace span so flight-recorder timelines attribute
    first-query compile stalls, and counts a per-device dispatch on
    every call.

    A call's HOST operands — numpy arrays, numpy and Python scalars —
    are committed to the device before the call, under the request's
    `device_upload` stage, in one `commit` of the tuple; a Python
    scalar crosses once per value and is kept (`_SCALARS`; never put
    into a donated position). The jitted function sees `jax.Array`s
    only, so `device_enqueue` is the call returning on resident
    operands. The same bytes cross as inside the call, in at most as
    many transfers; nothing blocks. Steady-state overhead is one
    isinstance pass over the operands, two clock reads and one
    enabled() check per call."""

    __slots__ = ("fn", "family", "compile_ns", "_timed", "_donated")

    def __init__(self, fn: Callable, family: str,
                 donate_argnums: tuple = ()):
        self.fn = fn
        self.family = family
        self.compile_ns: Optional[int] = None
        self._timed = False
        self._donated = frozenset(donate_argnums)

    @property
    def called(self) -> bool:
        """Has run at least once, so its first shape is built."""
        return self._timed

    def _upload(self, args: tuple) -> tuple:
        """`args` with every host operand replaced, in place, by a
        device array."""
        host = [i for i, a in enumerate(args)
                if isinstance(a, _HOST_OPERAND)]
        if not host:
            return args
        args = list(args)
        put, keys = [], {}
        for i in host:
            a = args[i]
            # a Python scalar is kept once it has crossed — but a donated
            # position would invalidate the kept buffer: put afresh there
            if isinstance(a, (float, int)) and i not in self._donated:
                keys[i] = (type(a), a)
                kept = _SCALARS.get(keys[i])
                if kept is not None:
                    args[i] = kept
                    continue
            put.append(i)
        if put:
            with stage("device_upload", family=self.family):
                arrs = commit(tuple(args[i] for i in put))
                for i, arr in zip(put, arrs):
                    if i in keys:
                        if len(_SCALARS) >= _SCALARS_MAX:
                            _SCALARS.clear()   # values that never repeat
                        _SCALARS[keys[i]] = arr
                    args[i] = arr
        return tuple(args)

    def __call__(self, *args):
        # first call: benign race — two threads may both time; the
        # ledger records both observations, results are identical
        first, self._timed = not self._timed, True
        t0 = time.perf_counter_ns()
        args = self._upload(args)
        # the call returning IS the request's `device_enqueue` stage
        # (the first call: trace + compile + enqueue)
        with stage("device_enqueue", family=self.family):
            out = self.fn(*args)
        ns = time.perf_counter_ns() - t0
        if not first:
            # whatever the switches: what one call costs the host
            metrics.DEVICE_ENQUEUE_CALL_HIST.observe_ns(ns)
        if not enabled():
            return out
        if first:
            self.compile_ns = ns
            PROGRAMS.record_compile_time(self.family, ns)
            tr = current_trace()
            if tr is not None:
                tr.add("device_compile", "device", t0, t0 + ns,
                       family=self.family)
        leaf = _first_jax_leaf(out)
        LEDGER.note_dispatch(
            array_device_ids(leaf) if leaf is not None else ())
        return out


def _named(family: str, body: Callable) -> Callable:
    """The program body traced under `jax.named_scope(family)` and
    called after its family, so a profiler trace's module and name-scope
    lines say `jit_device_agg` / `fused_topn/...` instead of
    `jit_program` / `%fusion`."""
    import jax

    def program(*args):
        with jax.named_scope(family):
            return body(*args)
    program.__name__ = program.__qualname__ = family
    return program


_announced = threading.local()


@contextlib.contextmanager
def announced_builds():
    """The programs this thread looks up inside are a closed set built
    ahead of the queries (an index build's `prebuild`), not shapes a
    churning cache key keeps producing: their compiles are counted like
    any other but stay out of the recompile-storm window."""
    _announced.on = True
    try:
        yield
    finally:
        _announced.on = False


def _new_family() -> dict:
    return {"entries": 0, "compiles": 0, "hits": 0, "misses": 0,
            "evictions": 0, "compile_ns": 0, "timed": 0,
            "last_compile_ns": 0, "storms": 0}


class ProgramLedger:
    """THE process-wide program cache (the `_PROGRAM_CACHE` successor):
    a bounded LRU of CompiledProgram wrappers keyed by
    (family, site key), plus per-family compile statistics. The bound
    fixes the PR 7 leak — before this, every novel (publication, query
    shape) pair pinned a compiled XLA executable for process lifetime —
    and eviction genuinely frees: dropping the wrapper drops the jit
    object, and a re-request re-compiles through the same builder."""

    def __init__(self):
        self._lock = threading.Lock()
        self._progs: "OrderedDict[tuple, CompiledProgram]" = OrderedDict()
        self._fams: dict[str, dict] = {}
        self._storm_t: dict[str, deque] = {}
        self._storm_warned: dict[str, float] = {}

    def _fam(self, family: str) -> dict:
        f = self._fams.get(family)
        if f is None:
            f = self._fams[family] = _new_family()
        return f

    def get(self, family: str, key: tuple, builder: Callable,
            profile=None, node_key=None,
            donate_argnums: tuple = ()) -> CompiledProgram:
        on = enabled()
        full = (family, key)
        with self._lock:
            prog = self._progs.get(full)
            if prog is not None:
                self._progs.move_to_end(full)
                if on:
                    self._fam(family)["hits"] += 1
                    metrics.DEVICE_PROGRAM_HITS.add()
                    if profile is not None and node_key is not None:
                        profile.stats(node_key).device_prog_hits += 1
                return prog
        # build OUTSIDE the lock: jit-wrapper creation is cheap but the
        # builder may construct meshes/shard_maps; a racing duplicate
        # build is wasted work, never wrong (the loser is discarded)
        import jax
        prog = CompiledProgram(
            jax.jit(_named(family, builder()),
                    donate_argnums=donate_argnums), family,
            donate_argnums)
        with self._lock:
            cur = self._progs.get(full)
            if cur is not None:
                self._progs.move_to_end(full)
                if on:
                    self._fam(family)["hits"] += 1
                    metrics.DEVICE_PROGRAM_HITS.add()
                    if profile is not None and node_key is not None:
                        profile.stats(node_key).device_prog_hits += 1
                return cur
            self._progs[full] = prog
            if on:
                fam = self._fam(family)
                fam["misses"] += 1
                fam["compiles"] += 1
                metrics.DEVICE_PROGRAM_MISSES.add()
                metrics.DEVICE_PROGRAMS_COMPILED.add()
                if profile is not None and node_key is not None:
                    profile.stats(node_key).device_prog_misses += 1
                if not getattr(_announced, "on", False):
                    self._note_storm(family, fam)
            cap = _cap()
            # the cap is STRUCTURAL (it bounds HBM/host memory) and
            # applies with telemetry off too — but dark means dark:
            # the stats/gauges move only when the switch is on, so the
            # surfaces can never show evictions against frozen misses
            while len(self._progs) > cap:
                (efam, _ekey), _ = self._progs.popitem(last=False)
                if on:
                    metrics.DEVICE_PROGRAM_EVICTIONS.add()
                    self._fam(efam)["evictions"] += 1
            if on:
                metrics.DEVICE_PROGRAM_ENTRIES.set(len(self._progs))
        return prog

    def _note_storm(self, family: str, fam: dict) -> None:
        """Called under self._lock on every miss-compile: a family
        re-compiling > RECOMPILE_STORM_PER_MIN new shapes per minute
        means repeat queries are NOT reusing executables (a churning
        cache key — the retrace-storm failure mode of ML serving)."""
        now = time.monotonic()
        dq = self._storm_t.get(family)
        if dq is None:
            dq = self._storm_t[family] = deque()
        dq.append(now)
        while dq and now - dq[0] > _STORM_WINDOW_S:
            dq.popleft()
        if len(dq) > RECOMPILE_STORM_PER_MIN and \
                now - self._storm_warned.get(family, -1e18) >= \
                _STORM_RELOG_S:
            self._storm_warned[family] = now
            fam["storms"] += 1
            metrics.DEVICE_RECOMPILE_STORMS.add()
            log.warn("device",
                     f"recompile storm: program family '{family}' "
                     f"compiled {len(dq)} new shapes in the last 60s — "
                     "repeat queries are not reusing cached executables "
                     "(churning cache key, or serene_program_cache_"
                     "entries too small for the live query mix)")

    def record_compile_time(self, family: str, ns: int) -> None:
        with self._lock:
            f = self._fam(family)
            f["compile_ns"] += int(ns)
            f["timed"] += 1
            f["last_compile_ns"] = int(ns)
        metrics.DEVICE_COMPILE_HIST.observe_ns(ns)

    def entries(self) -> int:
        with self._lock:
            return len(self._progs)

    def snapshot(self) -> list[dict]:
        """One row per program family, sorted — the sdb_programs()
        relation body."""
        with self._lock:
            per_fam_entries: dict[str, int] = {}
            for fam, _k in self._progs:
                per_fam_entries[fam] = per_fam_entries.get(fam, 0) + 1
            rows = []
            for fam in sorted(self._fams):
                f = self._fams[fam]
                rows.append({
                    "family": fam,
                    "entries": per_fam_entries.get(fam, 0),
                    "compiles": f["compiles"],
                    "hits": f["hits"],
                    "misses": f["misses"],
                    "evictions": f["evictions"],
                    "storms": f["storms"],
                    "compile_ms_total": round(f["compile_ns"] / 1e6, 3),
                    "compile_ms_mean": round(
                        f["compile_ns"] / max(f["timed"], 1) / 1e6, 3),
                    "last_compile_ms": round(
                        f["last_compile_ns"] / 1e6, 3)})
        return rows

    def family(self, name: str) -> dict:
        with self._lock:
            return dict(self._fams.get(name) or _new_family())

    def clear(self) -> None:
        """Drop every cached program AND the family statistics (tests /
        bench cold-compile measurement)."""
        with self._lock:
            self._progs.clear()
            self._fams.clear()
            self._storm_t.clear()
            self._storm_warned.clear()
            metrics.DEVICE_PROGRAM_ENTRIES.set(0)


PROGRAMS = ProgramLedger()


def compiled(family: str, key: tuple, builder: Callable, *,
             profile=None, node_key=None,
             donate_argnums: tuple = ()) -> CompiledProgram:
    """THE jit entry point (acceptance grep: no bare `jax.jit(` outside
    this module). `builder` is a zero-arg callable returning the python
    callable to jit (a traced program body, or a shard_map-wrapped
    one); it runs only on a ledger miss. `profile`/`node_key` stamp the
    hit/miss onto the plan operator so EXPLAIN ANALYZE's `Device:` line
    can say `compile=hit|miss`. `donate_argnums` names the positional
    arguments whose buffers the program may update in place (the BM25
    score planes: same shape in and out, so the donation is usable)."""
    return PROGRAMS.get(family, key, builder, profile=profile,
                        node_key=node_key, donate_argnums=donate_argnums)


# -- fused-tier decline accounting -------------------------------------------

#: reason slug → count of fused-tier declines (queries that fell back
#: to the host path and why) — the satellite-1 diagnosis surface
_FUSED_DECLINES: dict[str, int] = {}
_fused_declines_lock = threading.Lock()


def note_fused_decline(reason: str, profile=None, node_key=None) -> None:
    """One fused-tier fallback: count it per reason slug (bounded
    vocabulary — call sites pass short category strings, never query
    text), bump the per-reason `DeviceFusedDeclines_<reason>` gauge,
    and stamp the reason onto the plan operator so EXPLAIN ANALYZE's
    `Device:` line can say `declined=<reason>`."""
    reason = str(reason)[:64]
    with _fused_declines_lock:
        _FUSED_DECLINES[reason] = _FUSED_DECLINES.get(reason, 0) + 1
    metrics.REGISTRY.gauge(
        f"DeviceFusedDeclines_{reason}",
        "fused device pipeline declines for this reason (query fell "
        "back to the host path)").add()
    if profile is not None and node_key is not None:
        profile.stats(node_key).device_declined = reason


def fused_declines() -> dict[str, int]:
    with _fused_declines_lock:
        return dict(sorted(_FUSED_DECLINES.items()))


# -- surfaces -----------------------------------------------------------------


def device_rows() -> list[dict]:
    """One row per physical device: dispatches, transfer bytes/time
    up/down, the HBM live-bytes estimate (DEVICE_CACHE occupancy —
    column tiles, code tiles, row masks, cached build outputs — split
    per holding device) and, beside it, the bytes in use and the limit
    as the backend's own `memory_stats()` reports them (None where it
    reports none). Lists every jax device when a backend is
    already initialized (PASSIVE probe — a pure-host process must not
    pay backend init for a stats read), else only devices the ledger
    has seen."""
    from ..exec.device_pipeline import DEVICE_CACHE
    from ..parallel import mesh as mesh_mod
    from ..search.vector_store import VPOOL
    cache_bytes = DEVICE_CACHE.device_bytes()
    # the vector pool's paged region is HBM-live alongside the column
    # cache — one estimate covers every tenant
    for i, n in VPOOL.device_bytes().items():
        cache_bytes[i] = cache_bytes.get(i, 0) + n
    snap = LEDGER.snapshot()
    devs = {}
    if mesh_mod.device_count_if_initialized():
        import jax
        devs = {d.id: d for d in jax.devices()}
    ids = sorted(set(snap) | set(cache_bytes) | set(devs))
    zeros = {f: 0 for f in _DEV_FIELDS}
    rows = []
    for i in ids:
        s = snap.get(i, zeros)
        d = devs.get(i)
        # what the backend itself reports for the device's memory (the
        # CPU backend reports nothing → NULL): the measured side of
        # hbm_bytes_est, which only sums what this engine put there
        mem = (d.memory_stats() or {}) if d is not None else {}
        rows.append({
            "device": i,
            "platform": getattr(d, "platform", ""),
            "kind": getattr(d, "device_kind", ""),
            "dispatches": s["dispatches"],
            "bytes_up": s["bytes_up"],
            "transfers_up": s["transfers_up"],
            "up_ms": round(s["up_ns"] / 1e6, 3),
            "bytes_down": s["bytes_down"],
            "transfers_down": s["transfers_down"],
            "down_ms": round(s["down_ns"] / 1e6, 3),
            "hbm_bytes_est": cache_bytes.get(i, 0),
            "hbm_bytes_in_use": mem.get("bytes_in_use"),
            "hbm_bytes_limit": mem.get("bytes_limit")})
    return rows


def device_cache_rows() -> list[dict]:
    """One row per DEVICE_CACHE entry with the provider token resolved
    to its table name — the per-publication/column HBM occupancy view."""
    from ..exec.device_pipeline import DEVICE_CACHE
    rows = DEVICE_CACHE.snapshot()
    for r in rows:
        r["table"] = provider_name(r["token"])
    return rows


def stats_section() -> dict:
    """The `/_stats` / `GET /device` JSON payload: per-device ledger
    rows, the compile ledger, and the program/column cache summaries."""
    from ..exec.device_pipeline import DEVICE_CACHE
    from ..search.vector_store import VPOOL
    return {"devices": device_rows(),
            "programs": PROGRAMS.snapshot(),
            "program_cache": {"entries": PROGRAMS.entries(),
                              "cap": _cap()},
            "column_cache": DEVICE_CACHE.stats(),
            "vector_pool": VPOOL.stats(),
            "fused_declines": fused_declines()}
