"""Fused device-side relational execution — one dispatch per query.

PAPER.md §2's core claim is "one dispatch per query, not one kernel per
operator". The single-table half of that already exists (exec/device_agg.py
fuses Scan→Filter→Aggregate); this module extends the discipline across the
relational tier: a Scan→Filter→Join→Aggregate chain compiles into ONE jitted
JAX program over device-resident columns of BOTH tables, and a filtered
top-N (Sort+Limit over Filter→Scan) into one masked `top_k` dispatch. A
chain of primary-key joins of any length is exec/device_chain.py's.

Join representation (the PR-3 trick, moved on device): both sides' equi-keys
factorize host-side into ONE dense int64 code space
(exec/morsel.combined_codes — NULL keys masked to a per-side sentinel so
NULL never matches, every NaN occurrence its own code so NaN ≠ NaN, exactly
the row-tuple oracle's semantics). The codes upload as int32 tiles and the
probe happens *inside* the program as pure gathers: the build side scatters
per-code partials (count / limb sums / min / max), every probe row gathers
its code's partial and scatters it into the group accumulator — no pair list
ever materializes, on host or device. The fused-kernel shape mirrors
FLASH-MAXSIM's IO-aware late-interaction kernels and Ragged Paged
Attention's one-program-over-resident-data design (PAPERS.md).

Exactness policy (PG parity, x64 off): only integer/bool/date/DECIMAL
aggregate arguments compile (a DECIMAL's SUM/AVG/MIN/MAX aggregate its
scaled int64 as the BIGINT it is, `decimal_raw`, and every node of a
DECIMAL expression is bounded inside int32 first, `device.expr_bounds`)
— int sums ride the 8-bit limb decomposition of
ops/agg.py, weighted by the per-row match count (or ONE direct int32
scatter column when the argument is a plain column whose value bound
times the worst-case pair count provably fits int32), and the whole
plan is admitted only while the worst-case pair count keeps every int32
limb accumulator exact (`MAX_PAIRS_EXACT`). Float arguments, DISTINCT, FILTER
clauses, residual predicates and non-inner joins fall back to the host
oracle, which stays on as the bit-identical parity reference behind
`SET serene_device_fused = off` (the serene_join_vectorized=off pattern).

Transfers: uploads go through DEVICE_CACHE, a process-wide bytes-bounded
cache keyed by the PR-5 publication tuples (provider token, data_version,
mutation_epoch) + column + surviving row range — a repeat query on an
unchanged table skips host→device transfer entirely, and any write moves
the key. Zone maps bound what uploads at all: each side's scan-level
conjuncts shrink the transfer to the surviving block envelope
(device_agg's `_zonemap_range` logic, applied per join side).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Optional

import jax
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Batch, Column
from ..columnar.device import (DeviceColumn, DeviceNarrowingError, LANES,
                               pad_len, to_device_column)
from ..ops import agg as ops_agg
from ..obs import device as obs_device
from ..sql.binder import _expr_key
from ..sql.expr import AggSpec, BoundColumn, BoundExpr, BoundFunc
from ..obs.trace import span, stage
from ..utils import log, metrics
from ..utils.config import REGISTRY as _settings_registry
from .device import DeviceExpr, NotCompilable, compile_expr
from .device_agg import MAX_GROUP_PRODUCT, MAX_INT_KEY_RANGE

#: combined join-key code-space cap (dense per-code arrays live in HBM)
MAX_CODE_SPACE = 1 << 22
#: worst-case matched-pair bound under which every int32 limb/count
#: scatter in the program is provably exact (255 * pairs < 2^31)
MAX_PAIRS_EXACT = 1 << 23

_AGG_FUNCS = {"count_star", "count", "sum", "min", "max", "avg"}

#: expressions whose host-side evaluation draws shared mutable state or
#: runs a subplan — pre-evaluating them over unfiltered rows would
#: double-draw / reorder effects (same list the morsel tier excludes)
_HOST_EVAL_UNSAFE = {
    "scalar_subquery", "array_subquery", "in_subquery", "exists",
    "currval", "lastval"}


def fused_enabled(settings) -> bool:
    try:
        return bool(settings.get("serene_device_fused"))
    except KeyError:  # pragma: no cover — registry always declares it
        return False


def fused_ext_enabled(settings) -> bool:
    """PR 17 extended admission (strings/DISTINCT/FILTER/residual/outer
    joins + chained stage handoff); off restores the PR 7 walls."""
    try:
        return bool(settings.get("serene_device_fused_ext"))
    except KeyError:  # pragma: no cover
        return False


def _pow2_rows(n: int) -> int:
    """pow2 row bucket (floor BLOCK_ROWS): every upload in the fused
    path pads to this, so the number of DISTINCT traced shapes per
    program family grows O(log rows) instead of O(rows / BLOCK_ROWS) —
    the admission-wall removals multiply program axes, and without the
    bucketing that product would storm the compile ledger."""
    b = 1024
    while b < n:
        b <<= 1
    return b


def _pow2_int(n: int, floor: int = 8) -> int:
    """pow2 bucket for non-row axes (DISTINCT value spaces): same
    compile-storm rationale as _pow2_rows, smaller floor."""
    b = floor
    while b < n:
        b <<= 1
    return b


# -- publication-keyed device column cache ----------------------------------


def _pub(provider, pin) -> tuple:
    """(provider token, data_version, mutation_epoch) — the PR-5
    publication tuple. The token is process-unique per provider object,
    so DROP + CREATE can never alias generations."""
    from ..cache.result import _provider_token
    if pin is not None:
        return (_provider_token(provider), pin[1], pin[2])
    return (_provider_token(provider),
            getattr(provider, "data_version", 0),
            getattr(provider, "mutation_epoch", 0))


def _charge_upload(nbytes: int) -> None:
    """Per-query attribution of a host→device transfer: the statement
    that caused the upload records the bytes in its accounted peak
    (obs/resources; no-op when `serene_mem_account` is off or the
    upload happens outside a statement)."""
    from ..obs.resources import charge_device_upload
    charge_device_upload(nbytes)


class DeviceColumnCache:
    """Process-wide cache of device-resident arrays keyed by publication
    tuples. An entry's key embeds (token, data_version, mutation_epoch)
    + column + row range, so invalidation is implicit: any write bumps
    the publication and the next query keys past the stale upload. Bytes
    are bounded by the serene_device_cache_mb global (LRU past the cap);
    superseded generations of a token are swept eagerly on store so HBM
    never holds two versions of one column."""

    def __init__(self):
        #: key -> [value, nbytes, device ids, hits, last-touch epoch s]
        self._entries: OrderedDict[tuple, list] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()

    @staticmethod
    def _trade_on() -> bool:
        try:
            return bool(_settings_registry.get_global(
                "serene_device_cache_trade"))
        except KeyError:  # pragma: no cover
            return False

    def _cap_bytes(self) -> int:
        """Byte cap of THIS side of the device budget. With the
        pressure trade on, the cap is the serene_device_cache_mb
        envelope minus the vector pool's LIVE page bytes, floored at a
        quarter of the envelope — the pool's residency squeezes the
        column cache instead of a static carve-out, and vice versa via
        shed_colder. Consults the pool's lock, so call it OUTSIDE
        self._lock (the only cross-lock order is cache-unlocked →
        pool; the pool never calls into this cache)."""
        try:
            mb = int(_settings_registry.get_global("serene_device_cache_mb"))
        except KeyError:  # pragma: no cover
            mb = 256
        env = mb << 20
        if self._trade_on():
            try:
                from ..search.vector_store import VPOOL
                return max(env // 4, env - VPOOL.live_bytes())
            except Exception:  # noqa: BLE001 — sizing only, never fatal
                pass
        return env

    def get(self, key: tuple):
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                metrics.DEVICE_CACHE_MISSES.add()
                return None
            self._entries.move_to_end(key)
            entry[3] += 1
            entry[4] = time.time()
            metrics.DEVICE_CACHE_HITS.add()
            return entry[0]

    def put(self, key: tuple, value, nbytes: int, sweep=None) -> None:
        """Store + LRU/byte bookkeeping. `sweep(k) -> bool` lets a
        caller mark extra keys as superseded (e.g. code tiles whose
        staleness comes from the PARTNER table's publication, which the
        owner-generation rule below cannot see)."""
        dev_ids = obs_device.value_device_ids(value) \
            if obs_device.enabled() else ()
        cap = self._cap_bytes()        # pool consult happens pre-lock
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[1]
            # sweep superseded generations: same (token, name, tag) under
            # an older publication can never be read again
            token, name = key[0][0], key[1]
            stale = [k for k in self._entries
                     if (k[0][0] == token and k[1] == name and
                         k[0] != key[0]) or
                     (sweep is not None and k != key and sweep(k))]
            for k in stale:
                self._bytes -= self._entries.pop(k)[1]
                metrics.DEVICE_CACHE_EVICTIONS.add()
            self._entries[key] = [value, nbytes, dev_ids, 0, time.time()]
            self._bytes += nbytes
            over = self._bytes - cap
            tail_idle_s = None
            if over > 0:
                for e in self._entries.values():
                    tail_idle_s = time.time() - e[4]
                    break
        if over > 0 and self._trade_on() and tail_idle_s is not None:
            # pressure trade: before shedding our own tail, offer the
            # eviction to the vector pool's tail if it is idler than
            # ours — freed pages raise this cache's cap directly
            try:
                from ..search.vector_store import VPOOL
                pool_idle = VPOOL.tail_idle_ns()
                if pool_idle is not None and \
                        pool_idle > tail_idle_s * 1e9 and \
                        VPOOL.shed_colder(int(tail_idle_s * 1e9), over):
                    cap = self._cap_bytes()
            except Exception:  # noqa: BLE001 — sizing only, never fatal
                pass
        with self._lock:
            while self._bytes > cap and len(self._entries) > 1:
                _, e = self._entries.popitem(last=False)
                self._bytes -= e[1]
                metrics.DEVICE_CACHE_EVICTIONS.add()
            metrics.DEVICE_CACHE_BYTES.set(self._bytes)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0
            metrics.DEVICE_CACHE_BYTES.set(0)

    # -- telemetry surfaces (obs/device.py) ---------------------------------

    def stats(self) -> dict:
        cap = self._cap_bytes()        # pool consult happens pre-lock
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "cap_bytes": cap}

    def device_bytes(self) -> dict[int, int]:
        """HBM occupancy estimate per device id: each entry's bytes
        split across the devices holding it (mesh-sharded commits land
        on several). Entries stored with telemetry off carry no
        placement and attribute to the default device 0."""
        out: dict[int, int] = {}
        with self._lock:
            for e in self._entries.values():
                ids = e[2] or (0,)
                share = len(ids)
                for i in ids:
                    out[i] = out.get(i, 0) + e[1] // share
        return out

    def snapshot(self) -> list[dict]:
        """One row per live entry — the sdb_device_cache() body: which
        publication/column occupies HBM, how big, on which devices, how
        recently touched."""
        now = time.time()
        with self._lock:
            rows = []
            for (pub, name, kind, tag), e in self._entries.items():
                rows.append({
                    "token": pub[0], "data_version": pub[1],
                    "mutation_epoch": pub[2], "column": name,
                    "kind": kind, "tag": repr(tag)[:120],
                    "bytes": e[1],
                    "devices": ",".join(str(i) for i in e[2]),
                    "hits": e[3],
                    "idle_ms": round((now - e[4]) * 1e3, 1)})
        return rows

    # -- typed helpers ------------------------------------------------------

    def column(self, provider, pub: tuple, name: str, host_col_fn,
               zrange: Optional[tuple], pad: Optional[int] = None):
        """Device tiles of one column (optionally row-sliced), cached by
        (publication, column, range). host_col_fn() materializes the host
        column only on miss. `pad` pads rows to that multiple (the fused
        tier's pow2 bucket) and keys a DISTINCT entry, so other tiers'
        cached shapes are untouched."""
        obs_device.note_provider(pub[0], getattr(provider, "name", ""))
        key = (pub, name, "col", zrange if pad is None
               else (zrange, "pad", pad))
        dc = self.get(key)
        if dc is not None:
            return dc
        col = host_col_fn()
        if zrange is not None:
            col = col.slice(zrange[0], zrange[1])
        if pad is None:
            dc = to_device_column(col)  # upload accounted at the funnel
        else:
            dc = to_device_column(col, pad_multiple=pad)
        nbytes = int(dc.data.size * dc.data.dtype.itemsize) + \
            int(dc.mask.size)
        metrics.DEVICE_BYTES.add(nbytes)
        _charge_upload(nbytes)
        self.put(key, dc, nbytes)
        return dc

    def array(self, pub: tuple, name: str, tag, build_fn, sweep=None,
              device=None):
        """Generic cached device array (code tiles, row masks). `device`
        commits the array to a specific mesh device (the sharded tier's
        data-axis placement); callers embed the shard id in `tag`, so
        placement is a pure function of the key."""
        key = (pub, name, "arr", tag)
        arr = self.get(key)
        if arr is not None:
            return arr
        t0 = time.perf_counter_ns()
        arr = build_fn()
        if device is not None:
            arr = jax.device_put(arr, device)
        nbytes = int(arr.size * arr.dtype.itemsize)
        metrics.DEVICE_BYTES.add(nbytes)
        obs_device.note_upload(nbytes, obs_device.array_device_ids(arr),
                               time.perf_counter_ns() - t0)
        _charge_upload(nbytes)
        self.put(key, arr, nbytes, sweep=sweep)
        return arr

    def tuple_arrays(self, pub: tuple, name: str, tag, build_fn,
                     sweep=None):
        """Cached tuple of device arrays under ONE key (the sharded
        tier's build-phase outputs: bacc + min/max partials) — a repeat
        query skips the build dispatch and its transfer entirely."""
        key = (pub, name, "arr", tag)
        val = self.get(key)
        if val is not None:
            return val
        t0 = time.perf_counter_ns()
        val = tuple(build_fn())
        nbytes = sum(int(a.size * a.dtype.itemsize) for a in val)
        metrics.DEVICE_BYTES.add(nbytes)
        obs_device.note_upload(nbytes, obs_device.value_device_ids(val),
                               time.perf_counter_ns() - t0)
        _charge_upload(nbytes)
        self.put(key, val, nbytes, sweep=sweep)
        return val

    def column_spans(self, provider, pub: tuple, name: str, host_col_fn,
                     spans: list, shard_tag, device=None):
        """Device tiles of one column restricted to a SHARD's row spans
        (round-robin block set — exec/shard.py's partitioning), cached
        by (publication, column, shard spans). The host concat runs only
        on miss; `device` pins the upload to the shard's mesh device."""
        obs_device.note_provider(pub[0], getattr(provider, "name", ""))
        key = (pub, name, "col", ("shard", shard_tag, tuple(spans)))
        dc = self.get(key)
        if dc is not None:
            return dc
        from .shard import _concat_spans
        dc = to_device_column(_concat_spans(host_col_fn(), spans))
        if device is not None:
            # the funnel above attributed the upload to the default
            # device; the pin to the shard's mesh device is a SECOND
            # transfer — account it against the device the tiles
            # actually land on, so sdb_device()'s per-device rows stay
            # consistent with where hbm_bytes_est places the entry
            t0 = time.perf_counter_ns()
            dc = DeviceColumn(dc.type, jax.device_put(dc.data, device),
                              jax.device_put(dc.mask, device), dc.length,
                              dc.scheme, dc.offset, dc.wide)
            obs_device.note_upload(
                int(dc.data.size * dc.data.dtype.itemsize) +
                int(dc.mask.size),
                obs_device.array_device_ids(dc.data),
                time.perf_counter_ns() - t0)
        nbytes = int(dc.data.size * dc.data.dtype.itemsize) + \
            int(dc.mask.size)
        metrics.DEVICE_BYTES.add(nbytes)
        _charge_upload(nbytes)
        self.put(key, dc, nbytes)
        return dc


DEVICE_CACHE = DeviceColumnCache()

#: host-side factorized join-code cache: (pub_l, pub_r, key exprs) →
#: (codes_l, codes_r, g, worst-case pairs). Count- AND byte-bounded
#: (int64 code arrays of large tables are real host memory); the
#: factorize pass is O(n log n) once per publication pair and the
#: pair-count admission check O(n) once — both amortize across repeat
#: queries. Superseded publication pairs are swept on store.
_CODES_CACHE: OrderedDict[tuple, tuple] = OrderedDict()
_CODES_CACHE_MAX = 16
_CODES_CACHE_MAX_BYTES = 256 << 20
_codes_bytes = 0
_codes_lock = threading.Lock()

#: column admission stats, (pub, column) → (all_valid, finite_all, lo,
#: hi) — a pure function of the publication, so cached repeats skip the
#: O(n) host scans. Shared by fused top-N admission and the direct-sum
#: range check.
_COL_STATS_CACHE: OrderedDict[tuple, tuple] = OrderedDict()
_COL_STATS_MAX = 64
_col_stats_lock = threading.Lock()


def clear_codes_cache() -> None:
    """Drop every cached factorization and reset the byte accounting —
    the two must move together or later stores evict against a phantom
    total."""
    global _codes_bytes
    with _codes_lock:
        _CODES_CACHE.clear()
        _codes_bytes = 0


def _rowmask_tiles(nrows: int, pad: Optional[int] = None) -> "jax.Array":
    import jax.numpy as jnp
    n_pad = pad_len(nrows) if pad is None else pad_len(nrows, pad)
    rm = np.zeros(n_pad, dtype=bool)
    rm[:nrows] = True
    return jnp.asarray(rm.reshape(-1, LANES))


# -- pipeline recognition ----------------------------------------------------


def _split_and(e: BoundExpr) -> list[BoundExpr]:
    """Top-level AND conjuncts (a Filter keeps only rows where the whole
    expression is TRUE, so `a AND b` splits losslessly even under
    three-valued logic)."""
    if isinstance(e, BoundFunc) and e.name == "and":
        out: list[BoundExpr] = []
        for a in e.args:
            out.extend(_split_and(a))
        return out
    return [e]


def _unwrap_side(plan):
    """Filter*(Scan) → (scan, [scan-schema-bound predicates]) or None."""
    from .plan import FilterNode, ScanNode
    preds: list[BoundExpr] = []
    node = plan
    while isinstance(node, FilterNode):
        preds.append(node.pred)
        node = node.child
    if type(node) is not ScanNode:
        return None
    if node.filter is not None:
        preds.append(node.filter)
    return node, preds


def _side_of(expr: BoundExpr, nl: int) -> int:
    """0 = probe (left), 1 = build (right); raises when the expression
    reads columns of both join sides (no per-side decomposition)."""
    sides = set()
    for sub in expr.walk():
        if isinstance(sub, BoundColumn):
            sides.add(0 if sub.index < nl else 1)
    if len(sides) > 1:
        raise NotCompilable("expression spans both join sides")
    return sides.pop() if sides else 0


def _check_host_eval_safe(exprs: list[BoundExpr]) -> None:
    from ..sql.binder import _VOLATILE_FUNCS
    unsafe = _VOLATILE_FUNCS | _HOST_EVAL_UNSAFE
    for e in exprs:
        for sub in e.walk():
            if isinstance(sub, BoundFunc) and sub.name in unsafe:
                raise NotCompilable(f"host-evaluated {sub.name}")


class _Side:
    """One join side's publication observation + host access + zone range."""

    def __init__(self, scan, preds: list[BoundExpr], ctx):
        self.scan = scan
        self.preds = preds
        self.provider = scan.provider
        self.pin = self.provider.try_pin()
        self.pub = _pub(self.provider, self.pin)
        try:
            self.nrows = self.pin[0].num_rows if self.pin is not None \
                else self.provider.row_count()
        except NotImplementedError:
            raise NotCompilable("provider without row_count")
        #: per-block scan-conjunct verdicts (the sharded tier combines
        #: them with the shard-to-shard join filter); None when zone
        #: maps could not analyze this side
        self.verdicts = None
        self.zrange = self._zone_range(ctx)

    def host_col(self, name: str) -> Column:
        if self.pin is not None:
            return self.pin[0].column(name)
        return self.provider.host_column(name)

    def _zone_range(self, ctx) -> Optional[tuple[int, int]]:
        """Surviving block envelope under this side's scan conjuncts
        (upload shrink; interior SKIP blocks still upload). (0, 0) when
        everything prunes — the caller short-circuits to the empty
        result the host path would produce from the same verdicts."""
        if not self.preds:
            return None
        from . import zonemap
        block_rows = int(ctx.settings.get("serene_morsel_rows"))
        verdicts = zonemap.block_verdicts(
            self.provider, ctx.settings, self.preds, self.scan.columns,
            block_rows, self.pin)
        if verdicts is None:
            return None
        self.verdicts = verdicts
        lo, hi = zonemap.surviving_range(verdicts, block_rows, self.nrows)
        if hi <= lo:
            return (0, 0)
        if (lo, hi) == (0, self.nrows):
            return None
        n_blocks = len(verdicts)
        lo_b, hi_b = lo // block_rows, (hi + block_rows - 1) // block_rows
        metrics.ZONEMAP_PRUNED.add(n_blocks - (hi_b - lo_b))
        metrics.ZONEMAP_SCANNED.add(hi_b - lo_b)
        if zonemap.verify_enabled(ctx.settings):
            full = self.pin[0] if self.pin is not None else \
                self.provider.full_batch(self.scan.columns)
            full = Batch(list(self.scan.columns),
                         [full.column(c) for c in self.scan.columns])
            spans = [(s, e) for s, e in ((0, lo), (hi, self.nrows))
                     if e > s]
            zonemap.verify_pruned_blocks(
                self.preds, full, spans,
                f"fused pipeline {self.provider.name}")
        return lo, hi

    @property
    def lo(self) -> int:
        return 0 if self.zrange is None else self.zrange[0]

    @property
    def n_live(self) -> int:
        if self.zrange is None:
            return self.nrows
        return self.zrange[1] - self.zrange[0]


# -- fused Scan→Filter→Join→Aggregate ---------------------------------------


#: join kinds the fused tier executes (outer kinds behind
#: serene_device_fused_ext, single-dispatch only)
_JOIN_KINDS = ("inner", "left", "right", "full")

#: DISTINCT is a no-op for these (host _DISTINCT_INVARIANT ∩ _AGG_FUNCS)
_DISTINCT_DROP = {"min", "max"}


def _note_decline(reason: str, ctx, node) -> None:
    obs_device.note_fused_decline(
        reason, profile=getattr(ctx, "profile", None), node_key=id(node))


def _admit_pipeline(node, ctx, decline):
    """Shape recognition + admission walls shared by the aggregate hook
    (try_device_pipeline) and the chained top-N hook. Returns
    (join, probe_side, build_side, post_preds) or None — every None
    taken AFTER the shape is recognizably a join pipeline went through
    `decline` first."""
    from .plan import JoinNode, FilterNode

    settings = ctx.settings
    ext = fused_ext_enabled(settings)
    post_preds: list[BoundExpr] = []
    child = node.child
    while isinstance(child, FilterNode):
        post_preds.extend(_split_and(child.pred))
        child = child.child
    if type(child) is not JoinNode:
        return None
    join = child

    if not join.left_keys:
        return decline("cross_join")
    if join.merge_pairs:
        return decline("merge_pairs")
    if join.kind not in _JOIN_KINDS:
        return decline("join_kind")
    if join.kind != "inner" and not ext:
        return decline("outer_join")
    if join.residual is not None:
        # an inner join's residual is exactly a post-join pair filter;
        # under outer kinds it changes which rows null-extend, which
        # the pre-filter decomposition cannot express
        if not ext or join.kind != "inner":
            return decline("residual")
        post_preds = post_preds + _split_and(join.residual)
    probe_side = _unwrap_side(join.left)
    build_side = _unwrap_side(join.right)
    if probe_side is None or build_side is None:
        return decline("side_shape")
    for spec in node.aggs:
        if spec.func not in _AGG_FUNCS:
            return decline("agg_func")
        if spec.order_by:
            return decline("agg_order_by")
        if spec.distinct and not ext:
            return decline("distinct")
        if spec.filter is not None and not ext:
            return decline("agg_filter")
    pscan = probe_side[0]
    if settings.get("serene_device") == "auto":
        try:
            if pscan.provider.row_count() < \
                    settings.get("serene_device_min_rows"):
                return None
        except NotImplementedError:
            return None
    return join, probe_side, build_side, post_preds


def try_device_pipeline(node, ctx) -> Optional[Batch]:
    """Attempt one-dispatch execution of AggregateNode over an
    equi-join of two scans; None → host path (the parity oracle).
    Every None taken AFTER the shape is recognizably a join pipeline
    records a per-reason decline (obs_device.note_fused_decline) so a
    fallback is diagnosable from EXPLAIN ANALYZE / metrics."""
    settings = ctx.settings
    if settings.get("serene_device") == "cpu" or not fused_enabled(settings):
        return None

    def decline(reason: str) -> None:
        _note_decline(reason, ctx, node)
        return None

    admitted = _admit_pipeline(node, ctx, decline)
    if admitted is None:
        return None       # shape analysis only: no stage for a non-join
    join, probe_side, build_side, post_preds = admitted
    # the request's `device_prepare` stage: sides, key planning,
    # factorization, residency lookups / uploads and the program lookup
    # — everything but what stamps itself inside it (`device_enqueue`
    # at the program call, `device_wait` at the readback,
    # `device_finalize` at the host decode)
    with stage("device_prepare", op="fused"):
        try:
            out = _run_fused(node, join, probe_side, build_side,
                             post_preds, ctx)
            if out is not None:
                from .device_chain import pair_bytes
                metrics.DEVICE_JOINS_FUSED.add()
                metrics.DEVICE_JOIN_BYTES.add(pair_bytes(
                    node, join, probe_side, build_side, post_preds, ctx))
            return out
        except (NotCompilable, DeviceNarrowingError) as e:
            log.debug("device", f"fused pipeline fell back to CPU: {e}")
            return decline(getattr(e, "reason", "not_compilable"))


def _run_fused(node, join, probe_side, build_side,
               post_preds: list[BoundExpr], ctx, fetch: bool = True):
    """Execute the fused pipeline. fetch=True (default) fetches program
    outputs and finalizes to a host Batch. fetch=False is the chained-
    stage entry: it returns (device_outputs, finalize_ctx) WITHOUT any
    device→host readback, so a downstream fused stage (top-N) can
    consume the accumulators in HBM — the sharded/collective branches
    are skipped in that mode (single dispatch is always bit-identical)."""
    import jax.numpy as jnp

    prof = getattr(ctx, "profile", None)

    pscan, ppreds = probe_side
    bscan, bpreds = build_side
    nl = len(join.left.names)
    _check_host_eval_safe(list(join.left_keys) + list(join.right_keys))

    probe = _Side(pscan, ppreds, ctx)
    build = _Side(bscan, bpreds, ctx)

    # split the post-join conjuncts by side: a pair filter that reads
    # only probe (build) columns is exactly a probe (build) row filter
    # under an inner join — and under an OUTER join only on the side
    # that never null-extends (a post filter on the null-extended side
    # would drop rows the pre-filter instead turns into new
    # null-extensions, so those decline)
    post_p: list[BoundExpr] = []
    post_b: list[BoundExpr] = []
    for p in post_preds:
        try:
            sd = _side_of(p, nl)
        except NotCompilable:
            raise NotCompilable("post-join predicate spans both sides",
                                "post_pred_cross_side")
        (post_p if sd == 0 else post_b).append(p)
    outer_left = join.kind in ("left", "full")    # probe rows null-extend
    outer_right = join.kind in ("right", "full")  # build rows null-extend
    if outer_left and post_b:
        raise NotCompilable("post filter on null-extended build side",
                            "outer_post_filter")
    if outer_right and post_p:
        raise NotCompilable("post filter on null-extended probe side",
                            "outer_post_filter")

    # group keys: plain probe-side columns, direct-coded (dict codes /
    # small-range ints) — build-side or computed keys fall back
    for g in node.group_exprs:
        if not isinstance(g, BoundColumn) or g.index >= nl:
            raise NotCompilable("group key is not a plain probe column")

    # referenced-column discovery + dictionaries (join-schema namespace:
    # probe scan col i == join col i, build scan col i == join col nl+i;
    # the side is derived from the index, never assumed, so a build-side
    # string column can't pick up the probe column's dictionary)
    dictionaries: dict[int, np.ndarray] = {}
    join_types = list(join.types)

    def note_dicts(exprs):
        for e in exprs:
            for sub in e.walk():
                if isinstance(sub, BoundColumn) and sub.type.is_string:
                    ji = sub.index
                    if ji in dictionaries:
                        continue
                    if ji < nl:
                        col = probe.host_col(pscan.columns[ji])
                    else:
                        col = build.host_col(bscan.columns[ji - nl])
                    if col.dictionary is not None:
                        dictionaries[ji] = col.dictionary

    note_dicts(post_p + post_b + list(node.group_exprs) +
               [s.arg for s in node.aggs if s.arg is not None] +
               [s.filter for s in node.aggs if s.filter is not None])

    # scan-level predicates compile against the scan schema; their input
    # slots translate into the join namespace (probe scan col i == join
    # col i, build scan col i == join col nl + i)
    def compile_scan_preds(side: _Side, shift: int) -> list[DeviceExpr]:
        dicts = {}
        for e in side.preds:
            for sub in e.walk():
                if isinstance(sub, BoundColumn) and sub.type.is_string \
                        and sub.index not in dicts:
                    col = side.host_col(side.scan.columns[sub.index])
                    if col.dictionary is not None:
                        dicts[sub.index] = col.dictionary
        out = []
        for e in side.preds:
            ce = compile_expr(e, side.scan.types, dicts)
            ce.inputs = [i + shift for i in ce.inputs]
            out.append(ce)
        return out

    preds_probe = compile_scan_preds(probe, 0) + \
        [compile_expr(p, join_types, dictionaries) for p in post_p]
    preds_build = compile_scan_preds(build, nl) + \
        [compile_expr(p, join_types, dictionaries) for p in post_b]

    # group-key plans (direct coding; the NULL group takes the last slot)
    key_plans, group_space = _plan_group_keys(node, join_types, probe,
                                              pscan, dictionaries)
    group_mode = bool(node.group_exprs)

    # aggregate plans: (spec, side, compiled arg | None), plus the PR 17
    # sidecars — per-agg FILTER masks (same side as the arg; an extra
    # predicate ANDed into the value-validity mask), count_star FILTER
    # as its own accumulator column on the filter's side, and DISTINCT
    # presence-grid plans over plain probe-side columns
    ext = fused_ext_enabled(ctx.settings)
    agg_plans: list[tuple] = []
    agg_filters: dict[int, DeviceExpr] = {}
    star_filter: dict[int, int] = {}       # si → side of the filter
    distinct_sis: list[int] = []
    for si, spec in enumerate(node.aggs):
        fe = None
        fside = 0
        if spec.filter is not None:
            _check_host_eval_safe([spec.filter])
            fside = _side_of(spec.filter, nl)
            fe = compile_expr(spec.filter, join_types, dictionaries)
        if spec.func == "count_star":
            if fe is not None:
                star_filter[si] = fside
                agg_filters[si] = fe
                agg_plans.append((spec, fside, None))
            else:
                agg_plans.append((spec, 0, None))
            continue
        side = _side_of(spec.arg, nl)
        if fe is not None:
            if fside != side:
                raise NotCompilable(
                    "FILTER predicate on the other join side",
                    "filter_cross_side")
            agg_filters[si] = fe
        t = spec.arg.type
        if spec.distinct and spec.func not in _DISTINCT_DROP:
            distinct_sis.append(si)
        if spec.func in ("sum", "avg"):
            if not t.is_integer:
                raise NotCompilable(f"{spec.func} over {t} (exactness)",
                                    "agg_type")
        elif spec.func in ("min", "max"):
            if not (t.is_integer or
                    t.id in (dt.TypeId.BOOL, dt.TypeId.DATE)):
                # sorted dictionaries give strings a total order on
                # int32 codes: min/max over codes, decode at finalize
                if not (ext and t.is_string and
                        isinstance(spec.arg, BoundColumn) and
                        dictionaries.get(spec.arg.index) is not None):
                    raise NotCompilable(f"{spec.func} over {t}",
                                        "agg_type")
        agg_plans.append((spec, side,
                          compile_expr(spec.arg, join_types, dictionaries)))

    # DISTINCT (count/sum/avg): a (group, value) presence grid over the
    # probe side's direct-coded values — count = nonzero presences per
    # group, sum = Σ value · present recombined host-side in int64.
    # Build-side args have no per-output-row value representation in
    # the probe-phase scatter, so they decline.
    distinct_plans: dict[int, tuple] = {}
    for si in distinct_sis:
        spec, side, ce = agg_plans[si]
        if side != 0 or not isinstance(spec.arg, BoundColumn):
            raise NotCompilable("DISTINCT arg is not a plain probe column",
                                "distinct_arg")
        ji = spec.arg.index
        t = spec.arg.type
        if t.is_string:
            d = dictionaries.get(ji)
            if d is None:
                raise NotCompilable("DISTINCT string without dictionary",
                                    "distinct_arg")
            dkind, lo_v, vspace = "dict", 0, len(d)
        elif t.is_integer or t.id in (dt.TypeId.BOOL, dt.TypeId.DATE):
            _, _, lo_v, hi_v = _col_stats(probe, pscan.columns[ji])
            if lo_v is None:
                raise NotCompilable("DISTINCT value range unknown",
                                    "distinct_space")
            rng = hi_v - lo_v + 1
            if rng > MAX_INT_KEY_RANGE:
                raise NotCompilable("DISTINCT value range too large",
                                    "distinct_space")
            dkind, vspace = "int", rng
        else:
            raise NotCompilable(f"DISTINCT over {t}", "distinct_arg")
        vspace = _pow2_int(max(vspace, 1))  # pow2-bucket the new axis
        if group_space * vspace > MAX_GROUP_PRODUCT:
            raise NotCompilable("DISTINCT presence grid too large",
                                "distinct_space")
        distinct_plans[si] = (dkind, ji, int(lo_v), vspace)

    # join-key factorization (host, cached per publication pair along
    # with the worst-case pair count: every int32 count/limb scatter in
    # the program is exact below the bound)
    cl, cr, g, total_pairs = _join_codes(join, probe, build)
    if g + 2 > MAX_CODE_SPACE:
        raise NotCompilable("join code space too large")
    # outer kinds add up to one output row per null-extended input row
    # on top of the inner pairs — the scatter-exactness bound covers
    # the worst case of BOTH
    eff_pairs = total_pairs + (probe.nrows if outer_left else 0) + \
        (build.nrows if outer_right else 0)
    if eff_pairs > MAX_PAIRS_EXACT:
        raise NotCompilable(
            f"{eff_pairs} worst-case pairs exceed the exact-scatter "
            f"bound")
    if probe.zrange is not None:
        cl = cl[probe.zrange[0]:probe.zrange[1]]
    if build.zrange is not None:
        cr = cr[build.zrange[0]:build.zrange[1]]

    # direct-sum fast path: a plain-column sum whose |value| bound times
    # the worst-case pair count provably fits int32 skips the 5-column
    # limb decomposition for ONE direct scatter column (sound for every
    # slot the probe phase can read: a gathered code's build dups are
    # counted in total_pairs, so its partial is inside the bound too)
    sum_modes: dict[int, str] = {}
    for si, (spec, side_ix, ce) in enumerate(agg_plans):
        if spec.func not in ("sum", "avg") or ce is None:
            continue
        if si in distinct_plans:
            continue                 # presence-grid path, no value col
        mode = "limb"
        # outer joins weight rows by max(cnt, 1) (probe side) or add
        # the unmatched-build null-group reduction (build side) — the
        # direct bound below only covers inner pair counts, so the
        # affected side rides the always-exact limb decomposition
        outer_forced = (side_ix == 0 and outer_left) or \
            (side_ix == 1 and outer_right)
        arg = spec.arg
        if isinstance(arg, BoundColumn) and not outer_forced:
            if arg.index < nl:
                s_obj, cname = probe, pscan.columns[arg.index]
            else:
                s_obj, cname = build, bscan.columns[arg.index - nl]
            _, _, lo_v, hi_v = _col_stats(s_obj, cname)
            if lo_v is not None and max(abs(lo_v), abs(hi_v)) * \
                    max(total_pairs, 1) < (1 << 31):
                mode = "direct"
        sum_modes[si] = mode

    # empty short-circuit: zero output rows only when NEITHER side can
    # null-extend past the empty one; an outer kind whose non-empty
    # side survives would need the null-extension rows, which the
    # synthesized zero accumulators cannot express — decline
    if probe.n_live == 0 or build.n_live == 0:
        empty_ok = (probe.n_live == 0 and build.n_live == 0) or \
            (probe.n_live == 0 and not outer_right) or \
            (build.n_live == 0 and not outer_left)
        if not empty_ok:
            raise NotCompilable("outer join with an empty side",
                                "outer_empty")
        results = _zero_results(agg_plans, group_space, sum_modes,
                                star_filter, distinct_plans)
        return _finalize(node, key_plans, agg_plans, results, probe,
                         pscan, dictionaries, group_space, group_mode,
                         sum_modes, star_filter=star_filter,
                         distinct_plans=distinct_plans)

    #: everything the compiled program's shape depends on besides the
    #: publications/ranges — shared by the single-dispatch and sharded
    #: program cache keys
    shape_sig = (tuple(_expr_key(p) for p in ppreds),
                 tuple(_expr_key(p) for p in bpreds),
                 tuple(_expr_key(p) for p in post_preds),
                 tuple((s.func, _expr_key(s.arg) if s.arg is not None
                        else None, bool(s.distinct),
                        _expr_key(s.filter) if s.filter is not None
                        else None) for s in node.aggs),
                 tuple(_expr_key(gx) for gx in node.group_exprs),
                 tuple(sorted(sum_modes.items())), join.kind)

    # sharded tier: run the same fused program once per probe shard
    # (round-robin block partitions) with the build phase hoisted into
    # one shared dispatch; per-shard integer accumulators combine
    # exactly on host, so results stay bit-identical to shards = 1
    from . import shard as shard_mod
    n_shards = shard_mod.shard_count(ctx.settings)
    block_rows = int(ctx.settings.get("serene_morsel_rows"))
    # outer kinds, FILTER masks and DISTINCT grids run single-dispatch
    # only: per-shard probe partitions would double-count unmatched
    # rows (LEFT's max(cnt,1) weight is not additive across shards) and
    # presence grids don't combine by addition; chained (fetch=False)
    # callers need the outputs of ONE program in HBM
    plain = (join.kind == "inner" and not agg_filters and
             not star_filter and not distinct_plans)
    if fetch and plain and n_shards > 1 and probe.n_live > block_rows:
        return _run_fused_sharded(
            node, join, probe, build, pscan, bscan, nl, preds_probe,
            preds_build, key_plans, group_space, group_mode, agg_plans,
            sum_modes, cl, cr, g, dictionaries, shape_sig, ctx, prof,
            block_rows, n_shards)

    # device environment: columns via the publication-keyed cache
    needed: set[int] = set()
    for ce in preds_probe + preds_build:
        needed.update(ce.inputs)
    for kp in key_plans:
        needed.add(kp[1])
    for spec, side, ce in agg_plans:
        if ce is not None:
            needed.update(ce.inputs)
    for fe in agg_filters.values():
        needed.update(fe.inputs)
    for (_dk, d_ji, _lo, _vs) in distinct_plans.values():
        needed.add(d_ji)
    needed = sorted(needed)

    # pow2 row buckets: every upload (columns, code tiles, row masks)
    # pads to the same per-side bucket, so the traced program shape is a
    # function of the BUCKET, not the exact surviving row count — the
    # extended admission multiplies program axes and O(log rows) buckets
    # keep that product off the recompile-storm detector
    p_pad = _pow2_rows(probe.n_live)
    b_pad = _pow2_rows(build.n_live)
    env_cols = {}
    for ji in needed:
        if ji < nl:
            side, name, zr, pad = probe, pscan.columns[ji], \
                probe.zrange, p_pad
        else:
            side, name, zr, pad = build, bscan.columns[ji - nl], \
                build.zrange, b_pad
        env_cols[ji] = DEVICE_CACHE.column(
            side.provider, side.pub, name,
            (lambda s=side, n=name: s.host_col(n)), zr, pad=pad)

    # code tiles + row masks (sentinels baked in host-side: NULL-key /
    # padding probe rows → g+1, build rows → g; neither ever matches).
    # A codes entry is stale when EITHER side's publication moved: the
    # owner-generation sweep covers this side, the sweep predicate
    # covers entries pinned to an older generation of the partner.
    keyset = (tuple(_expr_key(k) for k in join.left_keys),
              tuple(_expr_key(k) for k in join.right_keys))

    pc_dev = DEVICE_CACHE.array(
        probe.pub, "__codes__",
        (build.pub, keyset, (probe.zrange, "pad", p_pad), "p"),
        lambda: _code_tiles(cl, g + 1, pad=p_pad),
        sweep=_partner_stale_pred(probe.pub, build.pub, "p", keyset))
    bc_dev = DEVICE_CACHE.array(
        build.pub, "__codes__",
        (probe.pub, keyset, (build.zrange, "pad", b_pad), "b"),
        lambda: _code_tiles(cr, g, pad=b_pad),
        sweep=_partner_stale_pred(build.pub, probe.pub, "b", keyset))
    prow = DEVICE_CACHE.array(probe.pub, "__rowmask__",
                              (probe.zrange, "pad", p_pad),
                              lambda: _rowmask_tiles(probe.n_live, p_pad))
    brow = DEVICE_CACHE.array(build.pub, "__rowmask__",
                              (build.zrange, "pad", b_pad),
                              lambda: _rowmask_tiles(build.n_live, b_pad))

    # -- the single program -------------------------------------------------
    decode_specs = [(env_cols[i].scheme, env_cols[i].offset) for i in needed]

    def env_for(ce: DeviceExpr, arrays):
        return [arrays[i] for i in ce.inputs]

    space = g + 2

    # CPU-backend reality: every row-scatter pass costs roughly the same
    # serial walk regardless of target size or column count, so the
    # program accumulates ALL add-reductions of one phase in ONE
    # multi-column scatter — build partials land in a single
    # (code space, C) scatter, probe group accumulators in a single
    # (group space, C) scatter — instead of one scatter per aggregate.
    # Only min/max need their own (non-add) scatter combinator.
    bstart, _bmm_sis = _build_layout(
        agg_plans, sum_modes,
        star_sides={si for si, sd in star_filter.items() if sd == 1})

    def program(*flat):
        arrays = {}
        for k, ji in enumerate(needed):
            data = flat[2 * k]
            scheme, off = decode_specs[k]
            if scheme != "raw":
                data = data.astype(jnp.int32) + jnp.int32(off)
            arrays[ji] = (data, flat[2 * k + 1])
        base = 2 * len(needed)
        bcodes, pcodes = flat[base], flat[base + 1]
        bmask, pmask = flat[base + 2], flat[base + 3]

        # build phase: mask, then per-code partials (one fused scatter;
        # per-column validity gates zero the value, which scatters the
        # same result as masking the index)
        for ce in preds_build:
            v, ok = ce.fn(env_for(ce, arrays))
            b = v if v.dtype == jnp.bool_ else (v != 0)
            bmask = jnp.logical_and(bmask, jnp.logical_and(b, ok))
        bc = jnp.where(bmask, bcodes, jnp.int32(g))
        bcols = [bmask.ravel().astype(jnp.int32)]       # col 0: match count
        bmm: dict[int, "jax.Array"] = {}

        def ftrue(si, base_m):
            """AND the agg's FILTER predicate (TRUE only — SQL drops
            FALSE and NULL alike) into a validity mask."""
            fe = agg_filters.get(si)
            if fe is None:
                return base_m
            fv, fok = fe.fn(env_for(fe, arrays))
            fb = fv if fv.dtype == jnp.bool_ else (fv != 0)
            return jnp.logical_and(base_m, jnp.logical_and(fb, fok))

        for si, (spec, side, ce) in enumerate(agg_plans):
            if spec.func == "count_star":
                if si in star_filter and side == 1:
                    # count_star FILTER on the build side: its own
                    # per-code satisfied-row count
                    m = ftrue(si, bmask)
                    assert bstart[si] == len(bcols)
                    bcols.append(m.ravel().astype(jnp.int32))
                continue
            if side != 1 or ce is None or si in distinct_plans:
                continue
            v, ok = ce.fn(env_for(ce, arrays))
            m = ftrue(si, jnp.logical_and(bmask, ok))
            mi = m.ravel().astype(jnp.int32)
            assert bstart[si] == len(bcols)      # trace-time layout check
            bcols.append(mi)                             # per-agg vcnt
            if spec.func in ("sum", "avg"):
                if sum_modes[si] == "direct":
                    bcols.append(v.astype(jnp.int32).ravel() * mi)
                else:
                    bcols.extend(_limb_cols(
                        v.astype(jnp.int32).ravel(), mi))
            elif spec.func in ("min", "max"):
                bmm[si] = ops_agg.group_min_max(
                    bcodes, m, v.astype(jnp.int32), space, spec.func)
        bacc = jnp.zeros((space, len(bcols)), jnp.int32) \
            .at[bc.ravel()].add(jnp.stack(bcols, axis=1))
        bacc = bacc.at[g].set(0).at[g + 1].set(0)        # sentinel slots

        # probe phase: ONE body shared with the sharded probe programs
        # (_probe_phase) — mask, gather match counts, one fused scatter
        # into the group accumulator
        return _probe_phase(arrays, pcodes, pmask, bacc, bmm,
                            preds_probe, key_plans, group_mode,
                            group_space, agg_plans, sum_modes, bstart, g,
                            join_kind=join.kind, agg_filters=agg_filters,
                            star_filter=star_filter,
                            distinct_plans=distinct_plans,
                            right_ext=((bcodes, bmask) if outer_right
                                       else None))

    # program cache: PUBLICATION-FREE. Every data-dependent constant the
    # trace closes over is keyed explicitly — decode schemes/offsets,
    # key plans (lo offsets), code/group spaces, pow2 row buckets,
    # DISTINCT grids, and the compiled expressions' baked constants
    # (string-comparison code thresholds) via DeviceExpr.consts — so
    # repeat queries across publications/tables reuse ONE executable
    # whenever the traced shape is genuinely identical, instead of
    # recompiling per publication bump
    consts_sig = tuple(ce.consts for ce in preds_probe + preds_build) + \
        tuple(ce.consts for _s, _sd, ce in agg_plans
              if ce is not None) + \
        tuple(agg_filters[si].consts for si in sorted(agg_filters))
    cache_key = ("fused", join.kind, tuple(needed), tuple(decode_specs),
                 space, group_space, tuple(key_plans),
                 tuple(sorted(star_filter.items())),
                 tuple(sorted(distinct_plans.items())),
                 p_pad, b_pad, consts_sig) + shape_sig
    jitted = obs_device.compiled("fused", cache_key, lambda: program,
                                 profile=prof, node_key=id(node))

    flat_args = []
    for ji in needed:
        dc = env_cols[ji]
        flat_args.extend([dc.data, dc.mask])
    flat_args.extend([bc_dev, pc_dev, brow, prow])

    from .plan import check_cancel
    check_cancel()
    metrics.DEVICE_OFFLOADS.add()
    if not fetch:
        # chained handoff: accumulators STAY in HBM — the downstream
        # fused stage consumes them directly; zero device→host bytes
        # move here (the transfer ledger is the proof). The chain's
        # device time runs from this enqueue to the readback of the
        # stage that consumes them, and is observed there
        t_enqueue = time.perf_counter_ns()
        outs = jitted(*flat_args)
        fin = {"node": node, "key_plans": key_plans,
               "agg_plans": agg_plans, "probe": probe, "pscan": pscan,
               "dictionaries": dictionaries, "group_space": group_space,
               "group_mode": group_mode, "sum_modes": sum_modes,
               "star_filter": star_filter,
               "distinct_plans": distinct_plans,
               "stage1_key": cache_key, "t_enqueue": t_enqueue}
        return outs, fin
    results = obs_device.dispatch(jitted, flat_args, profile=prof,
                                  node_key=id(node))
    return _finalize(node, key_plans, agg_plans, results, probe, pscan,
                     dictionaries, group_space, group_mode, sum_modes,
                     star_filter=star_filter,
                     distinct_plans=distinct_plans)


def _build_layout(agg_plans, sum_modes: dict,
                  star_sides=frozenset()) -> tuple[dict, list]:
    """Host-side mirror of the build accumulator's column layout, shared
    by every program shape (single-dispatch and sharded build/probe):
    col 0 = match count; per build-side agg: vcnt, then 1 direct / 5
    limb value columns for sum/avg; min/max partials ride separate
    outputs in ascending-si order. `star_sides` marks count_star aggs
    whose FILTER lives on the build side — each takes one satisfied-row
    count column."""
    bstart: dict[int, int] = {}
    bmm_sis: list[int] = []
    ncols = 1
    for si, (spec, side, ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            if si in star_sides:
                bstart[si] = ncols
                ncols += 1
            continue
        if side != 1 or ce is None:
            continue
        bstart[si] = ncols
        ncols += 1
        if spec.func in ("sum", "avg"):
            ncols += 1 if sum_modes.get(si) == "direct" else 5
        elif spec.func in ("min", "max"):
            bmm_sis.append(si)
    return bstart, bmm_sis


def _probe_phase(arrays, pcodes, pmask, bacc, bmm, preds_probe,
                 key_plans, group_mode: bool, group_space: int,
                 agg_plans, sum_modes: dict, bstart: dict, g: int,
                 join_kind: str = "inner", agg_filters=None,
                 star_filter=None, distinct_plans=None, right_ext=None):
    """THE probe phase, traced into both program shapes — the single
    fused dispatch computes `bacc`/`bmm` in-program, the sharded probe
    programs take them as inputs; one body keeps the two shapes'
    bit-identity contract in one place. Masks rows through the compiled
    probe predicates, gathers per-code build partials, and lands every
    add-reduction in ONE (group space, C) scatter.

    PR 17 extensions (single-dispatch callers only): LEFT/FULL weight
    each surviving probe row by max(matches, 1) so unmatched rows emit
    their null-extended output row; RIGHT/FULL take `right_ext =
    (bcodes, bmask-after-preds)` and reduce the unmatched build rows
    into the all-NULL-key group slot; per-agg FILTER masks AND into
    value validity; DISTINCT plans scatter a (group × value) presence
    grid each."""
    import jax.numpy as jnp

    agg_filters = agg_filters or {}
    star_filter = star_filter or {}
    distinct_plans = distinct_plans or {}
    outer_left = join_kind in ("left", "full")

    cnt_code = bacc[:, 0]
    for ce in preds_probe:
        v, ok = ce.fn([arrays[i] for i in ce.inputs])
        b = v if v.dtype == jnp.bool_ else (v != 0)
        pmask = jnp.logical_and(pmask, jnp.logical_and(b, ok))
    pc = jnp.where(pmask, pcodes, jnp.int32(g + 1))
    cnt = cnt_code[pc]                       # matches per probe row
    # output rows per surviving probe row: LEFT/FULL keep unmatched
    # probe rows as one null-extended row each
    w = jnp.maximum(cnt, 1) if outer_left else cnt

    if group_mode:
        gcodes = jnp.zeros_like(pc)
        for kind, ji, lo_v, size in key_plans:
            data, ok = arrays[ji]
            if kind == "dict":
                c = data.astype(jnp.int32)
            else:
                c = data.astype(jnp.int32) - jnp.int32(lo_v)
            c = jnp.where(ok, c, jnp.int32(size - 1))
            gcodes = gcodes * jnp.int32(size) + jnp.clip(c, 0, size - 1)
    else:
        gcodes = jnp.zeros_like(pc)
    gc = jnp.where(pmask, gcodes, 0).ravel()
    pmi = pmask.ravel().astype(jnp.int32)

    def ftrue(si, base_m):
        """AND the agg's FILTER predicate (TRUE only) into a mask."""
        fe = agg_filters.get(si)
        if fe is None:
            return base_m
        fv, fok = fe.fn([arrays[i] for i in fe.inputs])
        fb = fv if fv.dtype == jnp.bool_ else (fv != 0)
        return jnp.logical_and(base_m, jnp.logical_and(fb, fok))

    pcols = [jnp.where(pmask, w, 0).ravel()]         # col 0: output rows
    pstart: dict[int, int] = {}
    pmm: dict[int, "jax.Array"] = {}
    grids: dict[int, "jax.Array"] = {}
    for si, (spec, side, ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            if si not in star_filter:
                continue                     # shared output-row counts
            pstart[si] = len(pcols)
            if side == 0:
                m = ftrue(si, pmask)
                pcols.append(jnp.where(m, w, 0).ravel())
            else:
                vcnt = bacc[:, bstart[si]]
                pcols.append(jnp.where(pmask, vcnt[pc], 0).ravel())
            continue
        if si in distinct_plans:
            # presence grid: one cell per (group, value); host counts /
            # sums the present cells exactly
            dkind, ji, lo_v, vspace = distinct_plans[si]
            data, ok = arrays[ji]
            if dkind == "dict":
                c = data.astype(jnp.int32)
            else:
                c = data.astype(jnp.int32) - jnp.int32(lo_v)
            m = ftrue(si, jnp.logical_and(pmask, ok))
            m = jnp.logical_and(m, w > 0)
            cell = gcodes * jnp.int32(vspace) + jnp.clip(c, 0, vspace - 1)
            cell = jnp.where(m, cell, 0).ravel()
            grids[si] = jnp.zeros(group_space * vspace, jnp.int32) \
                .at[cell].add(m.ravel().astype(jnp.int32))
            continue
        if side == 0:
            v, ok = ce.fn([arrays[i] for i in ce.inputs])
            m = ftrue(si, jnp.logical_and(pmask, ok))
            vpairs = jnp.where(m, w, 0).ravel()
            pstart[si] = len(pcols)
            if spec.func == "count":
                pcols.append(vpairs)
            elif spec.func in ("sum", "avg"):
                if sum_modes[si] == "direct":
                    pcols.append(v.astype(jnp.int32).ravel() * vpairs)
                else:
                    pcols.extend(_limb_cols(
                        v.astype(jnp.int32).ravel(), vpairs))
                pcols.append(vpairs)
            else:   # min / max — a selection; pairs only gate entry
                pmm[si] = ops_agg.group_min_max(
                    gcodes, jnp.logical_and(m, w > 0),
                    v.astype(jnp.int32), group_space, spec.func)
                pcols.append(vpairs)
        else:
            vcnt = bacc[:, bstart[si]]
            gathered_cnt = jnp.where(pmask, vcnt[pc], 0).ravel()
            pstart[si] = len(pcols)
            if spec.func == "count":
                pcols.append(gathered_cnt)
            elif spec.func in ("sum", "avg"):
                if sum_modes[si] == "direct":
                    partial = bacc[:, bstart[si] + 1]
                    pcols.append(
                        jnp.where(pmask, partial[pc], 0).ravel())
                else:
                    lim = bacc[:, bstart[si] + 1:
                               bstart[si] + 6][pc.ravel()]
                    lim = lim * pmi[:, None]           # (n, 5)
                    pcols.extend([lim[:, j] for j in range(5)])
                pcols.append(gathered_cnt)
            else:
                mmv = bmm[si][pc]
                m2 = jnp.logical_and(pmask, vcnt[pc] > 0)
                pmm[si] = ops_agg.group_min_max(
                    gcodes, m2, mmv, group_space, spec.func)
                pcols.append(gathered_cnt)
    acc = jnp.zeros((group_space, len(pcols)), jnp.int32) \
        .at[gc].add(jnp.stack(pcols, axis=1))

    if right_ext is not None:
        # RIGHT/FULL: build rows surviving the build predicates whose
        # code matches ZERO surviving probe rows null-extend — their
        # probe side is all NULL, so every reduction lands in the
        # all-NULL composite group slot (SQL groups NULLs together, so
        # colliding with a real all-NULL-key probe group is correct)
        bcodes_r, bmask_r = right_ext
        bc_r = jnp.where(bmask_r, bcodes_r, jnp.int32(g)).ravel()
        pcc = jnp.zeros(g + 2, jnp.int32).at[pc.ravel()].add(pmi)
        # pcc[g] == 0 always (probe codes are < g or the g+1 sentinel),
        # so NULL-key build rows — host-rewritten to g — count as
        # unmatched here exactly as the oracle's NULL-never-matches rule
        ub = jnp.logical_and(bmask_r.ravel(), pcc[bc_r] == 0)
        null_gc = group_space - 1 if group_mode else 0
        acc = acc.at[null_gc, 0].add(
            jnp.sum(ub, dtype=jnp.int32))
        for si, (spec, side, ce) in enumerate(agg_plans):
            if spec.func == "count_star":
                if si in star_filter and side == 1:
                    m = ftrue(si, bmask_r)
                    mu = jnp.logical_and(m.ravel(), ub)
                    acc = acc.at[null_gc, pstart[si]].add(
                        jnp.sum(mu, dtype=jnp.int32))
                continue
            if side != 1 or si in distinct_plans:
                continue   # null-extended probe values aggregate to none
            v, ok = ce.fn([arrays[i] for i in ce.inputs])
            m = ftrue(si, jnp.logical_and(bmask_r, ok))
            mu = jnp.logical_and(m.ravel(), ub)
            mui = mu.astype(jnp.int32)
            nmu = jnp.sum(mui, dtype=jnp.int32)
            start = pstart[si]
            if spec.func == "count":
                acc = acc.at[null_gc, start].add(nmu)
            elif spec.func in ("sum", "avg"):
                # sum_modes forces limb for build-side sums under
                # RIGHT/FULL, so the layout here is always 5 limbs + cnt
                for j, lcol in enumerate(_limb_cols(
                        v.astype(jnp.int32).ravel(), mui)):
                    acc = acc.at[null_gc, start + j].add(
                        jnp.sum(lcol, dtype=jnp.int32))
                acc = acc.at[null_gc, start + 5].add(nmu)
            else:       # min / max
                ident = jnp.int32(_mm_ident(spec.func))
                red = jnp.where(mu, v.astype(jnp.int32).ravel(), ident)
                red = jnp.min(red) if spec.func == "min" else jnp.max(red)
                upd = pmm[si].at[null_gc]
                pmm[si] = upd.min(red) if spec.func == "min" \
                    else upd.max(red)
                acc = acc.at[null_gc, start].add(nmu)

    # slice the fused accumulator back into the per-agg output spec
    # (bit-identical to the one-scatter-per-aggregate layout)
    outputs = [acc[:, 0]]
    for si, (spec, side, ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            if si in star_filter:
                outputs.append(acc[:, pstart[si]])
            continue
        if si in distinct_plans:
            outputs.append(grids[si])
            continue
        start = pstart[si]
        if spec.func == "count":
            outputs.append(acc[:, start])
        elif spec.func in ("sum", "avg"):
            if sum_modes[si] == "direct":
                outputs.append(acc[:, start])
                outputs.append(acc[:, start + 1])
            else:
                outputs.append(acc[:, start:start + 5])
                outputs.append(acc[:, start + 5])
        else:
            outputs.append(pmm[si])
            outputs.append(acc[:, start])
    return tuple(outputs)


def _partner_stale_pred(owner_pub, partner_pub, side_tag, keyset,
                        name="__codes__"):
    """Sweep predicate for entries pinned to an older generation of the
    PARTNER table (whose publication the owner-side generation sweep
    cannot see): code tiles and the sharded tier's cached build-phase
    outputs both embed the partner publication at tag position 0."""
    def pred(k):
        return (k[0][0] == owner_pub[0] and k[1] == name and
                isinstance(k[3], tuple) and len(k[3]) >= 4 and
                k[3][3] == side_tag and k[3][1] == keyset and
                isinstance(k[3][0], tuple) and
                k[3][0][0] == partner_pub[0] and k[3][0] != partner_pub)
    return pred


# -- sharded fused execution (serene_shards > 1) ----------------------------
#
# The same fused program over hash-partitioned probe data (PAPER.md §8):
# the probe side's surviving blocks split round-robin into shards, the
# build phase runs ONCE as its own dispatch, and each shard's probe
# phase dispatches over only its block set — pinned across
# jax.devices() via parallel/mesh.shard_devices when a multi-device
# mesh is present, fanned out as concurrent pool tasks either way. All
# accumulators are int32 adds / min-max selections over disjoint row
# sets, so the host-side combine (int64 sums, elementwise min/max) is
# exact and the result is bit-identical to the shards=1 single
# dispatch. The build side additionally publishes PER-SHARD key min/max
# (shard-to-shard join filter): probe blocks outside every build
# shard's range never upload at all.

#: per-(build publication, keyset) cache of the published shard ranges,
#: so repeat queries skip the O(n) build-key min/max scans
_SHARD_RANGES_CACHE: OrderedDict[tuple, object] = OrderedDict()
_SHARD_RANGES_MAX = 32
_shard_ranges_lock = threading.Lock()


def _shard_build_ranges(join, build: _Side, n_shards: int,
                        block_rows: int):
    """The build side's per-shard key ranges (exec/shard.ShardedRanges)
    or None when no shard publishes a rangeable key / key eval must
    fall back. Cached per build publication — pure function of it."""
    from . import shard as shard_mod
    keyset = (tuple(_expr_key(k) for k in join.left_keys),
              tuple(_expr_key(k) for k in join.right_keys))
    ck = (build.pub, keyset, n_shards, block_rows)
    with _shard_ranges_lock:
        if ck in _SHARD_RANGES_CACHE:
            _SHARD_RANGES_CACHE.move_to_end(ck)
            return _SHARD_RANGES_CACHE[ck]
    bbatch = build.pin[0] if build.pin is not None \
        else build.provider.full_batch(build.scan.columns)
    bbatch = Batch(list(build.scan.columns),
                   [bbatch.column(c) for c in build.scan.columns])
    try:
        rkeys = [k.eval(bbatch) for k in join.right_keys]
        groups = shard_mod.build_shard_ranges(
            join.left_keys, rkeys,
            build.provider.shard_view(n_shards, block_rows,
                                      bbatch.num_rows))
    except Exception:
        # key eval over unfiltered rows may legitimately raise (the
        # host path evaluates keys only over surviving rows) — then no
        # shard filter, never an error
        groups = None
    with _shard_ranges_lock:
        while len(_SHARD_RANGES_CACHE) >= _SHARD_RANGES_MAX:
            _SHARD_RANGES_CACHE.popitem(last=False)
        _SHARD_RANGES_CACHE[ck] = groups
    return groups


def _sum_i64(arrs) -> np.ndarray:
    out = np.asarray(arrs[0]).astype(np.int64)
    for a in arrs[1:]:
        out = out + np.asarray(a).astype(np.int64)
    return out


def _combine_shard_results(agg_plans, sum_modes: dict,
                           shard_outs: list[list]) -> list:
    """Exact cross-shard combine of per-shard program outputs into the
    single-dispatch output spec _finalize consumes: counts/sums add in
    int64 (limb columns stack to (C, G, 5) — combine_sum_int_limbs
    recombines chunked), min/max reduce elementwise. Integer addition
    over disjoint row sets is associative, so the combined accumulators
    equal the shards=1 dispatch bit for bit."""
    per_slot = list(zip(*shard_outs))
    out: list = [_sum_i64(per_slot[0])]
    slot = 1
    for si, (spec, _side, _ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            continue
        if spec.func == "count":
            out.append(_sum_i64(per_slot[slot]))
            slot += 1
        elif spec.func in ("sum", "avg"):
            if sum_modes[si] == "direct":
                out.append(_sum_i64(per_slot[slot]))
            else:
                out.append(np.stack([np.asarray(r)
                                     for r in per_slot[slot]]))
            slot += 1
            out.append(_sum_i64(per_slot[slot]))
            slot += 1
        else:                              # min / max
            red = np.minimum.reduce if spec.func == "min" \
                else np.maximum.reduce
            out.append(red([np.asarray(m) for m in per_slot[slot]]))
            slot += 1
            out.append(_sum_i64(per_slot[slot]))
            slot += 1
    return out


def _run_fused_sharded(node, join, probe: _Side, build: _Side, pscan,
                       bscan, nl: int, preds_probe, preds_build,
                       key_plans, group_space: int, group_mode: bool,
                       agg_plans, sum_modes: dict, cl: np.ndarray,
                       cr: np.ndarray, g: int, dictionaries,
                       shape_sig: tuple, ctx, prof, block_rows: int,
                       n_shards: int) -> Batch:
    import jax.numpy as jnp

    from . import shard as shard_mod
    from . import zonemap
    from ..parallel import mesh as mesh_mod
    from .plan import check_cancel

    settings = ctx.settings

    keyset = (tuple(_expr_key(k) for k in join.left_keys),
              tuple(_expr_key(k) for k in join.right_keys))
    space = g + 2
    plo, phi = probe.lo, probe.lo + probe.n_live

    # -- shard-to-shard join filter: per-build-shard key ranges prune
    # probe blocks (and their uploads) before any transfer
    groups = _shard_build_ranges(join, build, n_shards, block_rows)
    v_shard = None
    if groups is not None:
        v_shard = shard_mod.sharded_verdicts(
            probe.provider, settings, groups, pscan.columns, block_rows,
            probe.pin)
    verdicts = zonemap.combine_verdicts(probe.verdicts, v_shard)

    needed_p = sorted(
        {i for ce in preds_probe for i in ce.inputs} |
        {kp[1] for kp in key_plans} |
        {i for _spec, side, ce in agg_plans
         if ce is not None and side == 0 for i in ce.inputs})
    needed_b = sorted(
        {i for ce in preds_build for i in ce.inputs} |
        {i for _spec, side, ce in agg_plans
         if ce is not None and side == 1 for i in ce.inputs})

    if v_shard is not None:
        # 4 bytes of code tile + 1 mask byte per needed column ride on
        # every uploaded probe row; count what per-shard pruning saved
        nbytes_row = 4 + sum(
            int(probe.host_col(pscan.columns[ji]).data.dtype.itemsize) + 1
            for ji in needed_p)
        shard_mod.count_shard_pruned(v_shard, nbytes_row, block_rows,
                                     probe.nrows)
        if zonemap.verify_enabled(settings) and \
                (v_shard == zonemap.SKIP).any():
            full = probe.pin[0] if probe.pin is not None else \
                probe.provider.full_batch(pscan.columns)
            full = Batch(list(pscan.columns),
                         [full.column(c) for c in pscan.columns])
            spans = [(int(b) * block_rows,
                      min((int(b) + 1) * block_rows, probe.nrows))
                     for b in np.flatnonzero(v_shard == zonemap.SKIP)]
            shard_mod.verify_sharded_pruned(
                groups, full, spans,
                f"fused shard filter {probe.provider.name}")

    n_blocks = (probe.nrows + block_rows - 1) // block_rows
    if verdicts is None:
        alive = [b for b in range(n_blocks)
                 if b * block_rows < phi and (b + 1) * block_rows > plo]
    else:
        alive = [int(b) for b in np.flatnonzero(verdicts != zonemap.SKIP)
                 if int(b) * block_rows < phi and
                 int(b) * block_rows >= plo]
    per_shard: dict[int, list[tuple[int, int]]] = {}
    for b in alive:
        s = shard_mod.shard_of_block(b, n_shards)
        per_shard.setdefault(s, []).append(
            (b * block_rows, min((b + 1) * block_rows, probe.nrows)))
    shard_ids = sorted(per_shard)
    pruned = int((v_shard == zonemap.SKIP).sum()) \
        if v_shard is not None else 0
    if not shard_ids:
        # zero pipelines actually ran — the Shards: line still renders
        # the pruning that short-circuited them
        shard_mod.stamp_profile(ctx, id(node), 0, pruned)
        results = _zero_results(agg_plans, group_space, sum_modes)
        return _finalize(node, key_plans, agg_plans, results, probe,
                         pscan, dictionaries, group_space, group_mode,
                         sum_modes)

    # -- build phase: ONE dispatch, outputs publication-cached ------------
    bstart, bmm_sis = _build_layout(agg_plans, sum_modes)

    # the build dispatch runs at most once per query (memoized closure)
    # and its outputs cache per (publication pair, device) — a repeat
    # query skips the build phase and its transfer entirely, leaving
    # only the per-shard probe dispatches
    build_state: dict = {}
    build_mu = threading.Lock()

    def _build_dispatch():
        with build_mu:
            if "v" in build_state:
                return build_state["v"]
            env_b = {}
            for ji in needed_b:
                name = bscan.columns[ji - nl]
                env_b[ji] = DEVICE_CACHE.column(
                    build.provider, build.pub, name,
                    (lambda s=build, n2=name: s.host_col(n2)),
                    build.zrange)
            bc_dev = DEVICE_CACHE.array(
                build.pub, "__codes__",
                (probe.pub, keyset, build.zrange, "b"),
                lambda: _code_tiles(cr, g),
                sweep=_partner_stale_pred(build.pub, probe.pub, "b",
                                          keyset))
            brow = DEVICE_CACHE.array(
                build.pub, "__rowmask__", (build.zrange,),
                lambda: _rowmask_tiles(build.n_live))
            jitted_b = _build_program(env_b)
            flat_b = []
            for ji in needed_b:
                dc = env_b[ji]
                flat_b.extend([dc.data, dc.mask])
            flat_b.extend([bc_dev, brow])
            check_cancel()
            metrics.DEVICE_OFFLOADS.add()
            # enqueue only: the build outputs stay in HBM for the probe
            # dispatches, whose readback observes the device time
            outs = jitted_b(*flat_b)
            build_state["v"] = outs
            return outs

    def _build_program(env_b):
        decode_b = [(env_b[i].scheme, env_b[i].offset) for i in needed_b]
        bkey = ("fshardb", probe.pub, build.pub, build.zrange,
                keyset) + shape_sig

        def build_program(*flat):
            arrays = {}
            for k2, ji in enumerate(needed_b):
                data = flat[2 * k2]
                scheme, off = decode_b[k2]
                if scheme != "raw":
                    data = data.astype(jnp.int32) + jnp.int32(off)
                arrays[ji] = (data, flat[2 * k2 + 1])
            base = 2 * len(needed_b)
            bcodes, bmask = flat[base], flat[base + 1]
            for ce in preds_build:
                v, ok = ce.fn([arrays[i] for i in ce.inputs])
                bb = v if v.dtype == jnp.bool_ else (v != 0)
                bmask = jnp.logical_and(bmask, jnp.logical_and(bb, ok))
            bc = jnp.where(bmask, bcodes, jnp.int32(g))
            bcols = [bmask.ravel().astype(jnp.int32)]
            bmm_out = []
            for si, (spec, side, ce) in enumerate(agg_plans):
                if side != 1 or ce is None:
                    continue
                v, ok = ce.fn([arrays[i] for i in ce.inputs])
                m = jnp.logical_and(bmask, ok)
                mi = m.ravel().astype(jnp.int32)
                bcols.append(mi)
                if spec.func in ("sum", "avg"):
                    if sum_modes[si] == "direct":
                        bcols.append(v.astype(jnp.int32).ravel() * mi)
                    else:
                        bcols.extend(_limb_cols(
                            v.astype(jnp.int32).ravel(), mi))
                elif spec.func in ("min", "max"):
                    bmm_out.append(ops_agg.group_min_max(
                        bcodes, m, v.astype(jnp.int32), space, spec.func))
            bacc = jnp.zeros((space, len(bcols)), jnp.int32) \
                .at[bc.ravel()].add(jnp.stack(bcols, axis=1))
            bacc = bacc.at[g].set(0).at[g + 1].set(0)
            return (bacc, *bmm_out)

        return obs_device.compiled("fused_build", bkey,
                                   lambda: build_program,
                                   profile=prof, node_key=id(node))

    # -- probe phase: one dispatch per shard, pinned across the mesh ------
    devs = mesh_mod.shard_devices(n_shards)

    def _build_outs_for(device, dev_tag: str):
        """The build outputs committed to one shard device, via the
        publication-keyed cache (tag position 0/1/3 match the partner
        sweep predicate)."""
        def make():
            outs = _build_dispatch()
            if device is not None:
                outs = tuple(jax.device_put(o, device) for o in outs)
            return outs
        return DEVICE_CACHE.tuple_arrays(
            build.pub, "__bacc__",
            (probe.pub, keyset, (build.zrange, shape_sig), dev_tag),
            make,
            sweep=_partner_stale_pred(build.pub, probe.pub, dev_tag,
                                      keyset, name="__bacc__"))

    # in-program cross-shard combine (serene_shard_combine=device): the
    # sharded probe executes as ONE shard_map-partitioned dispatch with
    # psum/pmin/pmax collectives reducing the integer accumulators in
    # HBM — the host sees only the final combined result. The build
    # outputs ride the SAME publication cache (mesh-replicated), so the
    # steady state is exactly one dispatch; a cold cache adds only the
    # one build dispatch, never the N per-shard probes.
    if shard_mod.combine_mode(settings) == "device":
        return _run_fused_collective(
            node, probe, build, pscan, preds_probe,
            key_plans, group_space, group_mode, agg_plans, sum_modes,
            cl, g, dictionaries, shape_sig, ctx, prof,
            per_shard, shard_ids, pruned, keyset, needed_p,
            _build_outs_for, bstart, bmm_sis)

    def run_shard(s: int) -> list[np.ndarray]:
        check_cancel()
        device = devs[s % len(devs)] if devs else None
        spans = per_shard[s]
        spans_t = tuple(spans)
        stag = (n_shards, s)
        env_p = {}
        for ji in needed_p:
            name = pscan.columns[ji]
            env_p[ji] = DEVICE_CACHE.column_spans(
                probe.provider, probe.pub, name,
                (lambda sd=probe, n2=name: sd.host_col(n2)), spans,
                stag, device)
        side_tag = f"ps{n_shards}.{s}"
        pc_dev = DEVICE_CACHE.array(
            probe.pub, "__codes__", (build.pub, keyset, spans_t, side_tag),
            lambda: _code_tiles(
                np.concatenate([cl[a - plo:b - plo] for a, b in spans]),
                g + 1),
            sweep=_partner_stale_pred(probe.pub, build.pub, side_tag,
                                      keyset),
            device=device)
        n_live_s = sum(b - a for a, b in spans)
        prow = DEVICE_CACHE.array(
            probe.pub, "__rowmask__", (spans_t, stag),
            lambda: _rowmask_tiles(n_live_s), device=device)

        decode_p = [(env_p[i].scheme, env_p[i].offset) for i in needed_p]
        pkey = ("fshardp", probe.pub, build.pub, spans_t, stag,
                keyset) + shape_sig

        def probe_program(*flat):
            arrays = {}
            for k2, ji in enumerate(needed_p):
                data = flat[2 * k2]
                scheme, off = decode_p[k2]
                if scheme != "raw":
                    data = data.astype(jnp.int32) + jnp.int32(off)
                arrays[ji] = (data, flat[2 * k2 + 1])
            base = 2 * len(needed_p)
            pcodes, pmask = flat[base], flat[base + 1]
            bacc = flat[base + 2]
            bmm = {si: flat[base + 3 + j]
                   for j, si in enumerate(bmm_sis)}
            # ONE probe-phase body shared with the single-dispatch
            # program — the bit-identity contract lives in one place
            return _probe_phase(arrays, pcodes, pmask, bacc, bmm,
                                preds_probe, key_plans, group_mode,
                                group_space, agg_plans, sum_modes,
                                bstart, g)

        jitted_p = obs_device.compiled("fused_probe", pkey,
                                       lambda: probe_program,
                                       profile=prof, node_key=id(node))

        # cache the committed build outputs per PHYSICAL device (two
        # shards mapped onto one device share a single copy)
        dev_tag = f"bacc{device.id}" if device is not None else "bacc"
        bouts = _build_outs_for(device, dev_tag)
        flat = []
        for ji in needed_p:
            dc = env_p[ji]
            flat.extend([dc.data, dc.mask])
        flat.extend([pc_dev, prow])
        flat.extend(bouts)
        metrics.DEVICE_OFFLOADS.add()
        return obs_device.dispatch(jitted_p, flat, profile=prof,
                                   node_key=id(node))

    shard_outs = shard_mod.run_shard_tasks(settings, run_shard, shard_ids)
    with stage("device_finalize"):
        results = _combine_shard_results(agg_plans, sum_modes, shard_outs)
    shard_mod.stamp_profile(ctx, id(node), len(shard_ids), pruned)
    return _finalize(node, key_plans, agg_plans, results, probe, pscan,
                     dictionaries, group_space, group_mode, sum_modes)


# -- in-program collective combine (serene_shard_combine=device) ------------
#
# The sharded fused join/aggregate as ONE shard_map-partitioned program
# over the parallel/mesh.py data axis: the surviving shard spans'
# tiles concatenate and split evenly across a leading mesh axis
# committed with a NamedSharding (the ragged tail pads with masked
# rows that never count — integer adds and min/max selections are
# exact over ANY row partition, so balanced re-slicing keeps
# bit-identity), the publication-cached build outputs enter
# mesh-REPLICATED, and the cross-shard reduction happens IN HBM —
# every probe-phase group accumulator reduces with a psum/pmin/pmax
# round before the (replicated) outputs return. The single dispatch is
# bit-identical to both the per-shard host combine and the shards=1
# program. Replaces PR 9's N probe dispatches + the numpy combine with
# ONE dispatch whose output is already the global answer (the build
# dispatch runs only on a publication-cache miss, exactly as in the
# host-combine path).


def _collective_out_kinds(agg_plans) -> list[str]:
    """Per-output cross-shard combine kinds mirroring _probe_phase's
    output order (the device_agg._out_combines sibling): every add
    accumulator psums (limb and direct sums alike — both are int32
    adds), min/max partials pmin/pmax."""
    kinds = ["sum"]                          # pair counts
    for si, (spec, _side, _ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            continue
        if spec.func == "count":
            kinds.append("sum")
        elif spec.func in ("sum", "avg"):
            kinds.extend(["sum", "sum"])     # value (limb/direct) + vcnt
        else:
            kinds.extend([spec.func, "sum"])  # mm partial + vcnt
    return kinds


def _run_fused_collective(node, probe: _Side, build: _Side, pscan,
                          preds_probe,
                          key_plans, group_space: int, group_mode: bool,
                          agg_plans, sum_modes: dict, cl: np.ndarray,
                          g: int, dictionaries,
                          shape_sig: tuple, ctx, prof,
                          per_shard: dict, shard_ids: list,
                          pruned: int, keyset, needed_p,
                          build_outs_for, bstart: dict,
                          bmm_sis: list) -> Batch:
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..columnar.device import host_tile_arrays
    from ..parallel import mesh as mesh_mod
    from . import shard as shard_mod
    from .plan import check_cancel
    from .shard import _concat_spans

    plo = probe.lo
    S = len(shard_ids)
    mesh = mesh_mod.data_mesh(S)
    M = mesh.shape[mesh_mod.AXIS]
    # the surviving shard spans concatenate (ascending shard, ascending
    # span — deterministic) and split EVENLY across the mesh axis:
    # integer adds and min/max selections are exact over ANY row
    # partition, so re-slicing for balance keeps bit-identity while
    # ragged shards cost < M·BLOCK_ROWS padding rows instead of padding
    # every shard to the widest one
    all_spans = [sp for s in shard_ids for sp in per_shard[s]]
    n_rows = sum(e - a for a, e in all_spans)
    t_slice = pad_len(-(-n_rows // M)) // LANES   # tiles per mesh slice
    rows_pad = M * t_slice * LANES
    spans_sig = tuple((s, tuple(per_shard[s])) for s in shard_ids)
    stack_tag = ("collstack", spans_sig, M, t_slice)
    sh3 = mesh_mod.data_sharding(mesh, 3)

    # -- shard-sharded inputs, publication-cached -------------------------

    def _for_spec(ji: int) -> tuple[str, int]:
        """Frame-of-reference scheme for one stacked column, decided
        ONCE from whole-column stats (cached per publication) so every
        mesh slice encodes with the same offset — range-fitting int
        tiles ship as uint8/uint16 deltas and decode in-kernel, the
        to_device_column compression restated for the stacked layout.
        Eligibility comes from the SCAN SCHEMA (dictionary strings ride
        int32 codes; never materializes a host column — _col_stats is
        publication-cached, so the warm path stays zero-host-work)."""
        t = pscan.types[ji]
        if t.is_string:
            kind, size = "i", 4              # dictionary codes
        else:
            try:
                nd = np.dtype(t.np_dtype)
            except Exception:  # pragma: no cover — exotic type ⇒ raw
                return "raw", 0
            kind, size = nd.kind, nd.itemsize
        if kind != "i" or size <= 1:
            return "raw", 0
        try:
            _av, _fin, lo_v, hi_v = _col_stats(probe, pscan.columns[ji])
        except Exception:  # noqa: BLE001 — unstatable column ⇒ raw
            return "raw", 0
        if lo_v is None or not (-2**31 <= lo_v and hi_v < 2**31):
            return "raw", 0
        rng = hi_v - lo_v
        if rng < (1 << 8):
            return "for8", lo_v
        if rng < (1 << 16):
            return "for16", lo_v
        return "raw", 0

    decode_p = {ji: _for_spec(ji) for ji in needed_p}

    def _stack_probe_col(name: str, scheme: str, offset: int):
        def mk():
            d2, m2 = host_tile_arrays(
                _concat_spans(probe.host_col(name), all_spans), rows_pad,
                scheme, offset)
            return (jax.device_put(
                        d2.reshape(M, t_slice, LANES), sh3),
                    jax.device_put(
                        m2.reshape(M, t_slice, LANES), sh3))
        return DEVICE_CACHE.tuple_arrays(probe.pub, name, stack_tag, mk)

    env_p = {ji: _stack_probe_col(pscan.columns[ji], *decode_p[ji])
             for ji in needed_p}

    def _stack_codes():
        padded = np.full(rows_pad, g + 1, dtype=np.int32)
        rows = np.concatenate(
            [cl[a - plo:b - plo] for a, b in all_spans])
        padded[:len(rows)] = rows
        return jax.device_put(padded.reshape(M, t_slice, LANES), sh3)

    pc_dev = DEVICE_CACHE.array(
        probe.pub, "__codes__", (build.pub, keyset, stack_tag, "pcoll"),
        _stack_codes,
        sweep=_partner_stale_pred(probe.pub, build.pub, "pcoll", keyset))

    def _stack_rowmask():
        m = np.zeros(rows_pad, dtype=bool)
        m[:n_rows] = True
        return jax.device_put(m.reshape(M, t_slice, LANES), sh3)

    prow = DEVICE_CACHE.array(probe.pub, "__rowmask__", stack_tag,
                              _stack_rowmask)

    # build outputs: the SAME publication-cached dispatch products the
    # host-combine path consumes, committed mesh-REPLICATED (every
    # device reads the full per-code partials) — a repeat query enters
    # the collective dispatch with zero build work and zero transfer
    rep_sh = NamedSharding(mesh, P())
    bouts = build_outs_for(rep_sh, f"coll{M}")

    # -- the single collective program ------------------------------------
    out_kinds = _collective_out_kinds(agg_plans)
    np_cols = len(needed_p)

    # the traced program depends only on (slice shape, mesh width,
    # publications [which pin decode schemes/code space/layout], key
    # set, expression shapes) — NOT on which spans survived pruning:
    # span-dependent values all enter as runtime inputs, so two
    # queries with different pruning patterns but equal t_slice reuse
    # one compiled executable (spans_sig keys only the DATA caches)
    cache_key = ("fcollective", probe.pub, build.pub,
                 t_slice, M, keyset) + shape_sig

    def build_collective():
        def collective(*flat):
            # local probe slice: (1, t_slice, L) tiles → one row block
            # (the mesh slice is just a row subset; the group scatter
            # is the same int add in any order)
            arrays = {}
            for k2, ji in enumerate(needed_p):
                d, m = flat[2 * k2], flat[2 * k2 + 1]
                d = d.reshape(-1, d.shape[-1])
                scheme, off = decode_p[ji]
                if scheme != "raw":
                    d = d.astype(jnp.int32) + jnp.int32(off)
                arrays[ji] = (d, m.reshape(-1, m.shape[-1]))
            base = 2 * np_cols
            pcodes = flat[base].reshape(-1, flat[base].shape[-1])
            pmask = flat[base + 1].reshape(-1, flat[base + 1].shape[-1])
            bacc = flat[base + 2]
            bmm = {si: flat[base + 3 + j] for j, si in enumerate(bmm_sis)}

            # probe phase: THE shared body (bit-identity contract in
            # one place), then the cross-shard psum/pmin/pmax combine
            outs = _probe_phase(arrays, pcodes, pmask, bacc, bmm,
                                preds_probe, key_plans, group_mode,
                                group_space, agg_plans, sum_modes,
                                bstart, g)
            return mesh_mod.apply_axis_combines(outs, out_kinds,
                                                fuse_sums=True)

        in_specs = tuple([P(mesh_mod.AXIS, None, None)] * (2 * np_cols)
                         + [P(mesh_mod.AXIS, None, None)] * 2
                         + [P()] * (1 + len(bmm_sis)))
        out_specs = tuple(P() for _ in out_kinds)
        # check_vma off: replication of the post-psum outputs holds by
        # construction but the checker can't infer it through the
        # scatter/gather bodies
        return shard_map(
            collective, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)

    jitted = obs_device.compiled("fused_collective", cache_key,
                                 build_collective, profile=prof,
                                 node_key=id(node))

    flat_args: list = []
    for ji in needed_p:
        flat_args.extend(env_p[ji])
    flat_args.extend([pc_dev, prow])
    flat_args.extend(bouts)

    check_cancel()
    t_d = time.perf_counter_ns()
    metrics.DEVICE_OFFLOADS.add()
    metrics.COLLECTIVE_DISPATCHES.add()
    # the shard workloads still execute — as lanes of one program
    metrics.SHARD_PIPELINES.add(S)
    from ..obs.resources import wait_scope
    with wait_scope("Device", "CollectiveCombine"), \
            span("collective_dispatch", "device", shards=S, mesh=M):
        results = obs_device.dispatch(jitted, flat_args, profile=prof,
                                      node_key=id(node))
    metrics.COLLECTIVE_COMBINE_NS.add(time.perf_counter_ns() - t_d)
    shard_mod.stamp_profile(ctx, id(node), S, pruned, collective=True)
    return _finalize(node, key_plans, agg_plans, results, probe, pscan,
                     dictionaries, group_space, group_mode, sum_modes)


def _mm_ident(func: str) -> int:
    info = np.iinfo(np.int32)
    return info.max if func == "min" else info.min


def _limb_cols(vals, weights) -> list:
    """Exact weighted int-sum columns: the 8-bit limb decomposition of
    ops_agg.group_sum_int_limbs, multiplicity-weighted and returned as
    5 per-row int32 columns [4 byte-limbs · w, (v < 0) · w] for the
    caller's fused scatter; host recombines in int64
    (ops_agg.combine_sum_int_limbs). Exact while 255 · Σw < 2^31 per
    group (the MAX_PAIRS_EXACT admission bound)."""
    import jax.numpy as jnp
    vu = jax.lax.bitcast_convert_type(vals, jnp.uint32)
    cols = [(jnp.right_shift(vu, 8 * limb) &
             jnp.uint32(0xFF)).astype(jnp.int32) * weights
            for limb in range(4)]
    cols.append((vals < 0).astype(jnp.int32) * weights)
    return cols


def _code_tiles(codes: np.ndarray, sentinel: int,
                pad: Optional[int] = None) -> "jax.Array":
    """Factorized join codes → int32 device tiles; padding rows take the
    side's never-matches sentinel. `pad` rounds rows up to that multiple
    (the fused tier's pow2 bucket)."""
    import jax.numpy as jnp
    n = len(codes)
    n_pad = pad_len(n) if pad is None else pad_len(n, pad)
    padded = np.full(n_pad, sentinel, dtype=np.int32)
    padded[:n] = codes
    return jnp.asarray(padded.reshape(-1, LANES))


def _join_codes(join, probe: _Side, build: _Side
                ) -> tuple[np.ndarray, np.ndarray, int, int]:
    """PR-3 key-code factorization over BOTH sides (one shared dense
    int64 code space), with NULL-key rows already rewritten to the
    per-side sentinel (g for build, g+1 for probe) so NULL never
    matches, plus the worst-case matched-pair count for the exactness
    admission (computed over the UNSLICED sides — an upper bound of any
    zone-sliced run, so admission stays sound). Cached per publication
    pair — repeat queries skip both the O(n log n) factorize and the
    O(n) pair count."""
    from .morsel import combined_codes, rows_valid
    keyset = (tuple(_expr_key(k) for k in join.left_keys),
              tuple(_expr_key(k) for k in join.right_keys))
    ck = (probe.pub, build.pub, keyset)
    with _codes_lock:
        hit = _CODES_CACHE.get(ck)
        if hit is not None:
            _CODES_CACHE.move_to_end(ck)
            return hit
    pbatch = probe.pin[0] if probe.pin is not None \
        else probe.provider.full_batch(probe.scan.columns)
    bbatch = build.pin[0] if build.pin is not None \
        else build.provider.full_batch(build.scan.columns)
    pbatch = Batch(list(probe.scan.columns),
                   [pbatch.column(c) for c in probe.scan.columns])
    bbatch = Batch(list(build.scan.columns),
                   [bbatch.column(c) for c in build.scan.columns])
    try:
        lkeys = [k.eval(pbatch) for k in join.left_keys]
        rkeys = [k.eval(bbatch) for k in join.right_keys]
    except Exception as e:
        # the host path evaluates keys only over filter-surviving rows;
        # an eval error on a filtered-out row must fall back, not surface
        raise NotCompilable(f"key eval over unfiltered rows: {e}")
    pair = combined_codes(lkeys, rkeys)
    if pair is None:
        raise NotCompilable("join keys have no shared code representation")
    cl, cr, g = pair
    lvalid = rows_valid(lkeys)
    rvalid = rows_valid(rkeys)
    cl = cl.astype(np.int64)
    cr = cr.astype(np.int64)
    if lvalid is not None:
        cl = np.where(lvalid, cl, g + 1)
    if rvalid is not None:
        cr = np.where(rvalid, cr, g)
    total_pairs = 0
    if len(cl) and len(cr) and g:
        bc_counts = np.bincount(cr[cr < g], minlength=g)
        pl = cl[cl < g]
        total_pairs = int(bc_counts[pl].sum()) if len(pl) else 0
    value = (cl, cr, g, total_pairs)
    nbytes = int(cl.nbytes) + int(cr.nbytes)
    global _codes_bytes
    with _codes_lock:
        # superseded generations of the same (table pair, keyset) are
        # unreachable — publications are monotone — sweep them first
        stale = [k for k in _CODES_CACHE
                 if k[2] == keyset and k[0][0] == ck[0][0] and
                 k[1][0] == ck[1][0] and k != ck]
        for k in stale:
            old = _CODES_CACHE.pop(k)
            _codes_bytes -= int(old[0].nbytes) + int(old[1].nbytes)
        while _CODES_CACHE and (
                len(_CODES_CACHE) >= _CODES_CACHE_MAX or
                _codes_bytes + nbytes > _CODES_CACHE_MAX_BYTES):
            _, old = _CODES_CACHE.popitem(last=False)
            _codes_bytes -= int(old[0].nbytes) + int(old[1].nbytes)
        _CODES_CACHE[ck] = value
        _codes_bytes += nbytes
    return value


def _plan_group_keys(node, join_types, probe: _Side, pscan, dictionaries
                     ) -> tuple[list, int]:
    """Direct coding of the probe-side group keys (device_agg's
    _plan_direct_keys, join-namespace variant): dictionary codes for
    strings, offset small-range coding for ints; the NULL group takes
    slot size-1, matching factorize_keys' (values asc, NULL last)
    composite order so the host oracle's group order is reproduced."""
    key_plans = []
    group_space = 1
    for gx in node.group_exprs:
        t = join_types[gx.index]
        if t.is_string:
            d = dictionaries.get(gx.index)
            if d is None:
                raise NotCompilable("string group key without dictionary")
            size = len(d) + 1
            key_plans.append(("dict", gx.index, 0, size))
        elif t.is_integer or t.id in (dt.TypeId.BOOL, dt.TypeId.DATE):
            col = probe.host_col(pscan.columns[gx.index])
            if col.data.size == 0:
                lo, hi = 0, 0
            else:
                lo, hi = int(col.data.min()), int(col.data.max())
            rng = hi - lo + 1
            if rng > MAX_INT_KEY_RANGE:
                raise NotCompilable("group key range too large")
            if not (-2**31 <= lo and hi < 2**31):
                raise NotCompilable("group key offset beyond int32")
            size = rng + 1
            key_plans.append(("int", gx.index, lo, size))
        else:
            raise NotCompilable(f"group key type {t}")
        group_space *= size
        if group_space > MAX_GROUP_PRODUCT:
            raise NotCompilable("group code space too large")
    return key_plans, group_space


def _zero_results(agg_plans, group_space: int, sum_modes: dict,
                  star_filter=None, distinct_plans=None) -> list:
    """Host-side zero accumulators matching the program's output spec —
    the no-surviving-rows short-circuit (empty table or every block
    zone-pruned) never dispatches."""
    star_filter = star_filter or {}
    distinct_plans = distinct_plans or {}
    out = [np.zeros(group_space, dtype=np.int32)]
    for si, (spec, side, ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            if si in star_filter:
                out.append(np.zeros(group_space, dtype=np.int32))
            continue
        if si in distinct_plans:
            vspace = distinct_plans[si][3]
            out.append(np.zeros(group_space * vspace, dtype=np.int32))
            continue
        if spec.func == "count":
            out.append(np.zeros(group_space, dtype=np.int32))
        elif spec.func in ("sum", "avg"):
            if sum_modes[si] == "direct":
                out.append(np.zeros(group_space, dtype=np.int32))
            else:
                out.append(np.zeros((group_space, 5), dtype=np.int32))
            out.append(np.zeros(group_space, dtype=np.int32))
        else:
            out.append(np.full(group_space, _mm_ident(spec.func),
                               dtype=np.int32))
            out.append(np.zeros(group_space, dtype=np.int32))
    return out


def _finalize(node, key_plans, agg_plans, results, probe: _Side, pscan,
              dictionaries, group_space: int, group_mode: bool,
              sum_modes: dict, star_filter=None, distinct_plans=None,
              slots=None) -> Batch:
    """Device accumulators → result batch, bit-matching the host oracle:
    groups emit in ascending composite-code order (= factorize_keys
    order), int sums recombine from limbs in int64, empty groups /
    scalar aggregates go NULL exactly where the oracle's do.

    `slots=(codes, row_lo, row_hi)` is the chained-top-N entry: results
    arrive pre-gathered to the selected group rows (stage 2's top_k
    indices), `codes` holds those rows' composite group codes, and only
    rows [row_lo, row_hi) emit (host-side OFFSET/LIMIT slice)."""
    with stage("device_finalize"):
        star_filter = star_filter or {}
        distinct_plans = distinct_plans or {}
        ri = iter(results)
        pair_counts = np.asarray(next(ri)).astype(np.int64)
        if slots is not None:
            slot_codes, row_lo, row_hi = slots
            present = np.arange(row_lo, row_hi)
        elif group_mode:
            present = np.flatnonzero(pair_counts > 0)
        else:
            present = np.asarray([0])
        cols: list[Column] = []
        if group_mode:
            sizes = [kp[3] for kp in key_plans]
            rem = slot_codes[row_lo:row_hi].copy() if slots is not None \
                else present.copy()
            key_codes = []
            for size in reversed(sizes):
                key_codes.append(rem % size)
                rem //= size
            key_codes.reverse()
            for pos, ((kind, ji, lo, size), kc) in \
                    enumerate(zip(key_plans, key_codes)):
                null_mask = kc == (size - 1)
                t = node.group_exprs[pos].type
                if kind == "dict":
                    d = dictionaries[ji]
                    data = np.where(null_mask, 0, kc).astype(np.int32)
                    cols.append(Column(
                        t, data, ~null_mask if null_mask.any() else None, d))
                else:
                    data = (kc + lo).astype(t.np_dtype)
                    data = np.where(null_mask, 0, data).astype(t.np_dtype)
                    cols.append(Column(
                        t, data, ~null_mask if null_mask.any() else None))
        for si, (spec, side, ce) in enumerate(agg_plans):
            if si in distinct_plans:
                cols.append(_distinct_result_col(
                    spec, np.asarray(next(ri)), distinct_plans[si],
                    group_space, group_mode, present))
                continue
            if spec.func == "count_star" and si in star_filter:
                c = np.asarray(next(ri)).astype(np.int64)
                if group_mode:
                    cols.append(Column(dt.BIGINT, c[present]))
                else:
                    cols.append(Column.from_pylist([int(c[0])], spec.type))
                continue
            cols.append(_agg_result_col(spec, ri, pair_counts, present,
                                        group_mode,
                                        sum_modes.get(si, "limb"),
                                        dictionaries))
        return Batch(list(node.names), cols)


def _distinct_result_col(spec: AggSpec, grid: np.ndarray, dplan,
                         group_space: int, group_mode: bool,
                         present) -> Column:
    """Presence grid → count/sum/avg DISTINCT, exactly: a cell is
    present iff ≥ 1 surviving (group, value) occurrence scattered into
    it; counts are presences per group, sums recombine value · present
    in int64 (values are the direct-coded axis, so the grid IS the
    distinct value set)."""
    _dkind, _ji, lo_v, vspace = dplan
    if grid.ndim == 1:
        grid = grid.reshape(group_space, vspace)
    pres = grid[present] > 0                   # (rows, vspace)
    cnt = pres.sum(axis=1).astype(np.int64)
    if spec.func == "count":
        if group_mode:
            return Column(dt.BIGINT, cnt)
        return Column.from_pylist([int(cnt[0])], spec.type)
    vals = (np.int64(lo_v) + np.arange(vspace, dtype=np.int64))
    sums = (pres * vals).sum(axis=1)
    t = spec.type
    if group_mode:
        empty = cnt == 0
        if spec.func == "avg":
            with np.errstate(invalid="ignore", divide="ignore"):
                data = np.where(empty, 0.0, sums / np.maximum(cnt, 1))
            return Column(dt.DOUBLE, data, ~empty if empty.any() else None)
        if t.is_integer:
            return Column(dt.BIGINT, sums,
                          ~empty if empty.any() else None)
        return Column(dt.DOUBLE, sums.astype(np.float64),
                      ~empty if empty.any() else None)
    s, n = int(sums[0]), int(cnt[0])
    if n == 0:
        return Column.from_pylist([None], t)
    if spec.func == "avg":
        return Column.from_pylist([s / n], t)
    return Column.from_pylist([s if t.is_integer else float(s)], t)


def _agg_result_col(spec: AggSpec, ri, pair_counts, present,
                    group_mode: bool, sum_mode: str = "limb",
                    dictionaries=None) -> Column:
    t = spec.type
    if spec.func == "count_star":
        if group_mode:
            return Column(dt.BIGINT, pair_counts[present])
        return Column.from_pylist([int(pair_counts[0])], t)
    if spec.func == "count":
        c = np.asarray(next(ri)).astype(np.int64)
        if group_mode:
            return Column(dt.BIGINT, c[present])
        return Column.from_pylist([int(c[0])], t)
    if spec.func in ("sum", "avg"):
        raw = np.asarray(next(ri))
        cnt = np.asarray(next(ri)).astype(np.int64)
        sums = raw.astype(np.int64) if sum_mode == "direct" \
            else ops_agg.combine_sum_int_limbs(raw)
        if group_mode:
            sums, cnt = sums[present], cnt[present]
            empty = cnt == 0
            if spec.func == "avg":
                with np.errstate(invalid="ignore", divide="ignore"):
                    data = np.where(empty, 0.0, sums / np.maximum(cnt, 1))
                return Column(dt.DOUBLE, data,
                              ~empty if empty.any() else None)
            if t.is_integer:
                return Column(dt.BIGINT, sums,
                              ~empty if empty.any() else None)
            return Column(dt.DOUBLE, sums.astype(np.float64),
                          ~empty if empty.any() else None)
        s, n = int(sums[0]), int(cnt[0])
        if n == 0:
            return Column.from_pylist([None], t)
        if spec.func == "avg":
            return Column.from_pylist([s / n], t)
        return Column.from_pylist([s if t.is_integer else float(s)], t)
    if spec.func in ("min", "max"):
        v = np.asarray(next(ri)).astype(np.int64)
        cnt = np.asarray(next(ri)).astype(np.int64)
        at = spec.arg.type
        if at.is_string:
            # min/max ran over sorted-dictionary codes (code order ==
            # string order); decode back through the dictionary
            d = (dictionaries or {}).get(spec.arg.index)
            if group_mode:
                v, cnt = v[present], cnt[present]
                empty = cnt == 0
                codes = np.where(empty, 0, v).astype(np.int32)
                return Column(at, codes,
                              ~empty if empty.any() else None, d)
            if int(cnt[0]) == 0:
                return Column.from_pylist([None], t)
            return Column.from_pylist([str(d[int(v[0])])], t)
        if group_mode:
            v, cnt = v[present], cnt[present]
            empty = cnt == 0
            data = np.where(empty, 0, v).astype(at.np_dtype)
            return Column(at, data, ~empty if empty.any() else None)
        if int(cnt[0]) == 0:
            return Column.from_pylist([None], t)
        out = int(v[0])
        if at.id is dt.TypeId.BOOL:
            out = bool(out)
        return Column.from_pylist([out], t)
    raise NotCompilable(spec.func)


# -- fused filtered top-N ----------------------------------------------------


def _col_stats(side: _Side, name: str) -> tuple:
    return col_stats(side.pub, name, side.host_col)


def col_stats(pub: tuple, name: str, host_col) -> tuple:
    """(all_valid, finite_all, lo, hi) of one column of the publication
    `pub` (`_pub`), read through `host_col(name)` — a pure function of
    the publication, so cached repeats skip the O(n) host scans.
    lo/hi are None for float columns (only finiteness gates those) and
    span EVERY slot including NULL ones (garbage under an invalid slot
    widens the range, which can only make callers more conservative)."""
    ck = (pub, name)
    with _col_stats_lock:
        hit = _COL_STATS_CACHE.get(ck)
        if hit is not None:
            _COL_STATS_CACHE.move_to_end(ck)
            return hit
    host = host_col(name)
    all_valid = bool(host.valid_mask().all())
    if host.data.dtype.kind == "f":
        stats = (all_valid, bool(np.isfinite(host.data).all()), None, None)
    elif host.data.size == 0:
        stats = (all_valid, True, 0, 0)
    else:
        stats = (all_valid, True,
                 int(host.data.min()), int(host.data.max()))
    with _col_stats_lock:
        while len(_COL_STATS_CACHE) >= _COL_STATS_MAX:
            _COL_STATS_CACHE.popitem(last=False)
        _COL_STATS_CACHE[ck] = stats
    return stats


def try_device_fused_topn(limit_node, ctx) -> Optional[Batch]:
    """One-dispatch ORDER BY col LIMIT k over a FILTERED scan: the
    compiled predicate masks filtered-out rows to the sort sentinel
    inside the same program as `top_k`, so Filter→Sort→Limit is one
    dispatch (device_topn covers only the unfiltered shape). None → CPU
    lexsort oracle."""
    from .plan import FilterNode, ProjectNode, ScanNode, SortNode
    from .device_topn import MAX_TOPN_K

    settings = ctx.settings
    if settings.get("serene_device") == "cpu" or not fused_enabled(settings):
        return None
    if limit_node.limit is None:
        return None

    def decline(reason: str) -> None:
        _note_decline(reason, ctx, limit_node)
        return None

    k = limit_node.limit + limit_node.offset
    if k == 0:
        return None
    if k > MAX_TOPN_K:
        return decline("topn_k")
    sort = limit_node.child
    if not isinstance(sort, SortNode) or len(sort.key_indices) != 1 or \
            sort.nulls_first[0] is not None:
        return None
    proj = None
    inner = sort.child
    if isinstance(inner, ProjectNode):
        proj = inner
        inner = inner.child
    side = _unwrap_side(inner)
    if side is None or not side[1]:
        return None       # unfiltered shape: device_topn's territory
    scan, preds = side
    ki = sort.key_indices[0]
    if proj is not None:
        # plain column projections only: the host oracle evaluates the
        # Project over EVERY filter-surviving row, the fused path over
        # only the k selected ones — a computed expression that raises
        # (100/b with a zero outside the top k) or draws state would
        # diverge, so anything beyond column selection/reorder falls back
        if not all(isinstance(e, BoundColumn) for e in proj.exprs):
            return decline("topn_project")
        ki = proj.exprs[ki].index
    t = scan.types[ki]
    if not (t.is_integer or t.id in (dt.TypeId.DATE, dt.TypeId.FLOAT)):
        return decline("topn_key_type")
    provider = scan.provider
    if settings.get("serene_device") == "auto":
        try:
            if provider.row_count() < settings.get("serene_device_min_rows"):
                return None
        except NotImplementedError:
            return None
    desc = bool(sort.descs[0])
    try:
        with stage("device_prepare", op="fused_topn"):
            return _run_fused_topn(limit_node, scan, preds, ki, desc, k,
                                   ctx, proj)
    except (NotCompilable, DeviceNarrowingError) as e:
        log.debug("device", f"fused top-N fell back to CPU: {e}")
        return decline(getattr(e, "reason", "not_compilable"))


def _run_fused_topn(limit_node, scan, preds, ki: int, desc: bool, k: int,
                    ctx, proj=None) -> Optional[Batch]:
    import jax.numpy as jnp
    from .device_topn import _I32_MIN, _I32_MAX
    from .plan import check_cancel

    side = _Side(scan, preds, ctx)
    if side.nrows == 0 or side.n_live == 0:
        from .plan import empty_batch
        if proj is not None:
            return empty_batch(list(proj.names),
                               [e.type for e in proj.exprs])
        return empty_batch(list(scan.names), list(scan.types))
    name = scan.columns[ki]
    all_valid, finite_all, lo_v, hi_v = _col_stats(side, name)
    if not all_valid:
        raise NotCompilable("top-N key column has NULLs")
    if lo_v is None:                         # float key
        if not finite_all:
            raise NotCompilable("top-N float key has NaN/inf")
    else:
        if desc and lo_v <= _I32_MIN:
            raise NotCompilable("key touches int32 min")
        if not desc and hi_v >= _I32_MAX:
            raise NotCompilable("key touches int32 max")

    dicts = {}
    for e in preds:
        for sub in e.walk():
            if isinstance(sub, BoundColumn) and sub.type.is_string and \
                    sub.index not in dicts:
                col = side.host_col(scan.columns[sub.index])
                if col.dictionary is not None:
                    dicts[sub.index] = col.dictionary
    compiled = [compile_expr(p, scan.types, dicts) for p in preds]

    needed = sorted({ki} | {i for ce in compiled for i in ce.inputs})
    env_cols = {
        i: DEVICE_CACHE.column(side.provider, side.pub, scan.columns[i],
                               (lambda s=side, n=scan.columns[i]:
                                s.host_col(n)), side.zrange)
        for i in needed}
    rowmask = DEVICE_CACHE.array(side.pub, "__rowmask__", (side.zrange,),
                                 lambda: _rowmask_tiles(side.n_live))
    kc = env_cols[ki]
    is_float = kc.data.dtype.kind == "f"
    if int(kc.data.shape[0]) * LANES < k:
        raise NotCompilable("k exceeds padded rows")

    decode_specs = [(env_cols[i].scheme, env_cols[i].offset) for i in needed]
    kpos = needed.index(ki)

    cache_key = ("fusedtopn", side.pub, side.zrange, name, desc, k,
                 tuple(_expr_key(p) for p in preds))

    def program(*flat):
        arrays = {}
        for j, i in enumerate(needed):
            data = flat[2 * j]
            scheme, off = decode_specs[j]
            if scheme != "raw":
                data = data.astype(jnp.int32) + jnp.int32(off)
            arrays[i] = (data, flat[2 * j + 1])
        mask = flat[-1]
        for ce in compiled:
            v, ok = ce.fn([arrays[i] for i in ce.inputs])
            b = v if v.dtype == jnp.bool_ else (v != 0)
            mask = jnp.logical_and(mask, jnp.logical_and(b, ok))
        v = arrays[needed[kpos]][0]
        if is_float:
            keys = v if desc else -v
            sent = jnp.float32(-jnp.inf)
        else:
            v = v.astype(jnp.int32)
            keys = v if desc else ~v
            sent = jnp.int32(_I32_MIN)
        keys = jnp.where(mask.ravel(), keys.ravel(), sent)
        kk, ii = jax.lax.top_k(keys, k)
        return kk, ii.astype(jnp.int32), \
            jnp.sum(mask, dtype=jnp.int32)

    jitted = obs_device.compiled("fused_topn", cache_key,
                                 lambda: program,
                                 profile=getattr(ctx, "profile", None),
                                 node_key=id(limit_node))

    flat_args = []
    for i in needed:
        dc = env_cols[i]
        flat_args.extend([dc.data, dc.mask])
    flat_args.append(rowmask)
    check_cancel()
    metrics.DEVICE_OFFLOADS.add()
    kk, ii, nsurv = obs_device.dispatch(
        jitted, flat_args, profile=getattr(ctx, "profile", None),
        node_key=id(limit_node))
    with stage("device_finalize"):
        idx = ii.astype(np.int64)
        k_eff = min(k, int(nsurv))
        idx = idx[:k_eff]
        if side.zrange is not None:
            idx = idx + side.zrange[0]
        idx = idx[limit_node.offset:]
        if side.pin is not None and \
                all(c in side.pin[0] for c in scan.columns):
            base = Batch(list(scan.columns),
                         [side.pin[0].column(c) for c in scan.columns])
        else:
            base = side.provider.full_batch(scan.columns)
        base = base.take(idx)
        if proj is None:
            return base
        return Batch(list(proj.names), [e.eval(base) for e in proj.exprs])


# -- chained device-resident stages: fused agg → fused top-N -----------------


def _stage1_out_slots(agg_plans, star_filter, distinct_plans
                      ) -> dict[int, int]:
    """agg index → its FIRST slot in the stage-1 output tuple (mirrors
    _probe_phase's output ordering exactly)."""
    slots: dict[int, int] = {}
    pos = 1
    for si, (spec, _side, _ce) in enumerate(agg_plans):
        if spec.func == "count_star":
            if si in star_filter:
                slots[si] = pos
                pos += 1
            continue
        slots[si] = pos
        if si in distinct_plans or spec.func == "count":
            pos += 1
        else:
            pos += 2                      # sum/avg and min/max: 2 slots
    return slots


def try_device_chained_topn(limit_node, ctx) -> Optional[Batch]:
    """Whole-query device residency: Limit(Sort(Project?(Aggregate)))
    over a fused-admissible join runs as TWO chained dispatches — the
    stage-1 group accumulators NEVER leave HBM. Stage 2 takes them as
    device arrays, masks absent groups to the sort sentinel, top_k-selects
    the k requested group slots, and gathers every accumulator down to
    those k rows; the host fetches only the k-row tail. Sort keys are
    group-key columns (composite-code order == value order: sorted
    dictionaries / offset ints, NULL slot last ⇒ PG's default asc
    NULLS LAST / desc NULLS FIRST exactly) or count-family aggregates;
    min/max/sum keys decline (their device identities have no
    NULL-consistent total order to hand top_k). None → host path."""
    from .plan import AggregateNode, ProjectNode, SortNode

    settings = ctx.settings
    if settings.get("serene_device") == "cpu" or \
            not fused_enabled(settings) or \
            not fused_ext_enabled(settings):
        return None
    if limit_node.limit is None or limit_node.limit == 0:
        return None
    k = limit_node.limit + limit_node.offset
    sort = limit_node.child
    if not isinstance(sort, SortNode) or len(sort.key_indices) != 1 or \
            sort.nulls_first[0] is not None:
        return None
    proj = None
    agg = sort.child
    if isinstance(proj_c := agg, ProjectNode):
        proj = proj_c
        agg = proj_c.child
    if not isinstance(agg, AggregateNode):
        return None
    if proj is not None and not all(isinstance(e, BoundColumn)
                                    for e in proj.exprs):
        return None
    if not agg.group_exprs:
        return None               # scalar aggregate: one row, host-trivial
    if agg.chain_claimed():
        return None               # the chain program's; the host sorts

    def decline(reason: str) -> None:
        _note_decline(reason, ctx, limit_node)
        return None

    sel = sort.key_indices[0]
    if proj is not None:
        sel = proj.exprs[sel].index
    ng = len(agg.group_exprs)
    if sel >= ng:
        spec = agg.aggs[sel - ng]
        if spec.func not in ("count_star", "count") or spec.distinct:
            return decline("chain_sort_key")
    admitted = _admit_pipeline(agg, ctx, decline)
    if admitted is None:
        return None
    with stage("device_prepare", op="fused_chain"):
        return _run_chained_topn(limit_node, agg, sort, proj, sel, ng, k,
                                 ctx, decline, admitted)


def _run_chained_topn(limit_node, agg, sort, proj, sel: int, ng: int,
                      k: int, ctx, decline, admitted) -> Optional[Batch]:
    import jax.numpy as jnp
    from .device_topn import _I32_MIN
    from .plan import check_cancel

    join, probe_side, build_side, post_preds = admitted
    try:
        res = _run_fused(agg, join, probe_side, build_side, post_preds,
                         ctx, fetch=False)
    except (NotCompilable, DeviceNarrowingError) as e:
        log.debug("device", f"chained fused top-N fell back to CPU: {e}")
        return decline(getattr(e, "reason", "not_compilable"))
    if isinstance(res, Batch):
        return None               # empty short-circuit: host path, cheap
    outs, fin = res
    desc = bool(sort.descs[0])
    group_space = fin["group_space"]
    key_plans = fin["key_plans"]
    agg_plans = fin["agg_plans"]
    sum_modes = fin["sum_modes"]
    star_filter = fin["star_filter"]
    distinct_plans = fin["distinct_plans"]
    k_pad = min(_pow2_int(k, floor=8), group_space)

    if sel >= ng:
        si = sel - ng
        if agg_plans[si][0].func == "count_star" and \
                si not in star_filter:
            sort_mode = ("agg", 0)        # shared output-row counts
        else:
            sort_mode = ("agg", _stage1_out_slots(
                agg_plans, star_filter, distinct_plans)[si])
    else:
        sizes = [kp[3] for kp in key_plans]
        stride = 1
        for s2 in sizes[sel + 1:]:
            stride *= s2
        sort_mode = ("gkey", stride, sizes[sel])

    ckey = ("fused_chain", fin["stage1_key"], sort_mode, desc, k_pad,
            group_space)

    def build_stage2():
        def stage2(*souts):
            present = souts[0] > 0
            if sort_mode[0] == "agg":
                v = souts[sort_mode[1]].astype(jnp.int32)
            else:
                idx = jnp.arange(group_space, dtype=jnp.int32)
                v = (idx // jnp.int32(sort_mode[1])) % \
                    jnp.int32(sort_mode[2])
            # asc rides ~v: monotone-decreasing, exact on int32 (codes
            # < 2^21 and counts ≤ 2^23 keep ~v clear of the sentinel);
            # ties take the lowest slot = ascending composite code =
            # the host oracle's stable sort order
            sv = v if desc else ~v
            sv = jnp.where(present, sv, jnp.int32(_I32_MIN))
            _kk, ii = jax.lax.top_k(sv, k_pad)
            picked = []
            for o in souts:
                if o.ndim == 1 and o.shape[0] != group_space:
                    o = o.reshape(group_space, -1)  # DISTINCT grid
                picked.append(o[ii])
            return (ii.astype(jnp.int32),
                    jnp.sum(present, dtype=jnp.int32), *picked)
        return stage2

    prof = getattr(ctx, "profile", None)
    # no buffer donation: stage 2's outputs are k_pad-row gathers, which
    # can never alias the group_space-row accumulators (the TPU compiler
    # reports such donations "not usable"); the accumulators' HBM is
    # released when `outs` goes out of scope below
    jitted2 = obs_device.compiled("fused_chain", ckey, build_stage2,
                                  profile=prof,
                                  node_key=id(limit_node))
    check_cancel()
    metrics.DEVICE_OFFLOADS.add()
    metrics.REGISTRY.gauge(
        "DeviceChainedStages",
        "Fused agg→top-N chains executed with the intermediate "
        "accumulators handed off in HBM").add()
    fetched = obs_device.dispatch(jitted2, outs, profile=prof,
                                  node_key=id(limit_node),
                                  t0_ns=fin["t_enqueue"])
    ii_np = np.asarray(fetched[0]).astype(np.int64)
    npres = int(fetched[1])
    k_eff = min(k, npres)
    row_lo = min(limit_node.offset, k_eff)
    out = _finalize(agg, key_plans, agg_plans, list(fetched[2:]),
                    fin["probe"], fin["pscan"], fin["dictionaries"],
                    group_space, True, sum_modes,
                    star_filter=star_filter,
                    distinct_plans=distinct_plans,
                    slots=(ii_np, row_lo, k_eff))
    if proj is not None:
        out = Batch(list(proj.names),
                    [out.columns[e.index] for e in proj.exprs])
    return out
