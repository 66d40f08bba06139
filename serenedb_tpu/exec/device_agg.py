"""Device-offloaded Scan→Filter→Aggregate (the flagship TPU path).

Mirrors the reference's hottest analytics loop (morsel-parallel filter +
hash aggregate over the columnstore; ClickBench shapes in BASELINE.md) as a
single jitted XLA program per (table, query) over HBM-cached columns:

    mask   = predicate(cols) & validity          (fused elementwise)
    counts = MXU histogram / scatter over codes  (ops/agg.py)
    sums   = exact int64 via limb scatter        (ops/agg.py)

A DECIMAL is its scaled int64, so a DECIMAL argument is an integer one;
with x64 off every node of a statement that reads one is bounded inside
int32 first (`device.expr_bounds`), and a SUM / AVG whose product leaves
int32 (TPC-H Q1's `sum(l_extendedprice * (1 - l_discount) * (1 + l_tax))`
at scale 6) is summed as two int32 products (`wide_parts`).

Falls back to the CPU oracle (plan.AggregateNode._cpu_aggregate) whenever
anything in the query shape isn't device-compilable — result parity between
the two paths is asserted in tests.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .. import errors
from ..columnar import dtypes as dt
from ..columnar.column import Batch, Column
from ..columnar.device import DeviceNarrowingError, pad_len
from ..obs.trace import stage
from ..ops import agg as ops_agg
from ..sql.binder import _expr_key
from ..sql.expr import AggSpec, BoundColumn, BoundExpr, BoundFunc
from ..utils import log, metrics
from .device import DeviceExpr, NotCompilable, compile_expr, expr_bounds
from .tables import TableProvider

MAX_GROUP_PRODUCT = 1 << 21   # combined-key code-space cap
MAX_INT_KEY_RANGE = 1 << 20   # direct-coding range cap for integer keys
MAX_DISTINCT_CELLS = 1 << 22  # (group_space x value_space) presence cap

import threading as _threading

_factorize_guard = _threading.Lock()


def _factorize_lock(provider) -> "_threading.Lock":
    """Per-provider lock guarding _factorize_cache (lazily attached)."""
    lk = getattr(provider, "_factorize_cache_lock", None)
    if lk is None:
        with _factorize_guard:
            lk = getattr(provider, "_factorize_cache_lock", None)
            if lk is None:
                lk = _threading.Lock()
                provider._factorize_cache_lock = lk
    return lk

_AGG_FUNCS = {"count_star", "count", "sum", "min", "max", "avg"}


def wide_parts(a: BoundExpr, col_bounds) -> list:
    """A SUM argument x * y whose product leaves int32, as the two int32
    products of x's halves, (x >> 16) * y and (x & 0xFFFF) * y: the sum
    is 2^16 S1 + S2. [(part, weight, lo, hi)]; NotCompilable where that
    does not bound it either."""
    inner = a
    while isinstance(inner, BoundFunc) and inner.name in (
            "decimal_raw", "decimal_of"):
        inner = inner.args[0]
    if not (isinstance(inner, BoundFunc) and inner.name == "op*"):
        raise NotCompilable("sum argument leaves int32", "int_range")
    x, y = inner.args
    xlo, xhi = expr_bounds(x, col_bounds)
    ylo, yhi = expr_bounds(y, col_bounds)
    if max(abs(ylo), abs(yhi)) * 65535 >= (1 << 31):
        raise NotCompilable("sum argument leaves int32", "int_range")
    t = dt.INT

    def half(kind):
        def impl(cols, batch, _k=kind):
            v = cols[0].data.astype(np.int64)
            return Column(t, (v >> 16 if _k == "hi" else v & 0xFFFF)
                          .astype(np.int32), cols[0].validity)
        return BoundFunc(f"int32_{kind}16", [x], t, impl)

    def times(lo, hi):
        ps = (lo * ylo, lo * yhi, hi * ylo, hi * yhi)
        return min(ps), max(ps)
    # `y` as the integer its physical values are: the product's scale is
    # the whole argument's, restored above the aggregate
    plain = BoundFunc("decimal_raw", [y], t, None) if y.type.is_decimal \
        else y
    return [(BoundFunc("op*", [half("hi"), plain], t, None), 65536,
             *times(xlo >> 16, xhi >> 16)),
            (BoundFunc("op*", [half("lo"), plain], t, None), 1,
             *times(0, 0xFFFF))]


class _Wide:
    """A SUM / AVG argument summed as `wide_parts`: each part is its own
    limb sum on the device, and the host adds them at their weights."""

    def __init__(self, parts: list):
        self.parts = parts               # [(DeviceExpr, weight)]
        self.inputs = sorted({i for ce, _ in parts for i in ce.inputs})

    @property
    def weights(self) -> list:
        return [w for _, w in self.parts]


def _weights(ce) -> Optional[list]:
    return ce.weights if isinstance(ce, _Wide) else None


def _add_weighted(total, part: np.ndarray, w: int) -> np.ndarray:
    """total + part * w in int64, or 22003 where a sum leaves it."""
    part = part.astype(np.int64)
    wide = np.abs(part.astype(np.float64)) * w
    if total is not None:
        wide = wide + np.abs(total.astype(np.float64))
    if np.any(wide >= 2.0 ** 63):
        raise errors.SqlError("22003", "numeric field overflow")
    part = part * w
    return part if total is None else total + part


def try_device_aggregate(node, ctx) -> Optional[Batch]:
    """Attempt device execution of an AggregateNode; None → CPU fallback."""
    from .plan import FilterNode, ScanNode

    device = ctx.settings.get("serene_device")
    if device == "cpu":
        return None
    # unwrap Filter(Scan) / Scan
    child = node.child
    preds: list[BoundExpr] = []
    while isinstance(child, FilterNode):
        preds.append(child.pred)
        child = child.child
    if not isinstance(child, ScanNode):
        return None
    scan = child
    if scan.filter is not None:
        preds.append(scan.filter)
    provider = scan.provider
    if device == "auto" and \
            provider.row_count() < ctx.settings.get("serene_device_min_rows"):
        return None
    for spec in node.aggs:
        if spec.func not in _AGG_FUNCS or spec.filter is not None:
            return None
        if spec.distinct and spec.func in ("count", "sum", "avg") and \
                not isinstance(spec.arg, BoundColumn):
            # DISTINCT runs as a (group, value)-presence scatter; value
            # coding needs a plain column (min/max ignore DISTINCT)
            return None
    try:
        # the whole offload is the request's `device_prepare` stage,
        # except what stamps itself inside it: the program call
        # (`device_enqueue`), the readback (`device_wait`) and the host
        # decode of the outputs (`device_finalize`)
        with stage("device_prepare", op="agg"):
            return _run(node, scan, provider, preds, ctx)
    except (NotCompilable, DeviceNarrowingError) as e:
        log.debug("device", f"aggregate fell back to CPU: {e}")
        return None


def _run(node, scan, provider: TableProvider, preds: list[BoundExpr], ctx) -> Batch:
    col_names = scan.columns

    # ONE publication observation for the WHOLE query: dictionaries, key
    # planning, factorized codes, the device column environment and the
    # row mask must all come from the same (batch, version) — per-column
    # fetches could straddle a concurrent publish and hand the device
    # program columns of different lengths/row orders. Immutable
    # providers (parquet) pin nothing and read per column lazily.
    pin = provider.try_pin()
    pin_batch = pin[0] if pin is not None else None
    dev_ver = pin[1] if pin is not None else provider.data_version

    def host_col(name):
        if pin_batch is not None:
            return pin_batch.column(name)
        return provider.host_column(name)

    # only referenced string columns need their dictionary materialized
    referenced: set[int] = set()
    for e in preds + list(node.group_exprs) + \
            [s.arg for s in node.aggs if s.arg is not None]:
        for sub in e.walk():
            if isinstance(sub, BoundColumn):
                referenced.add(sub.index)
    dictionaries: dict[int, np.ndarray] = {}
    for i in sorted(referenced):
        if scan.types[i].is_string:
            col = host_col(col_names[i])
            if col.dictionary is not None:
                dictionaries[i] = col.dictionary

    compiled_preds = [compile_expr(p, scan.types, dictionaries) for p in preds]
    wide: dict[int, list] = {}
    if any(scan.types[i].is_decimal for i in referenced):
        # a DECIMAL's scaled products leave int32 where its range says
        # so (x64 off: they would wrap): bound every node, sum a product
        # past int32 as its int32 parts, or decline
        from .device_pipeline import _pub, col_stats
        pub = _pub(provider, pin)

        def col_bounds(i):
            return col_stats(pub, col_names[i], host_col)[2:]
        for e in preds:
            expr_bounds(e, col_bounds)
        for si, spec in enumerate(node.aggs):
            if spec.arg is None:
                continue
            try:
                expr_bounds(spec.arg, col_bounds)
            except NotCompilable:
                if spec.func not in ("sum", "avg") or spec.distinct:
                    raise
                wide[si] = wide_parts(spec.arg, col_bounds)

    # group keys: direct coding (dict codes / small-range ints) when it
    # fits, else composite host factorization (arbitrary keys/cardinality)
    fact = None
    try:
        key_plans, group_space = _plan_direct_keys(
            node, scan, host_col, col_names, dictionaries)
    except NotCompilable:
        if not node.group_exprs:
            raise
        fact = _factorize_group_keys(node, scan, provider, pin_batch,
                                     dev_ver)
        key_plans, group_space = [], max(fact["g"], 1)

    agg_plans = []
    for si, spec in enumerate(node.aggs):
        if spec.func == "count_star":
            agg_plans.append((spec, None))
        elif si in wide:
            agg_plans.append((spec, _Wide([
                (compile_expr(p, scan.types, dictionaries), w)
                for p, w, _lo, _hi in wide[si]])))
        else:
            if spec.arg.type.is_string and spec.func != "count":
                raise NotCompilable(f"{spec.func} over strings")
            agg_plans.append((spec, compile_expr(spec.arg, scan.types,
                                                 dictionaries)))

    # DISTINCT value plans: each count/sum/avg DISTINCT column gets a
    # direct value coding (dict codes / small-range ints); the program
    # scatters a (group, value) presence matrix and shards combine it
    # with max (reference analog: DuckDB's distinct hash aggregate —
    # re-expressed as a dense presence bitmap so the per-row work is one
    # scatter on the device and the cross-shard merge one pmax)
    distinct_plans: dict[int, tuple] = {}
    for si, (spec, ce) in enumerate(agg_plans):
        if not (spec.distinct and spec.func in ("count", "sum", "avg")):
            continue
        vi = spec.arg.index
        vt = scan.types[vi]
        if vt.is_string:
            d = dictionaries.get(vi)
            if d is None:
                raise NotCompilable("DISTINCT string without dictionary")
            distinct_plans[si] = ("dict", vi, 0, len(d) + 1)
        elif vt.is_integer or vt.id in (dt.TypeId.BOOL, dt.TypeId.DATE):
            col = host_col(col_names[vi])
            if col.data.size == 0:
                lo, hi = 0, 0
            else:
                lo, hi = int(col.data.min()), int(col.data.max())
            rng = hi - lo + 1
            if rng > MAX_INT_KEY_RANGE:
                raise NotCompilable("DISTINCT value range too large")
            if not (-2**31 <= lo and hi < 2**31):
                raise NotCompilable("DISTINCT value offset beyond int32")
            distinct_plans[si] = ("int", vi, lo, rng + 1)
        else:
            raise NotCompilable(f"DISTINCT over {vt}")
    for si in distinct_plans:
        if max(group_space, 1) * distinct_plans[si][3] > MAX_DISTINCT_CELLS:
            raise NotCompilable("DISTINCT presence matrix too large")

    # zone maps: when the filter conjuncts prove a prefix/suffix of
    # morsel blocks can't match, upload (and aggregate) only the
    # surviving contiguous row range — the skip-scan analog of the
    # chunked dispatch, applied to the transfer itself. The factorized
    # code buffer is whole-table, so the shrink only engages on directly
    # coded keys.
    nrows = pin_batch.num_rows if pin_batch is not None \
        else provider.row_count()
    zrange = None
    if preds and fact is None:
        zrange = _zonemap_range(scan, provider, preds, pin, nrows, ctx)

    # collect needed device columns
    needed: set[int] = set()
    for ce in compiled_preds:
        needed.update(ce.inputs)
    for kp in key_plans:
        needed.add(kp[1])
    for spec, ce in agg_plans:
        if ce is not None:
            needed.update(ce.inputs)
    needed = sorted(needed)
    if zrange is None:
        by_name = provider.device_columns([col_names[i] for i in needed],
                                          pin)
    else:
        by_name = _range_device_columns(
            provider, [col_names[i] for i in needed], pin, zrange)
    env_cols = {i: by_name[col_names[i]] for i in needed}
    metrics.DEVICE_OFFLOADS.add()

    import jax.numpy as jnp

    def env_for(ce: DeviceExpr, arrays):
        return [arrays[i] for i in ce.inputs]

    group_mode = bool(node.group_exprs)
    reductions = _count_reductions(group_mode, group_space, agg_plans,
                                   distinct_plans)
    n_hist = sum(map(ops_agg.hist_form, reductions))
    metrics.DEVICE_AGG_HISTOGRAM.add(n_hist)
    metrics.DEVICE_AGG_SCATTER.add(len(reductions) - n_hist)
    # capture only the flag, not the fact dict — the closure lives in the
    # program cache and must not pin the codes buffer in HBM
    has_fact = fact is not None

    # frame-of-reference columns decode in-kernel right at program entry
    # (one widen+add), so every downstream op sees logical int32 values
    decode_specs = [(env_cols[i].scheme, env_cols[i].offset)
                    for i in needed]

    def program(*flat):
        arrays = {}
        for k, i in enumerate(needed):
            data = flat[2 * k]
            scheme, off = decode_specs[k]
            if scheme != "raw":
                data = data.astype(jnp.int32) + jnp.int32(off)
            arrays[i] = (data, flat[2 * k + 1])
        rowmask = flat[-1]
        mask = rowmask
        for ce in compiled_preds:
            v, ok = ce.fn(env_for(ce, arrays))
            b = v if v.dtype == jnp.bool_ else (v != 0)
            mask = jnp.logical_and(mask, jnp.logical_and(b, ok))
        outputs = []
        if group_mode:
            if has_fact:
                codes = flat[2 * len(needed)]  # precomputed composite codes
            else:
                codes = jnp.zeros_like(mask, dtype=jnp.int32)
                for kind, idx, lo, size in key_plans:
                    data, ok = arrays[idx]
                    if kind == "dict":
                        c = data.astype(jnp.int32)
                    else:
                        c = (data.astype(jnp.int32) - jnp.int32(lo))
                    c = jnp.where(ok, c, jnp.int32(size - 1))
                    codes = codes * jnp.int32(size) + jnp.clip(c, 0, size - 1)
            outputs.append(
                ops_agg.group_count_cells(codes, mask, group_space))
            for si, (spec, ce) in enumerate(agg_plans):
                if si in distinct_plans:
                    outputs.append(_presence(
                        distinct_plans[si], arrays, codes, mask,
                        group_space))
                else:
                    outputs.extend(
                        _group_agg_device(spec, ce, arrays, codes, mask,
                                          env_for, group_space))
        else:
            outputs.append(jnp.sum(mask, dtype=jnp.int32))
            for si, (spec, ce) in enumerate(agg_plans):
                if si in distinct_plans:
                    zc = jnp.zeros_like(mask, dtype=jnp.int32)
                    outputs.append(_presence(
                        distinct_plans[si], arrays, zc, mask, 1))
                else:
                    outputs.extend(
                        _scalar_agg_device(spec, ce, arrays, mask,
                                           env_for))
        return tuple(outputs)

    mesh_n = int(ctx.settings.get("serene_mesh") or 0)
    if mesh_n > 1 and len(jax.devices()) < mesh_n:
        mesh_n = 0
    # zrange is part of the key: the frame-of-reference scheme/offset of a
    # sliced upload differs from the whole column's, and the range itself
    # flips with SET serene_zonemap — a cached program must never decode
    # an environment built under the other setting
    key = (id(provider), dev_ver,
           tuple(_expr_key(p) for p in preds),
           tuple(_expr_key(g) for g in node.group_exprs),
           tuple((s.func, s.distinct, _expr_key(s.arg))
                 for s in node.aggs), mesh_n, zrange)
    from ..obs import device as obs_device

    def build():
        if mesh_n > 1:
            combines = _out_combines(node, agg_plans, group_mode)
            return _mesh_wrap(program, mesh_n, combines,
                              n_inputs=2 * len(needed) +
                              (1 if fact is not None else 0) + 1)
        return program

    jitted = obs_device.compiled("device_agg", key, build,
                                 profile=getattr(ctx, "profile", None),
                                 node_key=id(node))

    flat_args = []
    for i in needed:
        dc = env_cols[i]
        flat_args.extend([dc.data, dc.mask])
    if fact is not None:
        flat_args.append(fact["codes2d"])
    if mesh_n > 1:
        flat_args = [_pad_shard_axis(a, mesh_n) for a in flat_args]
    # A column's device mask excludes padding but ALSO that column's NULLs —
    # wrong as a row mask for count(*). Use a pure row-validity mask built
    # from the logical length of the SAME publication as the columns
    # (cached per version on the provider).
    mask_rows = nrows if zrange is None else zrange[1] - zrange[0]
    prows = pad_len(mask_rows)
    rm_entry = getattr(provider, "_device_rowmask", None)
    if rm_entry is None or rm_entry[0] != (dev_ver, zrange) or \
            rm_entry[1].shape != (prows // 128, 128):
        rm = np.zeros(prows, dtype=bool)
        rm[:mask_rows] = True
        rowmask_arr = jnp.asarray(rm.reshape(-1, 128))
        provider._device_rowmask = ((dev_ver, zrange), rowmask_arr)
    else:
        rowmask_arr = rm_entry[1]
    if mesh_n > 1:
        rowmask_arr = _pad_shard_axis(rowmask_arr, mesh_n)
    chunk_rows = int(ctx.settings.get("serene_device_chunk_rows") or 0)
    # clamp to one tile: tiny values must mean "maximum responsiveness",
    # never silently disable chunking
    chunk_tiles = max(1, chunk_rows // 128) if chunk_rows > 0 else 0
    n_tiles = int(rowmask_arr.shape[0])
    if chunk_tiles and n_tiles > chunk_tiles:
        # chunked dispatch: cancel/statement_timeout can fire between
        # chunks instead of waiting out one monolithic program
        # (reference: the session interrupt check inside execution
        # tasks, pg_wire_session.h:205-220)
        if mesh_n > 1:
            chunk_tiles += (-chunk_tiles) % mesh_n
        combines = _out_combines(node, agg_plans, group_mode)
        results = _chunked_dispatch(jitted, flat_args, rowmask_arr,
                                    chunk_tiles, combines, mesh_n,
                                    profile=getattr(ctx, "profile", None),
                                    node_key=id(node))
    else:
        results = obs_device.dispatch(
            jitted, (*flat_args, rowmask_arr),
            profile=getattr(ctx, "profile", None), node_key=id(node))

    with stage("device_finalize"):
        if group_mode:
            return _build_group_batch(node, key_plans, agg_plans, results,
                                      provider, col_names, dictionaries,
                                      group_space, fact, distinct_plans)
        return _build_scalar_batch(node, agg_plans, results,
                                   distinct_plans)


def _zonemap_range(scan, provider, preds, pin, nrows,
                   ctx) -> Optional[tuple[int, int]]:
    """Contiguous surviving row range [lo, hi) under the filter
    conjuncts' zone-map verdicts, or None when nothing prunes. Raises
    NotCompilable when EVERY block is pruned — the morsel path then
    resolves the query from the same verdicts without touching data.
    lo is block-aligned and therefore a multiple of the 128-lane tile."""
    from . import zonemap
    block_rows = int(ctx.settings.get("serene_morsel_rows"))
    verdicts = zonemap.block_verdicts(provider, ctx.settings, preds,
                                      scan.columns, block_rows, pin)
    if verdicts is None:
        return None
    lo, hi = zonemap.surviving_range(verdicts, block_rows, nrows)
    if hi <= lo:
        # don't touch the counters here: the host morsel path resolves
        # the query from the same verdict vector and does the counting
        raise NotCompilable("zone maps pruned every block")
    if (lo, hi) == (0, nrows):
        return None
    # only the envelope shrink is real pruning on the device path —
    # interior SKIP blocks inside [lo, hi) still upload and scan
    n_blocks = len(verdicts)
    lo_b, hi_b = lo // block_rows, (hi + block_rows - 1) // block_rows
    metrics.ZONEMAP_PRUNED.add(n_blocks - (hi_b - lo_b))
    metrics.ZONEMAP_SCANNED.add(hi_b - lo_b)
    if zonemap.verify_enabled(ctx.settings):
        full = pin[0] if pin is not None else \
            provider.full_batch(scan.columns)
        from ..columnar.column import Batch as _B
        full = _B(list(scan.columns),
                  [full.column(c) for c in scan.columns])
        spans = [(s, e) for s, e in ((0, lo), (hi, nrows)) if e > s]
        zonemap.verify_pruned_blocks(preds, full, spans,
                                     f"device aggregate {provider.name}")
    return lo, hi


def _range_device_columns(provider, names, pin, zrange) -> dict:
    """{name: DeviceColumn} for a row subrange, one publication
    observation (mirrors TableProvider.device_columns). Cached per
    (version, range) with one entry per column — repeated queries with
    the same shape reuse the upload, a different range rebuilds."""
    from . import zonemap as _zm
    from ..columnar.device import to_device_column
    lo, hi = zrange
    lock = _zm._zone_lock(provider)
    if pin is not None:
        batch, ver = pin[0], pin[1]
    else:
        batch, ver = None, provider.data_version
    with lock:
        cache = getattr(provider, "_zonemap_devcache", None)
        if cache is None:
            cache = provider._zonemap_devcache = {}
        hits = {n: e[1] for n in names
                if (e := cache.get(n)) is not None and e[0] == (ver, lo, hi)}
    out = dict(hits)
    metrics.DEVICE_CACHE_HITS.add(len(hits))
    metrics.DEVICE_CACHE_MISSES.add(len(set(names)) - len(hits))
    # uploads run OUTSIDE the lock: a multi-hundred-MB host→device copy
    # must not serialize every other query's zone-stats access on this
    # provider (a racing duplicate upload is wasted work, never wrong —
    # entries are (version, range)-stamped either way)
    for name in names:
        if name in out:
            continue
        col = (batch.column(name) if batch is not None
               else provider.full_batch([name]).column(name))
        dc = to_device_column(col.slice(lo, hi))
        metrics.DEVICE_BYTES.add(
            int(dc.data.size * dc.data.dtype.itemsize))
        with lock:
            cache[name] = ((ver, lo, hi), dc)
        out[name] = dc
    return out


def _count_reductions(group_mode: bool, group_space: int, agg_plans,
                      distinct_plans) -> list[int]:
    """The cell count of every grouped count / presence reduction that
    `program` runs for these plans, one entry per `group_count_cells`
    call of `program`, `_group_agg_device` and `_presence`: what
    `DeviceAggHistogram` / `DeviceAggScatter` count, by `hist_form`."""
    cells = [group_space] if group_mode else []
    for si, (spec, _ce) in enumerate(agg_plans):
        if si in distinct_plans:
            cells.append(group_space * distinct_plans[si][3])
        elif group_mode and spec.func != "count_star":
            # float MIN counts its non-NaN rows besides
            cells.extend([group_space] * (
                2 if spec.func == "min" and spec.arg.type.is_float else 1))
    return cells


def _presence(dplan, arrays, gcodes, mask, group_space):
    """(group, value) presence matrix for one DISTINCT aggregate: int32
    0/1 cells, the count of the coded pairs where it is above 0. NULL
    values contribute 0 (their row mask is False), so no cell lights up
    for them."""
    import jax.numpy as jnp
    kind, vi, lo, vsize = dplan
    data, ok = arrays[vi]
    vc = data.astype(jnp.int32)
    if kind == "int":
        vc = vc - jnp.int32(lo)
    vc = jnp.clip(vc, 0, vsize - 1)
    m = jnp.logical_and(mask, ok)
    pair = gcodes * jnp.int32(vsize) + vc
    counts = ops_agg.group_count_cells(pair, m, group_space * vsize)
    return (counts > 0).astype(jnp.int32).reshape(group_space, vsize)


def _out_combines(node, agg_plans, group_mode) -> list:
    """Per-output cross-shard combine kinds for the mesh wrap, mirroring
    the output order of `program`: 'sum' → psum (counts, float sums, the
    additive int limb arrays), 'min'/'max' → pmin/pmax, 'rows' → per-row
    partials that stay sharded (concatenated by the out_spec; the host
    combiner sums over rows, and zero-padded rows contribute nothing)."""
    out = ["sum"]        # group counts / scalar row count
    for spec, ce in agg_plans:
        if spec.func == "count_star":
            continue
        if spec.distinct and spec.func in ("count", "sum", "avg"):
            out.append("max")    # presence matrix: cross-shard union
            continue
        if spec.func == "count":
            out.append("sum")
            continue
        is_float = spec.arg is not None and spec.arg.type.is_float
        if spec.func in ("sum", "avg"):
            n = len(ce.parts) if isinstance(ce, _Wide) else 1
            if group_mode or is_float:
                out.extend(["sum"] * n + ["sum"])   # (limbs|float sum) + count
            else:
                out.extend(["rows"] * n + ["sum"])  # per-row int partials
        elif spec.func in ("min", "max"):
            out.extend([spec.func, "sum"])
        else:
            raise NotCompilable(f"mesh combine for {spec.func}")
    return out


def _pad_shard_axis(arr, mesh_n: int):
    from ..parallel.mesh import pad_to_multiple
    return pad_to_multiple(arr, mesh_n)


def _chunked_dispatch(jitted, flat_args, rowmask_arr, chunk_tiles: int,
                      combines: list, mesh_n: int, profile=None,
                      node_key=None):
    """Run the aggregate program chunk by chunk over the row-block axis,
    combining per-output partials on host ('sum' adds exactly in
    int64/float64, 'min'/'max' fold elementwise, 'rows' concatenates).
    check_cancel() runs between dispatches, so a cancel or a statement
    timeout interrupts a long aggregate within one chunk's latency. All
    chunks share one compiled shape (the tail pads with empty rows)."""
    from .plan import check_cancel
    import jax.numpy as jnp

    from ..obs import device as obs_device
    n_tiles = int(rowmask_arr.shape[0])
    acc = None
    for start in range(0, n_tiles, chunk_tiles):
        check_cancel()
        end = min(start + chunk_tiles, n_tiles)

        def cut(a):
            part = a[start:end]
            if end - start < chunk_tiles:
                pad = chunk_tiles - (end - start)
                widths = [(0, pad)] + [(0, 0)] * (part.ndim - 1)
                part = jnp.pad(part, widths)
            return part

        outs = obs_device.dispatch(
            jitted, (*[cut(a) for a in flat_args], cut(rowmask_arr)),
            profile=profile, node_key=node_key)
        def widen(o, c):
            if c != "sum":
                return o
            # chunk-size-stable host accumulation: ints widen to int64,
            # floats to float64
            return o.astype(np.int64 if o.dtype.kind in "iu"
                            else np.float64)

        if acc is None:
            acc = [widen(o, c) for o, c in zip(outs, combines)]
            continue
        for k, (o, c) in enumerate(zip(outs, combines)):
            if c == "sum":
                acc[k] = acc[k] + widen(o, c)
            elif c == "min":
                acc[k] = np.minimum(acc[k], o)
            elif c == "max":
                acc[k] = np.maximum(acc[k], o)
            else:   # per-row partials: stack chunks back together
                acc[k] = np.concatenate([acc[k], o])
    return tuple(acc)


def _mesh_wrap(program, mesh_n: int, combines: list, n_inputs: int):
    """shard_map the single-device aggregate program over an N-device
    mesh: row-block inputs shard on the leading axis, reductions merge
    with psum/pmin/pmax over ICI, per-row partial outputs stay sharded
    (reference analog: morsel-parallel pipelines re-expressed as XLA
    collectives — SURVEY.md §2.11/§5.7). Returns the un-jitted wrapped
    callable — the obs/device compile ledger owns the jit."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AXIS, apply_axis_combines, data_mesh
    mesh = data_mesh(mesh_n)

    def core(*flat):
        return apply_axis_combines(program(*flat), combines)

    in_specs = tuple(P(AXIS, None) for _ in range(n_inputs))
    out_specs = tuple(P() if c in ("sum", "min", "max")
                      else P(AXIS, None) for c in combines)
    return shard_map(core, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)


def _plan_direct_keys(node, scan, host_col, col_names, dictionaries):
    """Direct group-key coding: dictionary codes / small-range integers.
    Raises NotCompilable when any key needs factorization. host_col reads
    from the query's pinned publication."""
    key_plans = []
    group_space = 1
    for g in node.group_exprs:
        if not isinstance(g, BoundColumn):
            raise NotCompilable("group key is not a plain column")
        t = scan.types[g.index]
        if t.is_string:
            d = dictionaries.get(g.index)
            if d is None:
                raise NotCompilable("string key without dictionary")
            size = len(d) + 1      # +1: NULL group
            key_plans.append(("dict", g.index, 0, size))
        elif t.is_integer or t.id in (dt.TypeId.BOOL, dt.TypeId.DATE):
            col = host_col(col_names[g.index])
            if col.data.size == 0:
                lo, hi = 0, 0
            else:
                lo, hi = int(col.data.min()), int(col.data.max())
            rng = hi - lo + 1
            if rng > MAX_INT_KEY_RANGE:
                raise NotCompilable("integer key range too large for direct coding")
            if not (-2**31 <= lo and hi < 2**31):
                # small range but offset beyond int32 (snowflake-style ids):
                # the raw column can't upload exactly — factorize instead
                raise NotCompilable("integer key offset beyond int32")
            size = rng + 1
            key_plans.append(("int", g.index, lo, size))
        else:
            raise NotCompilable(f"group key type {t}")
        group_space *= size
        if group_space > MAX_GROUP_PRODUCT:
            raise NotCompilable("group code space too large")
    return key_plans, group_space


def _factorize_group_keys(node, scan, provider, pin_batch, dev_ver) -> dict:
    """Composite host factorization of arbitrary GROUP BY keys: evaluate
    the key expressions over the host columns, build dense codes with
    ops_agg.factorize_keys (NULLs group per PG semantics), upload the
    codes as device tiles. Cached per (data_version, key exprs) — the
    factorize pass is O(n log n) once, amortized across queries.

    Reference analog: DuckDB's RadixPartitionedHashTable grouped
    aggregate (SURVEY.md §1 L3) — re-expressed as host factorize +
    device scatter so the hot per-row work stays on the TPU."""
    import jax.numpy as jnp

    ekeys = tuple(_expr_key(g) for g in node.group_exprs)
    # version + batch are ONE observation (passed in from the query's
    # pin): codes factorized over batch N+1 must never cache under N
    ver = dev_ver
    lock = _factorize_lock(provider)
    with lock:
        # readers are lock-free and concurrent: all cache scans and
        # mutations go through this per-provider lock (two concurrent
        # GROUP BYs after an UPDATE would otherwise race the stale purge)
        cache = getattr(provider, "_factorize_cache", None)
        if cache is None:
            cache = provider._factorize_cache = {}
        stale = [k2 for k2 in cache if k2[0] != ver]
        for k2 in stale:  # old data versions can never be read again
            del cache[k2]
        hit = cache.get((ver, ekeys))
    if hit is not None:
        return hit
    if pin_batch is not None:
        full = Batch(list(scan.columns),
                     [pin_batch.column(c) for c in scan.columns])
    else:
        full = provider.full_batch(scan.columns)
    try:
        key_cols = [g.eval(full) for g in node.group_exprs]
    except Exception as e:
        # the CPU path evaluates keys only over WHERE-surviving rows; an
        # eval error on a filtered-out row (e.g. division by zero) must
        # fall back, not surface
        raise NotCompilable(f"group key eval over unfiltered rows: {e}")
    # shared with the host morsel sink: direct (perfect-hash) coding for
    # small int/dict key spaces — no composite sort — with the factorize
    # fallback for arbitrary keys; group order is identical either way
    from .morsel import _group_codes
    codes, uniq_vals, uniq_valid, g_count = _group_codes(key_cols)
    if g_count > MAX_GROUP_PRODUCT:
        raise NotCompilable(
            f"{g_count} distinct groups exceeds the device code-space cap")
    n_pad = pad_len(len(codes))
    padded = np.zeros(n_pad, dtype=np.int32)
    padded[:len(codes)] = codes
    value = {
        "codes2d": jnp.asarray(padded.reshape(-1, 128)),
        "uniq_vals": uniq_vals,
        "uniq_valid": uniq_valid,
        "g": g_count,
        "key_meta": [(c.type, c.dictionary) for c in key_cols],
    }
    with lock:
        if len(cache) >= 16:  # bound HBM held by codes buffers
            cache.pop(next(iter(cache)))
        cache[(ver, ekeys)] = value
    return value


def _scalar_agg_device(spec: AggSpec, ce, arrays, mask, env_for):
    import jax.numpy as jnp
    if spec.func == "count_star":
        return []  # uses the shared row count output
    if isinstance(ce, _Wide):
        outs, m = [], mask
        for part, _w in ce.parts:
            v, ok = part.fn(env_for(part, arrays))
            outs.append(ops_agg.masked_sum_int_partials(
                v, jnp.logical_and(mask, ok)))
            m = jnp.logical_and(m, ok)
        return outs + [jnp.sum(m, dtype=jnp.int32)]
    v, ok = ce.fn(env_for(ce, arrays))
    m = jnp.logical_and(mask, ok)
    if spec.func == "count":
        return [jnp.sum(m, dtype=jnp.int32)]
    is_float = jnp.issubdtype(v.dtype, jnp.floating)
    if spec.func in ("sum", "avg"):
        cnt = jnp.sum(m, dtype=jnp.int32)
        if is_float:
            s = jnp.sum(jnp.where(m, v, 0.0).astype(jnp.float32))
            return [s, cnt]
        return [ops_agg.masked_sum_int_partials(v, m), cnt]
    if spec.func in ("min", "max"):
        if is_float:
            ident = jnp.inf if spec.func == "min" else -jnp.inf
        else:
            info = jnp.iinfo(v.dtype)
            ident = info.max if spec.func == "min" else info.min
        cnt = jnp.sum(m, dtype=jnp.int32)
        if is_float and spec.func == "min":
            m_nn = jnp.logical_and(m, jnp.logical_not(jnp.isnan(v)))
            red = jnp.min(jnp.where(m_nn, v, ident))
            red = jnp.where(jnp.logical_and(
                cnt > 0, jnp.sum(m_nn, dtype=jnp.int32) == 0),
                jnp.nan, red)
            return [red, cnt]
        vv = jnp.where(m, v, ident)
        red = jnp.min(vv) if spec.func == "min" else jnp.max(vv)
        return [red, cnt]
    raise NotCompilable(spec.func)


def _group_agg_device(spec: AggSpec, ce, arrays, codes, mask, env_for, g):
    import jax.numpy as jnp
    if spec.func == "count_star":
        return []  # shared group counts output
    if isinstance(ce, _Wide):
        outs, m = [], mask
        for part, _w in ce.parts:
            v, ok = part.fn(env_for(part, arrays))
            outs.append(_group_int_sum(codes, jnp.logical_and(mask, ok), v,
                                       g))
            m = jnp.logical_and(m, ok)
        return outs + [ops_agg.group_count_cells(codes, m, g)]
    v, ok = ce.fn(env_for(ce, arrays))
    m = jnp.logical_and(mask, ok)
    if spec.func == "count":
        return [ops_agg.group_count_cells(codes, m, g)]
    is_float = jnp.issubdtype(v.dtype, jnp.floating)
    if spec.func in ("sum", "avg"):
        cnt = ops_agg.group_count_cells(codes, m, g)
        if is_float:
            return [ops_agg.group_sum_float(codes, m, v, g), cnt]
        return [_group_int_sum(codes, m, v, g), cnt]
    if spec.func in ("min", "max"):
        if is_float and spec.func == "min":
            # PG: NaN is the greatest float — MIN skips NaN unless a
            # group is ALL NaN (then it IS NaN). Counts keep the
            # original mask so NULL detection is untouched. (Under the
            # mesh, a group all-NaN on one shard only is a known edge.)
            counts = ops_agg.group_count_cells(codes, m, g)
            m_nn = jnp.logical_and(m, jnp.logical_not(jnp.isnan(v)))
            nonnan = ops_agg.group_count_cells(codes, m_nn, g)
            red = ops_agg.group_min_max(codes, m_nn, v, g, "min")
            red = jnp.where(jnp.logical_and(counts > 0, nonnan == 0),
                            jnp.nan, red)
            return [red, counts]
        return [ops_agg.group_min_max(codes, m, v, g, spec.func),
                ops_agg.group_count_cells(codes, m, g)]
    raise NotCompilable(spec.func)


def _group_int_sum(codes, m, v, g):
    """Exact per-group int sums as limbs: masked reductions for a few
    groups, else a scatter, chunked past the limbs' row bound."""
    if g <= ops_agg.SMALL_SPACE and codes.size <= ops_agg.LIMB_ROWS_EXACT:
        return ops_agg.group_sum_int_limbs_masked(codes, m, v, g)
    if codes.shape[0] > ops_agg.SCATTER_CHUNK_TILES:
        return ops_agg.group_sum_int_limbs_chunked(codes, m, v, g)
    return ops_agg.group_sum_int_limbs(codes, m, v, g)


def _build_scalar_batch(node, agg_plans, results,
                        distinct_plans=None) -> Batch:
    ri = iter(results)
    total = int(np.asarray(next(ri)))
    cols = []
    for si, (spec, ce) in enumerate(agg_plans):
        dplan = (distinct_plans or {}).get(si)
        if dplan is not None:
            pres = np.asarray(next(ri)).reshape(1, -1)
            cols.append(_distinct_result_col(spec, dplan, pres,
                                             np.asarray([0]))[0])
        else:
            cols.append(_scalar_result_col(spec, ri, total, _weights(ce)))
    return Batch(list(node.names), cols)


def _distinct_result_col(spec: AggSpec, dplan, pres: np.ndarray,
                         present: np.ndarray):
    """Presence matrix -> one result column, rows selected by `present`.
    Returns a 1-element list for uniform use."""
    kind, vi, lo, vsize = dplan
    sub = pres[present].astype(np.int64)
    cnt = sub.sum(axis=1)
    if spec.func == "count":
        return [Column(dt.BIGINT, cnt)]
    vals = (lo + np.arange(vsize, dtype=np.int64))
    sums = sub @ vals
    empty = cnt == 0
    if spec.func == "avg":
        with np.errstate(invalid="ignore", divide="ignore"):
            data = np.where(empty, 0.0, sums / np.maximum(cnt, 1))
        return [Column(dt.DOUBLE, data, ~empty if empty.any() else None)]
    t = spec.type
    if t.is_integer:
        return [Column(dt.BIGINT, sums,
                       ~empty if empty.any() else None)]
    return [Column(dt.DOUBLE, sums.astype(np.float64),
                   ~empty if empty.any() else None)]


def _partials_sum(first: np.ndarray) -> int:
    """masked_sum_int_partials' (rows, 2) [hi, lo] halves → the sum."""
    parts = first.astype(np.int64)
    return int((parts[:, 0].sum() << 16) + parts[:, 1].sum())


def _scalar_result_col(spec: AggSpec, ri, total: int,
                       weights: Optional[list] = None) -> Column:
    t = spec.type
    if spec.func == "count_star":
        return Column.from_pylist([total], t)
    if spec.func == "count":
        return Column.from_pylist([int(np.asarray(next(ri)))], t)
    if spec.func in ("sum", "avg"):
        if weights:
            s = sum(_partials_sum(np.asarray(next(ri))) * w
                    for w in weights)
            if not -2 ** 63 <= s < 2 ** 63:
                raise errors.SqlError("22003", "numeric field overflow")
        else:
            first = np.asarray(next(ri))
            s = float(first) if first.ndim == 0 else _partials_sum(first)
        cnt = int(np.asarray(next(ri)))
        if cnt == 0:
            return Column.from_pylist([None], t)
        if spec.func == "avg":
            return Column.from_pylist([s / cnt], t)
        return Column.from_pylist([s if t.is_integer else float(s)], t)
    if spec.func in ("min", "max"):
        v = np.asarray(next(ri))
        cnt = int(np.asarray(next(ri)))
        if cnt == 0:
            return Column.from_pylist([None], t)
        out = v.item()
        if t.is_integer:
            out = int(out)
        return Column.from_pylist([out], t)
    raise NotCompilable(spec.func)


def _build_group_batch(node, key_plans, agg_plans, results, provider,
                       col_names, dictionaries, g, fact=None,
                       distinct_plans=None) -> Batch:
    ri = iter(results)
    counts = np.asarray(next(ri)).astype(np.int64)
    present = np.flatnonzero(counts > 0)
    cols: list[Column] = []
    if fact is not None:
        for k2, (t, d) in enumerate(fact["key_meta"]):
            uv = np.asarray(fact["uniq_vals"][k2])[present]
            validity = fact["uniq_valid"][k2][present] \
                if fact["uniq_valid"].size else None
            if validity is not None and validity.all():
                validity = None
            cols.append(Column(t, uv, validity, d))
        for si, (spec, ce) in enumerate(agg_plans):
            dplan = (distinct_plans or {}).get(si)
            if dplan is not None:
                pres = np.asarray(next(ri))
                cols.extend(_distinct_result_col(spec, dplan, pres,
                                                 present))
            else:
                cols.append(_group_result_col(spec, ri, counts, present,
                                              _weights(ce)))
        return Batch(list(node.names), cols)
    # decode combined codes back to per-key codes
    sizes = [kp[3] for kp in key_plans]
    rem = present.copy()
    key_codes = []
    for size in reversed(sizes):
        key_codes.append(rem % size)
        rem //= size
    key_codes.reverse()
    for (kind, idx, lo, size), kc in zip(key_plans, key_codes):
        null_mask = kc == (size - 1)
        t = provider.type_of(col_names[idx])
        if kind == "dict":
            d = dictionaries[idx]
            data = np.where(null_mask, 0, kc).astype(np.int32)
            cols.append(Column(t, data,
                               ~null_mask if null_mask.any() else None, d))
        else:
            data = (kc + lo).astype(t.np_dtype)
            data = np.where(null_mask, 0, data).astype(t.np_dtype)
            cols.append(Column(t, data,
                               ~null_mask if null_mask.any() else None))
    for si, (spec, ce) in enumerate(agg_plans):
        dplan = (distinct_plans or {}).get(si)
        if dplan is not None:
            pres = np.asarray(next(ri))
            cols.extend(_distinct_result_col(spec, dplan, pres, present))
        else:
            cols.append(_group_result_col(spec, ri, counts, present,
                                          _weights(ce)))
    return Batch(list(node.names), cols)


def _group_result_col(spec: AggSpec, ri, star_counts, present,
                      weights: Optional[list] = None) -> Column:
    t = spec.type
    if spec.func == "count_star":
        return Column(dt.BIGINT, star_counts[present])
    if spec.func == "count":
        c = np.asarray(next(ri)).astype(np.int64)
        return Column(dt.BIGINT, c[present])
    if spec.func in ("sum", "avg"):
        if weights:
            sums = None
            for w in weights:
                sums = _add_weighted(sums, ops_agg.combine_sum_int_limbs(
                    np.asarray(next(ri)))[present], w)
        else:
            first = np.asarray(next(ri))
            if first.ndim >= 2:  # int limbs (G,5) or chunked (C,G,5)
                sums = ops_agg.combine_sum_int_limbs(first)[present]
            else:
                sums = first.astype(np.float64)[present]
        cnt = np.asarray(next(ri)).astype(np.int64)[present]
        empty = cnt == 0
        if spec.func == "avg":
            with np.errstate(invalid="ignore", divide="ignore"):
                data = np.where(empty, 0.0, sums / np.maximum(cnt, 1))
            return Column(dt.DOUBLE, data, ~empty if empty.any() else None)
        if t.is_integer:
            return Column(dt.BIGINT, sums.astype(np.int64),
                          ~empty if empty.any() else None)
        return Column(dt.DOUBLE, sums.astype(np.float64),
                      ~empty if empty.any() else None)
    if spec.func in ("min", "max"):
        v = np.asarray(next(ri))[present]
        cnt = np.asarray(next(ri)).astype(np.int64)[present]
        empty = cnt == 0
        data = np.where(empty, 0, v).astype(t.np_dtype)
        return Column(t, data, ~empty if empty.any() else None)
    raise NotCompilable(spec.func)
