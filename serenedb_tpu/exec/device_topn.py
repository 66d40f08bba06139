"""Device / mesh top-N: ORDER BY <column> LIMIT k over a table scan.

Reference analog: the columnstore top-N pushdown of the reference's
analytics path (DuckDB TopN operator over the iresearch columnstore;
SURVEY.md §1 L3) — re-expressed as one XLA `top_k` over the HBM-resident
key column. Under `SET serene_mesh = N` the key tiles shard across the
mesh, each shard computes its local top-k, and the (N x k) candidates
merge on the host — the same shard-then-merge shape as the sharded BM25
top-k (parallel/mesh.py).

Supported shape: Limit(Sort(Scan | Project(Scan))) with a single sort
key that is a plain numeric column (int / date / float32) with no NULLs
and no filter. Anything else falls back to the exact CPU lexsort
(plan.SortNode). The asc direction uses the bitwise-NOT transform
(~k = -k-1) so int32 min does not overflow under negation.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Batch
from ..obs.trace import stage
from ..utils import log, metrics
from .device import NotCompilable
from .tables import TableProvider

MAX_TOPN_K = 8192
_I32_MIN = -(1 << 31)
_I32_MAX = (1 << 31) - 1


def try_device_topn(limit_node, ctx) -> Optional[Batch]:
    """Attempt device execution of Limit(Sort(...)); None → CPU path."""
    from .plan import ProjectNode, ScanNode, SortNode

    device = ctx.settings.get("serene_device")
    if device == "cpu":
        return None
    if limit_node.limit is None:
        return None
    k = limit_node.limit + limit_node.offset
    if k == 0 or k > MAX_TOPN_K:
        return None
    sort = limit_node.child
    if not isinstance(sort, SortNode) or len(sort.key_indices) != 1:
        return None
    if sort.nulls_first[0] is not None:
        return None     # explicit NULLS placement: CPU handles it
    proj = None
    inner = sort.child
    if isinstance(inner, ProjectNode):
        proj = inner
        inner = inner.child
    if not isinstance(inner, ScanNode) or inner.filter is not None:
        return None
    scan = inner
    ki = sort.key_indices[0]
    if proj is not None:
        from ..sql.expr import BoundColumn
        key_expr = proj.exprs[ki]
        if not isinstance(key_expr, BoundColumn):
            return None
        col_idx = key_expr.index
    else:
        col_idx = ki
    t = scan.types[col_idx]
    if not (t.is_integer or t.id in (dt.TypeId.DATE, dt.TypeId.FLOAT)):
        return None
    provider = scan.provider
    if device == "auto" and \
            provider.row_count() < ctx.settings.get("serene_device_min_rows"):
        return None
    from ..columnar.device import DeviceNarrowingError
    try:
        # `device_prepare` but for what stamps itself inside: the
        # program call, the readback, and the host decode below
        with stage("device_prepare", op="topn"):
            # device-path time (enqueue start -> readback done) lands on
            # the Limit node that claimed the Sort pipeline (the offload
            # replaced its whole subtree)
            idx = _topn_indices(provider, scan, scan.columns[col_idx],
                                bool(sort.descs[0]), k, ctx,
                                prof_key=id(limit_node))
            if idx is None:
                return None
            with stage("device_finalize"):
                idx = idx[limit_node.offset:]
                base = provider.full_batch(scan.columns).take(idx)
                if proj is None:
                    return base
                cols = [e.eval(base) for e in proj.exprs]
                return Batch(list(proj.names), cols)
    except (NotCompilable, DeviceNarrowingError) as e:
        log.debug("device", f"top-N fell back to CPU: {e}")
        return None


def _topn_indices(provider: TableProvider, scan, col_name: str,
                  desc: bool, k: int, ctx,
                  prof_key=None) -> Optional[np.ndarray]:
    import jax
    import jax.numpy as jnp

    pin = provider.try_pin()
    dev_ver = pin[1] if pin is not None else provider.data_version
    host = (pin[0].column(col_name) if pin is not None
            else provider.host_column(col_name))
    n = len(host)
    if n == 0:
        return np.empty(0, dtype=np.int64)
    if not host.valid_mask().all():
        raise NotCompilable("top-N key column has NULLs")
    if host.data.dtype.kind == "f":
        if not np.isfinite(host.data).all():
            # NaN ordering is PG-specific; +/-inf would collide with the
            # -inf padding sentinel in the mesh merge
            raise NotCompilable("top-N float key has NaN/inf")
    else:
        # sentinel-tie gates (see module docstring): the transform must
        # keep every valid key strictly above the invalid sentinel
        lo, hi = int(host.data.min()), int(host.data.max())
        if desc and lo <= _I32_MIN:
            raise NotCompilable("key touches int32 min")
        if not desc and hi >= _I32_MAX:
            raise NotCompilable("key touches int32 max")

    mesh_n = int(ctx.settings.get("serene_mesh") or 0)
    if mesh_n > 1 and len(jax.devices()) < mesh_n:
        mesh_n = 0

    # zone-map skip-scan: block bounds alone can prove a prefix/suffix
    # of blocks holds no top-k candidate (cover k rows with the best
    # blocks' worst values, prune blocks strictly beyond that
    # threshold); only the surviving contiguous range uploads. Pruned
    # rows are strictly outside the top-k, so result AND tie order are
    # untouched — indices just shift by the range start.
    from . import zonemap
    block_rows = int(ctx.settings.get("serene_morsel_rows"))
    zrange = zonemap.topn_block_range(provider, ctx.settings, col_name,
                                      block_rows, desc, k, pin)

    # the range keys the program: a sliced upload's frame-of-reference
    # scheme can differ from the whole column's
    cache_key = ("topn", id(provider), dev_ver, col_name, desc, k, mesh_n,
                 zrange)
    if zrange is None:
        dc = provider.device_columns([col_name], pin)[col_name]
    else:
        from .device_agg import _range_device_columns
        dc = _range_device_columns(provider, [col_name], pin,
                                   zrange)[col_name]
    is_float = dc.data.dtype.kind == "f"

    def build():
        scheme, offset = dc.scheme, dc.offset

        def keys_of(data, mask):
            v = data
            if scheme != "raw":
                v = v.astype(jnp.int32) + jnp.int32(offset)
            if is_float:
                kv = v if desc else -v
                sent = jnp.float32(-jnp.inf)
            else:
                v = v.astype(jnp.int32)
                kv = v if desc else ~v
                sent = jnp.int32(_I32_MIN)
            return jnp.where(mask.ravel(), kv.ravel(), sent)

        if mesh_n > 1:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import AXIS, data_mesh
            mesh = data_mesh(mesh_n)

            def core(data, mask):
                keys = keys_of(data, mask)
                kk, ii = jax.lax.top_k(keys, k)
                shard_rows = data.shape[0] * data.shape[1]
                base = jax.lax.axis_index(AXIS).astype(jnp.int32) * \
                    jnp.int32(shard_rows)
                return kk, ii.astype(jnp.int32) + base

            return shard_map(
                core, mesh=mesh, in_specs=(P(AXIS, None), P(AXIS, None)),
                out_specs=(P(AXIS), P(AXIS)))

        def prog(data, mask):
            keys = keys_of(data, mask)
            kk, ii = jax.lax.top_k(keys, k)
            return kk, ii.astype(jnp.int32)

        return prog

    from ..obs import device as obs_device
    jitted = obs_device.compiled("device_topn", cache_key, build,
                                 profile=getattr(ctx, "profile", None),
                                 node_key=prof_key)

    data, mask = dc.data, dc.mask
    if mesh_n > 1:
        from .device_agg import _pad_shard_axis
        data = _pad_shard_axis(data, mesh_n)
        mask = _pad_shard_axis(mask, mesh_n)
    if data.shape[0] * data.shape[1] < k * max(mesh_n, 1):
        # top_k k exceeds the (per-shard) domain — tiny table, CPU wins
        raise NotCompilable("k exceeds per-shard rows")
    kk, ii = obs_device.dispatch(jitted, (data, mask),
                                 profile=getattr(ctx, "profile", None),
                                 node_key=prof_key)
    with stage("device_finalize"):
        ii = ii.astype(np.int64)
        if mesh_n > 1:
            # merge the per-shard candidate lists: global top-k of N*k.
            # Candidates from under-filled shards carry the padding sentinel
            # — drop them (finite/valid keys are strictly above it by the
            # gates), and widen to float64 so negating int32 min can't wrap.
            kkw = kk.astype(np.float64)
            sent = -np.inf if is_float else float(_I32_MIN)
            valid = kkw > sent
            kkw, ii = kkw[valid], ii[valid]
            order = np.argsort(-kkw, kind="stable")[: k]
            ii = ii[order]
        if zrange is not None:
            ii = ii + zrange[0]     # slice-relative → table row ids
        metrics.DEVICE_OFFLOADS.add()
        k_eff = min(k, n)
        return ii[:k_eff]
