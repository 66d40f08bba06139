"""Device-mesh parallel execution of scans, aggregates, and scoring.

Reference analog: the reference's intra-node parallelism (morsel-driven
pipelines, parallel top-k collectors, parallel sinks — SURVEY.md §2.11) has
no cross-device component; on TPU the same roles map onto a
`jax.sharding.Mesh`: row blocks shard across devices ("data parallel" scan),
per-device partial aggregates combine with psum over ICI, and per-device
top-k merges via all_gather — XLA inserts the collectives.

The mesh axis is named "shard". Multi-host scaling uses the same programs
over a larger mesh (jax handles DCN vs ICI placement).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.device import LANES

AXIS = "shard"


def _mesh_key(mesh: "Mesh") -> tuple:
    """Compile-ledger key component for a mesh: its device ids (two
    meshes over the same devices trace to the same program).

    The step builders below key on the mesh (+ scalar params) only, not
    on input shapes — one ledger entry holds a SHAPE-POLYMORPHIC jit
    wrapper whose internal per-shape executables accumulate like the
    module-level @jax.jit kernels in ops/ (jit's own cache), and the
    retraces are invisible to the compile ledger. Acceptable for these
    test/bench/dryrun-facing builders (the engine's query-path programs
    all key on full shape signatures); evicting the wrapper still frees
    every shape variant at once."""
    return tuple(d.id for d in mesh.devices.flat)

#: process-wide cache of data-axis meshes by device count — Mesh
#: construction is cheap but identity-stable meshes keep shard_map
#: program caches (keyed on the jitted callable) from re-tracing
_MESH_CACHE: dict[int, Mesh] = {}


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    note_backend_initialized()
    n = n_devices or len(devs)
    return Mesh(np.asarray(devs[:n]), (AXIS,))


#: set once this process has deliberately touched its jax backend: at
#: the upload/mesh choke points and by the entry points that own the
#: device (serened via utils/backend.init_backend)
_BACKEND_NOTED = False


def note_backend_initialized() -> None:
    global _BACKEND_NOTED
    _BACKEND_NOTED = True


def device_count_if_initialized() -> int:
    """Number of jax devices IF this process has already touched its
    backend, else 0 — NEVER triggers backend initialization. An
    accelerator belongs to one process at a time, so a passive caller
    (the sharded search merge deciding whether a device combine is even
    worth it, a stats read) must not be the one that claims it: a
    process that has dispatched nothing stays off the device."""
    return len(jax.devices()) if _BACKEND_NOTED else 0


def data_mesh(n_shards: int) -> Mesh:
    """THE data-axis mesh of the sharded execution tier's in-program
    combine (serene_shard_combine=device): one axis named `shard` over
    min(n_shards, device count) devices — shards beyond the device
    count stack on the leading axis and reduce locally before the
    psum/pmin/pmax hop. Cached per width so repeat queries reuse the
    identical Mesh object."""
    n = max(1, min(int(n_shards), len(jax.devices())))
    mesh = _MESH_CACHE.get(n)
    if mesh is None:
        mesh = _MESH_CACHE[n] = make_mesh(n)
    return mesh


def data_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """NamedSharding splitting the LEADING axis over the mesh's shard
    axis (the stacked-shards layout): committed inputs land one shard
    group per device, so the collective dispatch never re-shuffles."""
    return NamedSharding(mesh, P(AXIS, *([None] * (ndim - 1))))


def apply_axis_combines(outs: tuple, kinds: list, fuse_sums: bool = False):
    """Cross-shard reduction of a program's per-device outputs over the
    mesh axis, by kind: 'sum' → psum (counts, int limb stacks, direct
    int sums), 'min'/'max' → pmin/pmax (selection partials), 'rows' →
    left sharded (per-row outputs the out_spec concatenates). Integer
    adds and min/max selections are exact in ANY reduction order, so
    the collective result is bit-identical to the host-side combine —
    the sharded tier's parity contract. Shared by the fused collective
    pipeline (exec/device_pipeline.py) and the mesh-wrapped device
    aggregate (exec/device_agg.py).

    `fuse_sums` batches every same-dtype/same-leading-dim 'sum' output
    into ONE psum (flatten trailing dims, concatenate, reduce, split):
    each all-reduce is a cross-device rendezvous, so N tiny psums cost
    N synchronizations where one fused psum costs one — element-wise
    identical either way (psum is independent per element)."""
    import jax.lax as lax
    import jax.numpy as jnp
    fused: dict[int, object] = {}
    if fuse_sums:
        sums = [(i, o) for i, (o, kind) in enumerate(zip(outs, kinds))
                if kind == "sum"]
        if len(sums) > 1 and len({o.dtype for _, o in sums}) == 1 and \
                len({o.shape[0] for _, o in sums}) == 1:
            flat = [o.reshape(o.shape[0], -1) for _, o in sums]
            red = lax.psum(jnp.concatenate(flat, axis=1), AXIS)
            at = 0
            for (i, o), f in zip(sums, flat):
                fused[i] = red[:, at:at + f.shape[1]].reshape(o.shape)
                at += f.shape[1]
    combined: list = []
    for i, (o, kind) in enumerate(zip(outs, kinds)):
        if i in fused:
            combined.append(fused[i])
        elif kind == "sum":
            combined.append(lax.psum(o, AXIS))
        elif kind == "min":
            combined.append(lax.pmin(o, AXIS))
        elif kind == "max":
            combined.append(lax.pmax(o, AXIS))
        else:                               # 'rows': stays sharded
            combined.append(o)
    return tuple(combined)


def shard_devices(n_shards: int) -> Optional[list]:
    """Data-axis placement for the sharded execution tier
    (exec/shard.py): shard s's per-shard program inputs commit to
    device s % n_devices along the mesh's shard axis, so concurrent
    shard dispatches land on distinct devices of the same mesh a
    shard_map program would span. None on a single-device host — the
    shards then share the default device and fan out as worker-pool
    tasks only."""
    devs = jax.devices()
    if len(devs) <= 1 or n_shards <= 1:
        return None
    return [devs[s % len(devs)] for s in range(n_shards)]


def pad_to_multiple(arr, n: int, fill=0):
    """Pad the leading axis to a multiple of n (THE shard-padding helper:
    data pads with `fill`, masks with False — padded rows never count).
    Works on numpy and jax arrays alike."""
    rows = arr.shape[0]
    pad = (-rows) % n
    if not pad:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    if isinstance(arr, np.ndarray):
        return np.pad(arr, widths, constant_values=fill)
    return jnp.pad(arr, widths, constant_values=fill)


def shard_rows(arr: np.ndarray, mesh: Mesh) -> np.ndarray:
    """Pad the leading (row-block) axis to a multiple of the mesh size."""
    return pad_to_multiple(arr, mesh.shape[AXIS])


def sharded_agg_step(mesh: Mesh):
    """Build a jitted sharded filter+aggregate step:
    (vals (R,128) i32, mask (R,128) bool, lo, hi) →
    (total count, per-row-block [hi16, lo16] int32 partial sums (R, 2)).

    Each 128-lane partial is exact in int32 (lo ≤ 128·65535, hi ≤ 128·2^15);
    the caller combines them on host as (Σhi << 16) + Σlo in int64 —
    device-side whole-shard int32 accumulation would wrap (int64 reductions
    are emulated on TPU, so the exact combine stays on host)."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P(), P()),
        out_specs=(P(), P(AXIS, None)))
    def step(vals, mask, lo, hi):
        sel = jnp.logical_and(mask,
                              jnp.logical_and(vals >= lo, vals < hi))
        cnt = jnp.sum(sel, dtype=jnp.int32)
        v = jnp.where(sel, vals, 0).astype(jnp.int32)
        loh = (v & 0xFFFF).astype(jnp.int32)
        hih = jnp.right_shift(v, 16)
        partials = jnp.stack([jnp.sum(hih, axis=1, dtype=jnp.int32),
                              jnp.sum(loh, axis=1, dtype=jnp.int32)], axis=1)
        return jax.lax.psum(cnt, AXIS), partials

    from ..obs import device as obs_device
    return obs_device.compiled("mesh_agg", (_mesh_key(mesh),),
                               lambda: step)


def combine_agg_partials(partials: np.ndarray) -> int:
    """(R, 2) int32 [hi16, lo16] row partials → exact int64 total."""
    p = np.asarray(partials).astype(np.int64)
    return int((p[:, 0].sum() << 16) + p[:, 1].sum())


def sharded_bm25_topk(mesh: Mesh, ndocs_pad: int, k: int,
                      k1: float = 1.2, b: float = 0.75):
    """Build a jitted sharded BM25 top-k: posting blocks shard across
    devices; each scores its blocks into a local dense accumulator; psum
    merges accumulators (doc space is replicated), then one top-k."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P(), P(AXIS, None), P(AXIS), P(), P()),
        out_specs=(P(), P()))
    def step(flat_docs, flat_tfs, norms, gidx, block_term, idf, avgdl):
        valid = gidx >= 0
        safe = jnp.where(valid, gidx, 0)
        docs = flat_docs[safe]
        tfs = flat_tfs[safe].astype(jnp.float32)
        dl = norms[docs].astype(jnp.float32)
        w = idf[block_term][:, None]
        denom = tfs + k1 * (1.0 - b + b * dl / jnp.maximum(avgdl, 1e-9))
        contrib = jnp.where(valid, w * (k1 + 1.0) * tfs /
                            jnp.maximum(denom, 1e-9), 0.0)
        local = jnp.zeros((ndocs_pad,), dtype=jnp.float32)
        local = local.at[docs.reshape(-1)].add(contrib.reshape(-1))
        scores = jax.lax.psum(local, AXIS)
        return tuple(jax.lax.top_k(scores, k))

    from ..obs import device as obs_device
    return obs_device.compiled(
        "mesh_bm25_topk", (_mesh_key(mesh), ndocs_pad, k, k1, b),
        lambda: step)


def sharded_query_step(mesh: Mesh, num_groups: int):
    """The full "training step" equivalent: one sharded query combining a
    filtered grouped aggregate with BM25 scoring — exercises scatter, matmul
    one-hot, and psum/all-reduce over the mesh in a single jitted program."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS, None), P(AXIS, None),
                  P(), P(), P(AXIS, None), P(AXIS)),
        out_specs=(P(), P(), P()))
    def step(vals, mask, codes, flat_docs, flat_tfs, gidx, block_term):
        # grouped count + sum over the row shard
        sel = jnp.logical_and(mask, vals >= 0)
        oh = jax.nn.one_hot(jnp.clip(codes, 0, num_groups - 1), num_groups,
                            dtype=jnp.float32)
        oh = oh * sel.astype(jnp.float32)[..., None]
        counts = jax.lax.psum(jnp.einsum("rbg->g", oh), AXIS)
        sums = jax.lax.psum(
            jnp.einsum("rbg,rb->g", oh,
                       jnp.where(sel, vals, 0).astype(jnp.float32)), AXIS)
        # BM25-ish scoring over the posting shard
        valid = gidx >= 0
        safe = jnp.where(valid, gidx, 0)
        docs = flat_docs[safe]
        tfs = flat_tfs[safe].astype(jnp.float32)
        contrib = jnp.where(valid, tfs / (tfs + 1.2), 0.0)
        local = jnp.zeros_like(flat_docs, dtype=jnp.float32)
        local = local.at[docs.reshape(-1)].add(contrib.reshape(-1))
        scores = jax.lax.psum(local, AXIS)
        return counts, sums, scores

    from ..obs import device as obs_device
    return obs_device.compiled("mesh_query",
                               (_mesh_key(mesh), num_groups),
                               lambda: step)
