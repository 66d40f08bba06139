"""Sharded execution tier: the same pipeline over hash-partitioned data.

PAPER.md §8's scale-out claim is "the same program over a mesh" — no
shard-aware operators, no exchange plans: storage partitions
deterministically, every shard runs the UNCHANGED morsel / fused-device
pipeline over its partition, and the engine's existing deterministic
merge sinks (ordered partial merge from PR 1, single-heap top-k,
partial-aggregate combine) become the cross-shard combiners.

Partitioning is a pure function of (row count, `serene_morsel_rows`,
`serene_shards`): morsel block b belongs to shard b % N (round-robin).
Round-robin keeps existing blocks pinned to their shard forever, so a
pure append only creates/extends TAIL blocks — every other shard's zone
maps, device uploads and cached fragments stay valid, the same
append-friendliness the zone maps rely on. `serene_shards = 1` is
today's unsharded execution and the bit-identity parity oracle: the
shard split only GROUPS work, the combine consumes partials in the same
global morsel order the unsharded path produces, so results are
bit-identical at any shard count, worker count, or device count.

Placement: shard pipelines run as concurrent PR-1 worker-pool tasks;
when a multi-device jax mesh is present (parallel/mesh.py), per-shard
fused device programs additionally pin their inputs to
`mesh.shard_devices()` so shard s dispatches on device s % n_devices —
the data axis of the mesh, with the host-side exact integer combine
playing the psum role.
"""

from __future__ import annotations

from typing import Callable, Optional

from ..utils import metrics


def shard_count(settings=None) -> int:
    """The session's `serene_shards` (>= 1). settings=None → the
    executing connection's settings when inside a statement, else the
    global default (library callers outside any session) — the
    session_workers(None) pattern."""
    if settings is None:
        from ..engine import CURRENT_CONNECTION
        conn = CURRENT_CONNECTION.get()
        if conn is not None:
            settings = conn.settings
    try:
        if settings is not None:
            n = int(settings.get("serene_shards"))
        else:
            from ..utils.config import REGISTRY
            n = int(REGISTRY.get_global("serene_shards"))
    except KeyError:  # pragma: no cover — registry always declares it
        n = 1
    return max(1, n)


def combine_mode(settings=None) -> str:
    """Resolved `serene_shard_combine`: 'device' or 'host'. 'auto'
    resolves to device when the process sees more than one jax device
    (the mesh data axis has real width), else host — so a single-chip
    box defaults to the PR 9 per-shard-dispatch path and a multi-device
    mesh gets the one-dispatch psum combine. The auto probe is PASSIVE:
    it never initializes the jax backend (a process that has dispatched
    nothing must not claim the accelerator — see
    mesh.device_count_if_initialized), so before the first real device
    dispatch of the process auto conservatively reads host.
    Same settings-resolution pattern as shard_count(None)."""
    if settings is None:
        from ..engine import CURRENT_CONNECTION
        conn = CURRENT_CONNECTION.get()
        if conn is not None:
            settings = conn.settings
    try:
        if settings is not None:
            mode = str(settings.get("serene_shard_combine"))
        else:
            from ..utils.config import REGISTRY
            mode = str(REGISTRY.get_global("serene_shard_combine"))
    except KeyError:  # pragma: no cover — registry always declares it
        mode = "auto"
    if mode == "auto":
        from ..parallel.mesh import device_count_if_initialized
        return "device" if device_count_if_initialized() > 1 else "host"
    return mode


def shard_of_block(block: int, n_shards: int) -> int:
    """Round-robin block→shard assignment (THE partitioning function)."""
    return block % n_shards


def shard_spans(nrows: int, block_rows: int, n_shards: int
                ) -> list[list[tuple[int, int]]]:
    """Per-shard row spans of a table: shard s owns every morsel block b
    with b % n_shards == s, as [(start, end)] in ascending block order.
    Empty tables yield n_shards empty lists."""
    out: list[list[tuple[int, int]]] = [[] for _ in range(n_shards)]
    for b, start in enumerate(range(0, nrows, block_rows)):
        out[shard_of_block(b, n_shards)].append(
            (start, min(start + block_rows, nrows)))
    return out


def group_round_robin(items: list, n_shards: int) -> list[list]:
    """Round-robin grouping of an ordered work list (segments, morsels)
    into at most n_shards non-empty shard groups, preserving intra-group
    order. Pure function of (len(items), n_shards) — never of worker
    count or scheduling."""
    n = min(n_shards, len(items))
    if n <= 1:
        return [list(items)] if items else []
    groups: list[list] = [[] for _ in range(n)]
    for i, it in enumerate(items):
        groups[i % n].append(it)
    return groups


def run_shard_tasks(settings, fn: Callable, shard_items: list) -> list:
    """One pipeline execution per shard on the shared worker pool,
    results in shard order (deterministic). Counts each launched shard
    pipeline in the ShardPipelines gauge; under `serene_trace` each
    shard's execution is stamped as a `shard_pipeline` span (with its
    shard index) into the query's timeline — the shard fan-out becomes
    visible as parallel lanes in the Chrome trace."""
    from ..obs.resources import current_accountant
    from ..obs.trace import current_trace, span
    from ..parallel.pool import parallel_map
    metrics.SHARD_PIPELINES.add(len(shard_items))
    acct = current_accountant()
    if acct is not None:
        # live progress: the statement is now fanning out per-shard
        # pipelines (sdb_query_progress current-operator label)
        acct.set_op(f"ShardFanout n={len(shard_items)}")
    trace = current_trace()
    if trace is None:
        return parallel_map(settings, fn, shard_items)

    def traced(pair):
        s, item = pair
        # the fused device path passes REAL shard ids (possibly
        # non-contiguous after pruning, e.g. [0, 2, 3]) — label with
        # them so the lane agrees with the device spans stamped inside;
        # other callers pass per-shard work lists, labeled by position
        label = item if isinstance(item, int) else s
        with span("shard_pipeline", "shard", shard=label):
            return fn(item)

    return parallel_map(settings, traced, list(enumerate(shard_items)))


class ShardedRanges(list):
    """Per-shard build-key min/max conjunct groups published through
    `ExecContext.join_filters` (shard-to-shard sideways information
    passing). Each element is one build shard's conjunct list
    (`col >= lo AND col <= hi` per rangeable key); a probe block may
    match a build row only if SOME shard's conjunction can hold, so the
    block verdict is the OR (elementwise max) across groups — strictly
    more pruning than the single global range whenever the shard ranges
    leave gaps."""


def build_shard_ranges(probe_keys, build_key_cols,
                       shard_view: list[list[tuple[int, int]]]
                       ) -> Optional[ShardedRanges]:
    """Per-build-shard key ranges: slice the build keys by the given
    shard view (TableProvider.shard_view for provider-backed sides,
    shard_spans for materialized batches) and fold each shard's
    observed min/max into synthetic range conjuncts
    (zonemap.build_key_range_exprs per shard). None when no shard
    publishes a rangeable key (caller falls back to the global range /
    plain scan)."""
    from .zonemap import build_key_range_exprs
    groups = ShardedRanges()
    for spans in shard_view:
        if not spans:
            continue
        sliced = [_concat_spans(c, spans) for c in build_key_cols]
        exprs = build_key_range_exprs(probe_keys, sliced)
        if not exprs:
            return None     # an unrangeable shard can match anywhere
        groups.append(exprs)
    return groups if groups else None


def _concat_spans(col, spans: list[tuple[int, int]]):
    """One column restricted to a shard's row spans (a host-side view
    concat; spans are block-aligned and ascending)."""
    if len(spans) == 1:
        return col.slice(spans[0][0], spans[0][1])
    from ..columnar.column import Batch, concat_batches
    parts = [Batch(["c"], [col.slice(s, e)]) for s, e in spans]
    return concat_batches(parts).columns[0]


def sharded_verdicts(provider, settings, groups: ShardedRanges,
                     columns: list[str], block_rows: int, pin=None):
    """Per-block verdicts for the OR of per-shard range groups: a block
    prunes only when EVERY shard's range conjunction proves no row can
    match (elementwise max over the per-group verdict vectors — SKIP <
    SCAN < ALL, so max is exactly disjunction). None when any group's
    range cannot be analyzed (unknown ⇒ no pruning)."""
    import numpy as np

    from . import zonemap
    combined = None
    for exprs in groups:
        v = zonemap.block_verdicts(provider, settings, exprs, columns,
                                   block_rows, pin)
        if v is None:
            return None
        combined = v if combined is None else np.maximum(combined, v)
    return combined


def verify_sharded_pruned(groups: ShardedRanges, full, spans,
                          what: str) -> None:
    """serene_zonemap_verify for shard-pruned blocks: a block was pruned
    because NO shard's range conjunction can hold, so re-scan it against
    every group and fail loudly if any group's conjunction matches a
    row."""
    from . import zonemap
    for exprs in groups:
        zonemap.verify_pruned_blocks(exprs, full, spans, what)


def count_shard_pruned(verdicts, nbytes_per_row: int = 0,
                       block_rows: int = 0, nrows: int = 0) -> None:
    """Gauge attribution of one shard-filter pruning pass; when the
    caller is about to upload (device path) it passes the per-row byte
    width so the skipped transfer volume lands in ShardBytesSkipped."""
    import numpy as np

    from . import zonemap
    pruned_blocks = np.flatnonzero(verdicts == zonemap.SKIP)
    if not len(pruned_blocks):
        return
    metrics.SHARD_MORSELS_PRUNED.add(len(pruned_blocks))
    if nbytes_per_row and block_rows:
        rows = 0
        for b in pruned_blocks:
            rows += min((int(b) + 1) * block_rows, nrows) - \
                int(b) * block_rows
        metrics.SHARD_BYTES_SKIPPED.add(rows * nbytes_per_row)


def stamp_profile(ctx, key: int, pipelines: int, pruned: int = 0,
                  collective: bool = False) -> None:
    """Per-shard span stamp for EXPLAIN ANALYZE's `Shards:` line.
    `collective=True` marks the shards as combined in-program (one
    shard_map dispatch, psum/pmin/pmax) — rendered as combine=device."""
    prof = getattr(ctx, "profile", None)
    if prof is not None:
        prof.add_shards(key, pipelines, pruned,
                        pipelines if collective else 0)


__all__ = [
    "shard_count", "combine_mode", "shard_of_block", "shard_spans",
    "group_round_robin", "run_shard_tasks", "ShardedRanges",
    "build_shard_ranges", "sharded_verdicts", "verify_sharded_pruned",
    "count_shard_pruned", "stamp_profile",
]
