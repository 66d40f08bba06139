"""One fused device program over a chain of key joins.

The shape TPC-H and every star/snowflake report send: a fact table (the
probe) joined by inner equi-joins to relations that are each UNIQUE on
their join key (primary key to foreign key), under filters, a GROUP BY
and aggregates. Left-deep, as the planner's join graph lays it out, each
join hangs off one relation already joined (its parent): lineitem →
orders → customer → nation → region is a chain through the build sides'
own keys.

Every probe row finds its row of each relation through a join row index:
for the edge parent → child, the child's row for each parent row (-1 for
none), built on the host once per pair of table publications from the
child's key → row lookup. The probe's own edges sit in HBM as tiles of
the probe's rows. A build relation is ONE int32 matrix of its rows
(DEVICE_CACHE, per publication and what the statement reads of it): the
columns read, the masks of those that hold NULLs, and its children's row
indexes, the next hop of the chain. So a child of the probe is one row
gather, a grandchild a gather of a gather through its parent's matrix
(lineitem → orders → customer), and no pair list is ever made, on host or
device. Filters, predicates over two build sides, CASE arguments, group
keys and aggregate arguments then compile as they would over one table
(exec/device.py). A predicate or key that reads one string or date column
through a function the device has no form for (`p_name LIKE '%green%'`,
`extract(year FROM o_orderdate)`) is evaluated once over that column's
dictionary or date range on the host: over a build relation the result
rides in its matrix, over the probe the program reads it by code.

Group codes come from dictionary codes, small integer ranges and a
relation's row id: a group key that is a single-column join key stands
for its relation's row (Q3's `l_orderkey`, Q10's `c_custkey`), and keys
read from that relation or those hanging off it add nothing to the code
space. The host reads each present group's keys back at one of its probe
rows (the program keeps the largest), so no key range is coded wider
than the rows it holds.

Exactness (x64 off): every node of an integer expression is bounded from
the columns' ranges and must stay inside int32 (`expr_bounds`); sums ride
ops/agg.py's limb columns (`int_limbs`) summed in int32, each limb as
wide as the most rows a group can hold allows (8 bits for 2^23 rows; 28
where a group is an order's lines). A product that leaves int32 is summed
as device_agg's `wide_parts`. Few groups reduce as masked sums, sixteen a
pass over the rows; many as one 1-D scatter a column of the rows that
survive, gathered first into the smallest rung of a closed ladder of
sizes that holds them (every row past its last rung).

One table is device_agg's, whatever its column types. What the program
cannot admit declines with a named reason. A two-table join whose build
side is not unique on its key, or one under `serene_shards` > 1 (whose
probe shards the pair-count program runs side by side), stays
exec/device_pipeline.py's.
"""

from __future__ import annotations

import copy
import threading
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..columnar import dtypes as dt
from ..columnar.column import Batch, Column
from ..columnar.device import DeviceNarrowingError, LANES
from ..obs import device as obs_device
from ..obs.trace import stage
from ..ops import agg as ops_agg
from ..sql.binder import _expr_key
from ..sql.expr import (BoundCase, BoundColumn, BoundExpr, BoundFunc,
                        BoundLiteral)
from ..utils import log, metrics
from .device import NotCompilable, compile_expr, expr_bounds
from .device_agg import MAX_INT_KEY_RANGE, wide_parts

#: relations one program joins
MAX_RELATIONS = 8
#: group code space (a build side's row ids are one axis: Q3's 1.5M
#: orders at SF1)
MAX_GROUPS = 1 << 22
#: probe rows under which every int32 limb / count scatter is exact
MAX_ROWS_EXACT = 1 << 23
#: widest value range, and dictionary, a single-column function is
#: evaluated over on the host (TPC-H SF1's p_name: 200,000 codes)
MAX_LOOKUP = 1 << 16
MAX_LOOKUP_CODES = 1 << 22
#: dense key -> row lookups up to this key range; wider keys sort
MAX_DENSE_KEYS = 1 << 26

_AGG_FUNCS = {"count_star", "count", "sum", "avg", "min", "max"}
#: single-column functions that become a host-evaluated lookup table
_LOOKUP_FUNCS = {"like", "extract", "date_part", "substring", "substr"}


class _Rel:
    """One relation of the chain: its scan, scan predicates, columns'
    place in the joined schema, and the edge it hangs off."""

    def __init__(self, scan, preds, offset: int):
        self.scan, self.preds, self.offset = scan, preds, offset
        self.parent: Optional[int] = None
        self.pkeys: list[int] = []       # parent's key columns, joined idx
        self.bkeys: list[int] = []       # own key columns, scan idx
        self.side = None                 # device_pipeline._Side

    @property
    def width(self) -> int:
        return len(self.scan.columns)


def _shift(e: BoundExpr, by: int) -> BoundExpr:
    e = copy.deepcopy(e)
    for x in e.walk():
        if isinstance(x, BoundColumn):
            x.index += by
    return e


def _subst(e: BoundExpr, exprs: list) -> BoundExpr:
    """`e` over a projection's output, rewritten over its input."""
    if isinstance(e, BoundColumn):
        return copy.deepcopy(exprs[e.index])
    e = copy.copy(e)
    if isinstance(e, BoundFunc):
        e.args = [_subst(a, exprs) for a in e.args]
    elif isinstance(e, BoundCase):
        e.branches = [(_subst(c, exprs), _subst(v, exprs))
                      for c, v in e.branches]
        if e.else_ is not None:
            e.else_ = _subst(e.else_, exprs)
    return e


def _split_and(e: BoundExpr) -> list:
    if isinstance(e, BoundFunc) and e.name == "and":
        return [x for a in e.args for x in _split_and(a)]
    return [e]


def _recognize(node):
    """(rels, post predicates, group exprs, agg specs) of an Aggregate
    over Filter* [Project] Filter* over a left-deep inner key-join chain
    of Filter*(Scan), or None. Expressions come back over the joined
    schema (a derived table's projection substituted in)."""
    from .device_pipeline import _unwrap_side
    from .plan import FilterNode, JoinNode, ProjectNode
    group = list(node.group_exprs)
    aggs = [copy.copy(s) for s in node.aggs]
    post: list = []
    child = node.child
    projected = False
    while True:
        if isinstance(child, FilterNode):
            post.extend(_split_and(child.pred))
            child = child.child
        elif isinstance(child, ProjectNode) and not projected:
            ex = child.exprs
            group = [_subst(g, ex) for g in group]
            post = [_subst(p, ex) for p in post]
            for s in aggs:
                if s.arg is not None:
                    s.arg = _subst(s.arg, ex)
                if s.filter is not None:
                    s.filter = _subst(s.filter, ex)
            projected = True
            child = child.child
        else:
            break

    def unwind(plan):
        if isinstance(plan, FilterNode) and isinstance(plan.child,
                                                       (JoinNode,
                                                        FilterNode)):
            post.extend(_split_and(plan.pred))
            return unwind(plan.child)
        if not isinstance(plan, JoinNode):
            side = _unwrap_side(plan)
            return None if side is None else [_Rel(side[0], side[1], 0)]
        if plan.kind != "inner" or not plan.left_keys or \
                plan.residual is not None or plan.merge_pairs:
            return None
        left = unwind(plan.left)
        side = _unwrap_side(plan.right)
        if left is None or side is None:
            return None
        rel = _Rel(side[0], side[1], len(plan.left.names))
        for lk, rk in zip(plan.left_keys, plan.right_keys):
            if not (isinstance(lk, BoundColumn) and
                    isinstance(rk, BoundColumn)):
                return None
            rel.pkeys.append(lk.index)
            rel.bkeys.append(rk.index)
        owners = {_owner(left, i) for i in rel.pkeys}
        if len(owners) != 1:
            return None
        rel.parent = owners.pop()
        return left + [rel]

    rels = unwind(child)
    if rels is None or len(rels) > MAX_RELATIONS:
        return None
    return rels, post, group, aggs


def _owner(rels: list, joined_index: int) -> int:
    k = 0
    while k + 1 < len(rels) and rels[k + 1].offset <= joined_index:
        k += 1
    return k


def pair_bytes(node, join, probe_side, build_side, post: list,
               ctx) -> int:
    """`work_bytes` of a statement the two-table pair-count program ran
    (exec/device_pipeline.py)."""
    from .device_pipeline import _Side
    nl = len(join.left.names)
    rels = [_Rel(probe_side[0], probe_side[1], 0),
            _Rel(build_side[0], build_side[1], nl)]
    for r in rels:
        r.side = _Side(r.scan, [], ctx)
    ref: set = set()
    for r in rels:
        for p in r.preds:
            ref |= {x.index + r.offset for x in p.walk()
                    if isinstance(x, BoundColumn)}
    exprs = post + list(node.group_exprs) + list(join.left_keys) + \
        [e for s in node.aggs for e in (s.arg, s.filter) if e is not None]
    for e in exprs:
        ref |= {x.index for x in e.walk() if isinstance(x, BoundColumn)}
    for k in join.right_keys:
        ref |= {x.index + nl for x in k.walk() if isinstance(x, BoundColumn)}
    return work_bytes(rels, ref)


def claims(node) -> bool:
    """Is this Aggregate the chain program's alone: a key-join chain of
    three relations or more? A two-table join may be the pair-count
    program's too, and one table is device_agg's."""
    shape = _recognize(node)
    return shape is not None and len(shape[0]) > 2


# -- join row indexes: key -> row, per pair of publications --------------------

_INDEX: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
_INDEX_MAX_BYTES = 1 << 30
_index_bytes = 0
_index_lock = threading.Lock()


def _key_codes(cols: list, los: list, spans: list):
    """Mixed-radix int64 codes of key columns (NULL / out of range: -1)."""
    code = np.zeros(len(cols[0]), np.int64)
    ok = np.ones(len(cols[0]), bool)
    for c, lo, span in zip(cols, los, spans):
        v = c.data.astype(np.int64) - lo
        ok &= c.valid_mask() & (v >= 0) & (v < span)
        code = code * span + np.clip(v, 0, span - 1)
    return np.where(ok, code, -1)


def join_index(parent, child, pcols: list, bcols: list) -> np.ndarray:
    """int32 child row of each parent row (-1: no partner), from the
    child's key -> row lookup. Raises NotCompilable('build_not_unique')
    where a key holds two child rows. Cached per pair of publications;
    a write to either side moves the key."""
    global _index_bytes
    ck = (parent.pub, child.pub, tuple(pcols), tuple(bcols))
    with _index_lock:
        hit = _INDEX.get(ck)
        if hit is not None:
            _INDEX.move_to_end(ck)
            return hit
    pk = [parent.host_col(c) for c in pcols]
    bk = [child.host_col(c) for c in bcols]
    for c in pk + bk:
        if not (c.type.is_integer or c.type.id is dt.TypeId.DATE):
            raise NotCompilable(f"join key of type {c.type}",
                                "join_key_type")
    los, spans = [], []
    for c in bk:
        v = c.data[c.valid_mask()] if c.validity is not None else c.data
        lo, hi = (int(v.min()), int(v.max())) if len(v) else (0, 0)
        los.append(lo)
        spans.append(hi - lo + 1)
    if float(np.prod([float(s) for s in spans])) >= 2.0 ** 62:
        raise NotCompilable("composite key space", "join_key_type")
    bcode = _key_codes(bk, los, spans)
    pcode = _key_codes(pk, los, spans)
    live = np.flatnonzero(bcode >= 0)
    total = int(np.prod(spans))
    if total <= MAX_DENSE_KEYS:
        table = np.full(total, -1, np.int32)
        table[bcode[live]] = live.astype(np.int32)
        if int((table >= 0).sum()) != len(live):
            raise NotCompilable("build side not unique on its key",
                                "build_not_unique")
        idx = np.where(pcode >= 0, table[np.clip(pcode, 0, None)], -1)
    else:
        order = live[np.argsort(bcode[live], kind="stable")]
        sorted_codes = bcode[order]
        if len(sorted_codes) > 1 and \
                (np.diff(sorted_codes) == 0).any():
            raise NotCompilable("build side not unique on its key",
                                "build_not_unique")
        at = np.searchsorted(sorted_codes, pcode)
        at = np.clip(at, 0, max(len(order) - 1, 0))
        hit = (pcode >= 0) & (len(order) > 0)
        if len(order):
            hit &= sorted_codes[at] == pcode
        idx = np.where(hit, order[at] if len(order) else -1, -1)
    idx = idx.astype(np.int32)
    metrics.DEVICE_JOIN_INDEX_BUILDS.add()
    with _index_lock:
        stale = [k for k in _INDEX if k[0][0] == ck[0][0] and
                 k[1][0] == ck[1][0] and k[2:] == ck[2:] and k != ck]
        for k in stale:
            _index_bytes -= int(_INDEX.pop(k).nbytes)
        while _INDEX and _index_bytes + idx.nbytes > _INDEX_MAX_BYTES:
            _index_bytes -= int(_INDEX.popitem(last=False)[1].nbytes)
        _INDEX[ck] = idx
        _index_bytes += int(idx.nbytes)
        metrics.DEVICE_JOIN_INDEX_BYTES.set(_index_bytes)
    return idx


# -- the bytes a statement has to read -----------------------------------------


def _width(lo: int, hi: int) -> int:
    """benchmark/harness/workbytes.py's rule: the narrowest 1/2/4/8-byte
    integer that holds the range."""
    span = max(int(hi) - int(lo), 0)
    for w in (1, 2, 4):
        if span < (1 << (8 * w)):
            return w
    return 8


def work_bytes(rels: list, referenced: set) -> int:
    """For every relation, its rows times the narrowest integer width of
    each column the statement references (a string column as its code).
    The same count as `benchmark/references/tpch_numpy.py: join_bytes`."""
    from .device_pipeline import _col_stats
    total = 0
    for r in rels:
        cols = sorted(i - r.offset for i in referenced
                      if r.offset <= i < r.offset + r.width)
        w = 0
        for ci in cols:
            name = r.scan.columns[ci]
            col = r.side.host_col(name)
            if col.type.is_string:
                d = col.dictionary
                w += _width(0, max((0 if d is None else len(d)) - 1, 0))
            else:
                _, _, lo, hi = _col_stats(r.side, name)
                w += 8 if lo is None else _width(lo, hi)
        total += r.side.nrows * w
    return total


# -- the program ---------------------------------------------------------------


def try_device_chain(node, ctx) -> tuple[Optional[Batch], bool]:
    """(result, whether a decline was noted). The result is None when
    the program did not run; a decline is noted only where no other
    device tier could take the statement (three relations or more): two
    tables it does not admit stay the pair-count program's to take or
    decline, and one table is device_agg's."""
    from .device_pipeline import _note_decline, fused_enabled
    settings = ctx.settings
    if settings.get("serene_device") == "cpu" or not fused_enabled(settings):
        return None, False
    shape = _recognize(node)
    if shape is None:
        return None, False
    rels, post, group, aggs = shape
    if len(rels) == 1:
        return None, False           # device_agg's
    from .shard import shard_count
    if len(rels) == 2 and shard_count(settings) > 1:
        # the pair-count program runs a probe's shards side by side
        return None, False
    if settings.get("serene_device") == "auto":
        try:
            if rels[0].scan.provider.row_count() < \
                    settings.get("serene_device_min_rows"):
                return None, False
        except NotImplementedError:
            return None, False
    try:
        with stage("device_prepare", op="chain"):
            return _run(node, rels, post, group, aggs, ctx), False
    except (NotCompilable, DeviceNarrowingError) as e:
        reason = getattr(e, "reason", "not_compilable")
        log.debug("device", f"join chain fell back: {e}")
        if len(rels) < 3:
            return None, False       # the pair-count program's
        _note_decline(reason, ctx, node)
        return None, True


#: host tables of single-column functions, per publication, column and
#: expression: evaluated once, not per statement
_LOOKUPS: "OrderedDict[tuple, tuple]" = OrderedDict()
_LOOKUPS_MAX = 256
_lookup_lock = threading.Lock()


def _lookup_table(e: BoundFunc, col: Column, side, name: str) -> tuple:
    """(lo, int32 table, type) of `e` over its one column's domain: the
    dictionary's codes, or an integer / date range."""
    from .device_pipeline import _col_stats
    if col.type.is_string:
        d = col.dictionary
        if d is None or len(d) > MAX_LOOKUP_CODES:
            raise NotCompilable("lookup over a wide dictionary", "lookup")
        lo, dom = 0, Column(col.type, np.arange(len(d), dtype=np.int32),
                            None, d)
    elif col.type.is_integer or col.type.id is dt.TypeId.DATE:
        _, _, lo, hi = _col_stats(side, name)
        if hi - lo + 1 > MAX_LOOKUP:
            raise NotCompilable("lookup domain too wide", "lookup")
        dom = Column(col.type, np.arange(lo, hi + 1).astype(
            col.type.np_dtype))
    else:
        raise NotCompilable(f"lookup over {col.type}", "lookup")
    one = _shift(e, 0)
    for x in one.walk():
        if isinstance(x, BoundColumn):
            x.index = 0
    vals = one.eval(Batch(["v"], [dom]))
    data = vals.data
    if vals.type.is_float and not (np.all(np.isfinite(data)) and
                                   np.all(data == np.round(data))):
        raise NotCompilable("non-integral lookup", "lookup")
    if vals.type.is_string:
        raise NotCompilable("string-valued lookup", "lookup")
    table = np.where(vals.valid_mask(), data, 0).astype(np.int32)
    t = dt.BOOL if vals.type.id is dt.TypeId.BOOL else dt.INT
    return int(lo), table, t


def _pow2(n: int, floor: int = 1024) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def _run(node, rels, post, group, aggs, ctx) -> Batch:
    import jax.numpy as jnp

    from .device_pipeline import DEVICE_CACHE, _Side, _col_stats
    for s in aggs:
        if s.func not in _AGG_FUNCS:
            raise NotCompilable(s.func, "agg_func")
        if s.distinct or s.filter is not None or s.order_by:
            raise NotCompilable("DISTINCT / FILTER / ORDER BY aggregate",
                                "agg_modifier")
    for k, r in enumerate(rels):
        # zone maps shrink the probe's upload; build sides are read whole
        r.side = _Side(r.scan, r.preds if k == 0 else [], ctx)
    probe = rels[0].side
    if probe.n_live > MAX_ROWS_EXACT:
        raise NotCompilable("probe rows past the exact-scatter bound",
                            "probe_rows")
    types: list = [t for r in rels for t in r.scan.types]
    width = len(types)

    def host_col_of(ji: int) -> Column:
        r = rels[_owner(rels, ji)]
        return r.side.host_col(r.scan.columns[ji - r.offset])

    preds = [_shift(p, r.offset) for r in rels for p in r.preds] + post
    # single-column functions the device has no form for: a host table
    # over the column's domain, read by code (`lookups`: synthetic joined
    # index -> (source index, lo, table, type, cache key))
    lookups: dict[int, tuple] = {}

    def rewrite(e: BoundExpr) -> BoundExpr:
        if isinstance(e, BoundFunc) and e.name in _LOOKUP_FUNCS:
            src = {x.index for x in e.walk() if isinstance(x, BoundColumn)}
            if len(src) == 1:
                return lookup(e, src.pop())
        if isinstance(e, BoundFunc):
            e = copy.copy(e)
            e.args = [rewrite(a) for a in e.args]
        elif isinstance(e, BoundCase):
            e = copy.copy(e)
            e.branches = [(rewrite(c), rewrite(v)) for c, v in e.branches]
            e.else_ = rewrite(e.else_) if e.else_ is not None else None
        return e

    def lookup(e: BoundFunc, ji: int) -> BoundColumn:
        side = rels[_owner(rels, ji)].side
        key = (side.pub, col_name(ji), _expr_key(e))
        with _lookup_lock:
            hit = _LOOKUPS.get(key)
        if hit is None:
            hit = _lookup_table(e, host_col_of(ji), side, col_name(ji))
            with _lookup_lock:
                while len(_LOOKUPS) >= _LOOKUPS_MAX:
                    _LOOKUPS.popitem(last=False)
                _LOOKUPS[key] = hit
        lo, table, t = hit
        si = width + len(lookups)
        lookups[si] = (ji, lo, table, t, key[1:])
        return BoundColumn(si, t, f"#lookup{si}")

    def never_null(ji: int) -> bool:
        if ji >= width:
            ji = lookups[ji][0]
        return _col_stats(rels[_owner(rels, ji)].side, col_name(ji))[0]

    def col_name(ji: int) -> str:
        r = rels[_owner(rels, ji)]
        return r.scan.columns[ji - r.offset]

    dev_preds = [rewrite(p) for p in preds]
    dev_group = [rewrite(g) for g in group]
    dev_args = [rewrite(s.arg) if s.arg is not None else None for s in aggs]
    all_types = types + [lookups[si][3] for si in sorted(lookups)]

    # dictionaries of the string columns any expression reads
    dictionaries: dict[int, np.ndarray] = {}
    for e in dev_preds + dev_group + [a for a in dev_args if a is not None]:
        for x in e.walk():
            if isinstance(x, BoundColumn) and x.index < width and \
                    x.type.is_string and x.index not in dictionaries:
                d = host_col_of(x.index).dictionary
                if d is not None:
                    dictionaries[x.index] = d

    stats_cache: dict[int, tuple] = {}

    def col_bounds(ji: int) -> tuple[int, int]:
        if ji >= width:
            t = lookups[ji][2]
            return (int(t.min()), int(t.max())) if len(t) else (0, 0)
        if ji not in stats_cache:
            r = rels[_owner(rels, ji)]
            _, _, lo, hi = _col_stats(r.side, col_name(ji))
            if lo is None:
                raise NotCompilable("float column", "agg_type")
            stats_cache[ji] = (lo, hi)
        return stats_cache[ji]

    compiled_preds = []
    for p in dev_preds:
        expr_bounds(p, col_bounds)
        compiled_preds.append(compile_expr(p, all_types, dictionaries))

    # -- group codes ---------------------------------------------------------
    children = {k: [j for j, r in enumerate(rels) if r.parent == k]
                for k in range(len(rels))}

    def below(k: int) -> set:
        out = {k}
        for j in children[k]:
            out |= below(j)
        return out

    key_plans: list[tuple] = []      # (kind, joined idx | rel, lo, size)
    determined: set = set()
    row_keys: set = set()            # group exprs standing for a row id
    for gi, g in enumerate(dev_group):
        if isinstance(g, BoundColumn) and g.index < width:
            for k, r in enumerate(rels[1:], 1):
                if len(r.bkeys) == 1 and g.index in (
                        r.pkeys[0], r.offset + r.bkeys[0]):
                    row_keys.add(gi)
                    if k not in determined:
                        key_plans.append(("row", k, 0, r.side.nrows))
                        determined |= below(k)
                    break
    covered = set()
    for k in determined:
        covered |= set(range(rels[k].offset, rels[k].offset + rels[k].width))
    for gi, g in enumerate(dev_group):
        if gi in row_keys:
            continue
        reads = {x.index for x in g.walk() if isinstance(x, BoundColumn)}
        srcs = {lookups[i][0] if i >= width else i for i in reads}
        if srcs and srcs <= covered:
            continue                 # a function of a determined row
        if not isinstance(g, BoundColumn):
            raise NotCompilable("computed group key", "group_key")
        if g.index >= width:
            t = lookups[g.index][2]
            lo, hi = (int(t.min()), int(t.max())) if len(t) else (0, 0)
            key_plans.append(("int", g.index, lo, hi - lo + 2))
        elif g.type.is_string:
            d = dictionaries.get(g.index)
            if d is None:
                raise NotCompilable("string key without dictionary",
                                    "group_key")
            key_plans.append(("dict", g.index, 0, len(d) + 1))
        elif g.type.is_integer or g.type.is_decimal or g.type.id in (
                dt.TypeId.DATE, dt.TypeId.BOOL):
            lo, hi = col_bounds(g.index)
            if hi - lo + 1 > MAX_INT_KEY_RANGE:
                raise NotCompilable("group key range too wide", "group_key")
            key_plans.append(("int", g.index, lo, hi - lo + 2))
        else:
            raise NotCompilable(f"group key of type {g.type}", "group_key")
    space = 1
    for kp in key_plans:
        space *= kp[3]
        if space > MAX_GROUPS:
            raise NotCompilable("group code space too large", "group_space")
    group_mode = bool(group)

    # -- aggregate plans -----------------------------------------------------
    # one accumulator column layout for all aggregates: col 0 counts the
    # joined rows; a count of an argument's non-NULL rows only where it
    # can be NULL; each distinct summed part once (SUM and AVG of one
    # argument share it), as the limbs its range needs
    layout = _Layout(never_null)
    agg_plans: list[tuple] = []      # (mode, vcnt col, parts | mm slot)
    for s, a in zip(aggs, dev_args):
        if s.func == "count_star":
            agg_plans.append(("star", 0, None))
            continue
        if a.type.is_string and s.func != "count":
            raise NotCompilable(f"{s.func} over strings", "agg_type")
        if a.type.is_float:
            raise NotCompilable(f"{s.func} over {a.type}", "agg_type")
        vcnt = layout.count_of(a)
        if s.func == "count":
            agg_plans.append(("count", vcnt, None))
            continue
        if s.func in ("min", "max"):
            expr_bounds(a, col_bounds)
            agg_plans.append((s.func, vcnt, layout.minmax(a, s.func)))
            continue
        try:
            lo, hi = expr_bounds(a, col_bounds)
            parts = [(a, 1, lo, hi)]
        except NotCompilable:
            parts = wide_parts(a, col_bounds)
        agg_plans.append(("sum", vcnt, [(layout.part(p, lo, hi), w)
                                        for p, w, lo, hi in parts]))
    compiled_parts = [(compile_expr(e, all_types, dictionaries), lo, hi)
                      for e, lo, hi in layout.parts]
    compiled_masks = [compile_expr(e, all_types, dictionaries)
                      for e in layout.masks]
    compiled_mm = [(compile_expr(e, all_types, dictionaries), f, m)
                   for e, f, m in layout.mm]

    # -- the device environment ----------------------------------------------
    needed: set[int] = set()
    for ce in compiled_preds + [c for c, _, _ in compiled_parts] + \
            compiled_masks + [c for c, _, _ in compiled_mm]:
        needed.update(ce.inputs)
    for kind, ref, _lo, _size in key_plans:
        if kind != "row":
            needed.add(ref)
    lk_needed = sorted(i for i in needed if i >= width)
    for si in lk_needed:
        # a lookup over the probe gathers its table by the column's codes
        # on the device; one over a build relation is applied on the host
        # and packed with the relation's rows
        if _owner(rels, lookups[si][0]) == 0:
            needed.add(lookups[si][0])
    col_needed = sorted(i for i in needed if i < width)
    owner = {ji: _owner(rels, ji) for ji in col_needed + [
        lookups[si][0] for si in lk_needed]}
    whole = {ji: never_null(ji) for ji in owner}
    parents = [r.parent for r in rels]
    p_pad = _pow2(probe.n_live)
    # the probe's columns: device tiles of its zone range, as every tier
    probe_cols = [ji for ji in col_needed if owner[ji] == 0]
    env_cols = {ji: DEVICE_CACHE.column(
        probe.provider, probe.pub, col_name(ji),
        (lambda n=col_name(ji): probe.host_col(n)), probe.zrange,
        pad=p_pad) for ji in probe_cols}
    decode = {ji: (env_cols[ji].scheme, env_cols[ji].offset)
              for ji in probe_cols}
    lk_probe = [si for si in lk_needed if owner[lookups[si][0]] == 0]
    edge_of = {}                     # child k -> (pcols, bcols, host index)
    for k in range(1, len(rels)):
        r = rels[k]
        pcols = [col_name(i) for i in r.pkeys]
        bcols = [r.scan.columns[i] for i in r.bkeys]
        edge_of[k] = (pcols, bcols, join_index(rels[r.parent].side, r.side,
                                               pcols, bcols))
    # a probe edge: the child's row of each probe row, probe-aligned tiles
    probe_edges = [k for k in range(1, len(rels)) if parents[k] == 0]
    edge_dev = {}
    for k in probe_edges:
        pcols, bcols, host_idx = edge_of[k]

        def build(h=host_idx, zr=probe.zrange):
            h = h if zr is None else h[zr[0]:zr[1]]
            out = np.full(p_pad, -1, np.int32)
            out[:len(h)] = h
            return jnp.asarray(out.reshape(-1, LANES))
        edge_dev[k] = DEVICE_CACHE.array(
            probe.pub, "__join_index__",
            (rels[k].side.pub, tuple(pcols), tuple(bcols), probe.zrange,
             p_pad), build)
    # a build relation: ONE int32 matrix of its rows holding what the
    # statement reads of it — its columns (decoded), the masks of those
    # that hold NULLs, its children's row indexes (the next hop of the
    # chain) and the host tables of lookups over its columns — so that a
    # probe row reads it with one row gather
    packs: dict[int, tuple] = {}
    for k in range(1, len(rels)):
        r = rels[k]
        desc: list[tuple] = []
        for ji in col_needed:
            if owner[ji] == k:
                desc.append(("col", ji))
                if not whole[ji]:
                    desc.append(("mask", ji))
        for j in range(1, len(rels)):
            if parents[j] == k:
                desc.append(("edge", j))
        for si in lk_needed:
            src = lookups[si][0]
            if owner[src] == k:
                desc.append(("lookup", si))
                if not whole[src] and ("mask", src) not in desc:
                    desc.append(("mask", src))
        if not desc:
            continue
        n_pad = _pow2(r.side.nrows, floor=8)
        tag = tuple(_pack_tag(d, col_name, edge_of, lookups, rels)
                    for d in desc) + (n_pad,)

        def build(r=r, desc=desc, n_pad=n_pad):
            out = np.zeros((n_pad, len(desc)), np.int32)
            for c, d in enumerate(desc):
                out[:, c] = -1 if d[0] == "edge" else 0
                out[:r.side.nrows, c] = _pack_column(d, r, col_name,
                                                     edge_of, lookups)
            return jnp.asarray(out)
        packs[k] = (DEVICE_CACHE.array(r.side.pub, "__packed__", tag, build),
                    {d: c for c, d in enumerate(desc)},
                    r.side.nrows <= SMALL_TABLE)
    lk_dev = {}
    for si in lk_probe:
        ji, lo, table, _t, ekey = lookups[si]
        lk_dev[si] = DEVICE_CACHE.array(
            probe.pub, "__lookup__", (col_name(ji), ekey, len(table)),
            lambda t=table: jnp.asarray(t))
    from .device_pipeline import _rowmask_tiles
    prow = DEVICE_CACHE.array(probe.pub, "__rowmask__",
                              (probe.zrange, "pad", p_pad),
                              lambda: _rowmask_tiles(probe.n_live, p_pad))
    lk_src = {si: (lookups[si][0], lookups[si][1]) for si in lk_needed}
    pack_rels = sorted(packs)
    slots = {k: packs[k][1] for k in pack_rels}
    small_rel = {k: packs[k][2] for k in pack_rels}
    limb_w = layout.limb_width(_group_rows(rels, key_plans, edge_of,
                                           probe))
    rungs = _rungs(p_pad, space)

    def program(*flat):
        it = iter(flat)
        rowmask = next(it)
        edge_in = {k: next(it) for k in probe_edges}
        raw = {ji: (next(it), next(it)) for ji in probe_cols}
        packed = {k: next(it) for k in pack_rels}
        tables = {si: next(it) for si in lk_probe}
        valid = rowmask
        rows, oks, arrays, got = {}, {}, {}, {}
        for ji in probe_cols:
            data, mask = raw[ji]
            scheme, off = decode[ji]
            if scheme != "raw":
                data = data.astype(jnp.int32) + jnp.int32(off)
            arrays[ji] = (data, mask)
        for k in range(1, len(rels)):
            par = parents[k]
            if par == 0:
                ix = edge_in[k]
                ok = ix >= 0
            else:
                ix = got[par][("edge", k)]
                ok = jnp.logical_and(ix >= 0, oks[par])
            rows[k] = jnp.where(ok, ix, 0)
            oks[k] = ok
            valid = jnp.logical_and(valid, ok)
            if k not in packed:
                continue
            mat, shape = packed[k], ix.shape
            if small_rel[k]:
                # a small table: element gathers stay on chip memory
                got[k] = {d: jnp.take(mat[:, c], rows[k], mode="clip")
                          for d, c in slots[k].items()}
            else:
                g = jnp.take(mat, rows[k].ravel(), axis=0, mode="clip")
                got[k] = {d: g[:, c].reshape(shape)
                          for d, c in slots[k].items()}
            for d, v in got[k].items():
                if d[0] == "col":
                    mask = oks[k] if whole[d[1]] else jnp.logical_and(
                        oks[k], got[k][("mask", d[1])] != 0)
                    arrays[d[1]] = (v, mask)
            for d, v in got[k].items():
                if d[0] == "lookup":
                    src = lk_src[d[1]][0]
                    arrays[d[1]] = (v, oks[k] if whole[src] else
                                    jnp.logical_and(
                                        oks[k], got[k][("mask", src)] != 0))
        for si in lk_probe:
            src, lo = lk_src[si]
            codes, mask = arrays[src]
            t = tables[si]
            v = jnp.take(t, jnp.clip(codes.astype(jnp.int32) - lo, 0,
                                     t.shape[0] - 1))
            arrays[si] = (v, mask)

        def env(ce):
            return [arrays[i] for i in ce.inputs]
        for ce in compiled_preds:
            v, ok = ce.fn(env(ce))
            b = v if v.dtype == jnp.bool_ else (v != 0)
            valid = jnp.logical_and(valid, jnp.logical_and(b, ok))
        code = jnp.zeros(valid.shape, jnp.int32)
        for kind, ref, lo, size in key_plans:
            if kind == "row":
                c = rows[ref]
            else:
                data, ok = arrays[ref]
                c = jnp.where(ok, jnp.clip(data.astype(jnp.int32) - lo,
                                           0, size - 2), size - 1)
            code = code * jnp.int32(size) + c
        code = jnp.where(valid, code, jnp.int32(space)).ravel()
        cols = [valid.astype(jnp.int32)]
        masks = [valid]
        for ce in compiled_masks:
            _, ok = ce.fn(env(ce))
            m = jnp.logical_and(valid, ok)
            masks.append(m)
            cols.append(m.astype(jnp.int32))
        for ce, lo, hi in compiled_parts:
            v, ok = ce.fn(env(ce))
            m = jnp.logical_and(valid, ok).astype(jnp.int32)
            cols.extend(ops_agg.int_limbs(v.astype(jnp.int32), m, lo, hi,
                                          limb_w))
        extremes = []
        for ce, func, mi in compiled_mm:
            v, ok = ce.fn(env(ce))
            info = np.iinfo(np.int32)
            ident = info.max if func == "min" else info.min
            extremes.append((func, ident, jnp.where(
                jnp.logical_and(masks[mi], ok), v.astype(jnp.int32),
                jnp.int32(ident)).ravel()))
        cols = [c.ravel() for c in cols]
        pos = jnp.arange(code.size, dtype=jnp.int32)
        return _reduce(code, cols, pos, extremes, space, rungs)

    consts = tuple(ce.consts for ce in compiled_preds + compiled_masks +
                   [c for c, _, _ in compiled_parts + compiled_mm])
    cache_key = ("join_chain", tuple(parents), tuple(col_needed),
                 tuple(sorted(whole.items())),
                 tuple(sorted(decode.items())), p_pad,
                 tuple((k, tuple(slots[k]), small_rel[k],
                        packs[k][0].shape) for k in pack_rels),
                 tuple(key_plans), space, rungs, limb_w,
                 tuple((si, lk_src[si], len(lookups[si][2]))
                       for si in lk_needed),
                 tuple(_expr_key(p) for p in dev_preds),
                 layout.signature(), consts)
    prof = getattr(ctx, "profile", None)
    jitted = obs_device.compiled("join_chain", cache_key, lambda: program,
                                 profile=prof, node_key=id(node))
    flat = [prow] + [edge_dev[k] for k in probe_edges]
    for ji in probe_cols:
        flat.extend([env_cols[ji].data, env_cols[ji].mask])
    flat.extend(packs[k][0] for k in pack_rels)
    flat.extend(lk_dev[si] for si in lk_probe)
    referenced = set()
    for e in preds + list(group) + [s.arg for s in aggs if s.arg is not None]:
        referenced |= {x.index for x in e.walk()
                       if isinstance(x, BoundColumn)}
    for r in rels[1:]:
        referenced |= set(r.pkeys) | {r.offset + b for b in r.bkeys}

    from .plan import check_cancel
    check_cancel()
    metrics.DEVICE_OFFLOADS.add()
    metrics.DEVICE_JOINS_FUSED.add(len(rels) - 1)
    metrics.DEVICE_JOIN_BYTES.add(work_bytes(rels, referenced))
    results = obs_device.dispatch(jitted, flat, profile=prof,
                                  node_key=id(node))
    with stage("device_finalize"):
        return _finalize(node, rels, group, aggs, agg_plans, layout,
                         results, group_mode, probe, limb_w, rungs)


def _pack_tag(d: tuple, col_name, edge_of, lookups, rels) -> tuple:
    """What one packed column holds, for the cache key."""
    if d[0] in ("col", "mask"):
        return (d[0], col_name(d[1]))
    if d[0] == "edge":
        pcols, bcols, _ = edge_of[d[1]]
        return ("edge", rels[d[1]].side.pub, tuple(pcols), tuple(bcols))
    ji, lo, table, _t, ekey = lookups[d[1]]
    return ("lookup", col_name(ji), ekey, len(table))


def _pack_column(d: tuple, r: _Rel, col_name, edge_of,
                 lookups) -> np.ndarray:
    """One column of a build relation's packed matrix, host side."""
    from ..columnar.device import _narrow_exact
    if d[0] == "edge":
        return edge_of[d[1]][2]
    if d[0] == "lookup":
        ji, lo, table, _t, _k = lookups[d[1]]
        codes = r.side.host_col(col_name(ji)).data.astype(np.int64)
        return table[np.clip(codes - lo, 0, len(table) - 1)]
    col = r.side.host_col(col_name(d[1]))
    if d[0] == "mask":
        return col.valid_mask().astype(np.int32)
    return _narrow_exact(col.data, len(col.data)).astype(np.int32)


#: build relations up to this many rows are read with element gathers
#: (8.4M reads on one TPU v5e: 0.8 ms from 25 rows, 49 ms from
#: 16,384, 73 from 200,000; a row gather of 1-8 columns is 22-52 ms
#: whatever the table's size)
SMALL_TABLE = 1 << 10

_FANIN: "OrderedDict[tuple, int]" = OrderedDict()


def _group_rows(rels, key_plans, edge_of, probe) -> int:
    """Most probe rows one group can hold: the probe's live rows, or,
    where a group key is a relation's row id, the most probe rows that
    reach one row of it (7 lines an order in TPC-H). Sizes the limbs."""
    bound = probe.n_live
    for kind, k, _lo, _size in key_plans:
        if kind != "row":
            continue
        path = []
        j = k
        while j != 0:
            path.append(j)
            j = rels[j].parent
        key = tuple((rels[j].side.pub, tuple(edge_of[j][0]),
                     tuple(edge_of[j][1])) for j in path) + \
            (probe.pub, probe.zrange)
        if key not in _FANIN:
            rows = None
            for j in reversed(path):
                idx = edge_of[j][2]
                rows = idx if rows is None else np.where(
                    rows >= 0, idx[np.clip(rows, 0, None)], -1)
            if probe.zrange is not None:
                rows = rows[probe.zrange[0]:probe.zrange[1]]
            live = rows[rows >= 0]
            while len(_FANIN) >= 64:
                _FANIN.popitem(last=False)
            _FANIN[key] = int(np.bincount(live).max()) if len(live) else 1
        bound = min(bound, _FANIN[key])
    return max(bound, 1)


#: the many-group reduction's ladder: a rung holds p_pad // d surviving
#: rows (2^23 probe rows: 65,536, 262,144 and 1,048,576), compacted
#: before their scatter; past the last rung every row is scattered
COMPACT_RUNGS = (128, 32, 8)


def _rungs(p_pad: int, space: int) -> tuple:
    """The ladder's sizes for a program of `p_pad` probe rows, ascending;
    none where the groups are few enough for masked reductions."""
    if space + 1 <= ops_agg.SMALL_SPACE:
        return ()
    return tuple(sorted({max(p_pad // d, 1) for d in COMPACT_RUNGS}))


def _reduce(code, cols, pos, extremes, space: int, rungs: tuple):
    """Per-group sums of the int32 columns, the largest probe position
    and the min/max columns. A few groups (the sentinel slot included):
    ops/agg.py's masked reductions. Many: a 1-D scatter a column (58 ms
    for 8.4M rows into 1.5M slots, against 724 for five columns as one
    (rows, 5) scatter, on one TPU v5e) of the rows that survive, gathered
    first into the smallest rung of `rungs` that holds them (a scatter
    costs its updates, not its slots); past the last rung, of every
    row."""
    import jax
    import jax.numpy as jnp
    if space + 1 <= ops_agg.SMALL_SPACE:
        acc, mm = ops_agg.group_reduce_masked(
            code, cols, [("max", -1, pos)] + list(extremes), space)
        return (acc, mm[0]) + tuple(mm[1:])
    funcs = [(f, ident) for f, ident, _v in extremes]

    def scatter(code, cols, pos, vals):
        acc = jnp.stack([jnp.zeros(space + 1, jnp.int32).at[code].add(c)
                         [:space] for c in cols], axis=1)
        rep = jnp.full(space + 1, -1, jnp.int32).at[code].max(pos)[:space]
        mm = []
        for (f, ident), v in zip(funcs, vals):
            t = jnp.full(space + 1, ident, jnp.int32)
            mm.append((t.at[code].min(v) if f == "min"
                       else t.at[code].max(v))[:space])
        return (acc, rep) + tuple(mm)
    blocks = _survivor_blocks(code != space)

    def compacted(b: int):
        def run(code, cols, pos, vals):
            at, live = _compact_positions(blocks, b)
            # one row gather of the stacked columns: a 1-D gather a column
            # costs 6x as much at 262,144 rows (one TPU v5e)
            got = jnp.take(jnp.stack([code] + cols + vals, axis=1), at,
                           axis=0)
            fills = [space] + [0] * len(cols) + [i for _f, i in funcs]
            got = [jnp.where(live, got[:, j], jnp.int32(f))
                   for j, f in enumerate(fills)]
            n = 1 + len(cols)
            return scatter(got[0], got[1:n], jnp.where(live, at, -1),
                           got[n:])
        return run
    total = jnp.sum(blocks[1])
    rung = sum((total > b).astype(jnp.int32) for b in rungs)
    return jax.lax.switch(rung, [compacted(b) for b in rungs] + [scatter],
                          code, cols, pos, [v for _f, _i, v in extremes])


def _survivor_blocks(valid):
    """The survivors of flat `valid` (a multiple of 128 rows) in blocks
    of 128 rows: each block's flags as four 32-bit words (int32), its
    survivors, and the survivors before it."""
    import jax
    import jax.numpy as jnp
    bits = valid.reshape(-1, 4, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=2,
                    dtype=jnp.uint32)
    counts = jax.lax.population_count(words).astype(jnp.int32).sum(axis=1)
    return (jax.lax.bitcast_convert_type(words, jnp.int32), counts,
            jnp.cumsum(counts) - counts)


def _running_max(x):
    """Running max of a flat array of non-negative values, by doubling
    shifts: XLA's cummax of 2^20 values takes 40 s to compile for a TPU
    v5e."""
    import jax.numpy as jnp
    step = 1
    while step < x.shape[0]:
        x = jnp.maximum(x, jnp.pad(x[:-step], (step, 0)))
        step *= 2
    return x


def _compact_positions(blocks, b: int):
    """(row of each of the first b survivors, in row order; which of the
    b slots hold one). A block that holds survivors marks the slot its
    first one lands in (a scatter of one update a block), a running max
    hands every slot its block, and the slot's rank inside the block
    picks the word, then the bit."""
    import jax
    import jax.numpy as jnp
    words, counts, before = blocks
    mark = jnp.zeros(b, jnp.int32).at[jnp.where(counts > 0, before, b)] \
        .max(jnp.arange(counts.shape[0], dtype=jnp.int32), mode="drop")
    blk = _running_max(mark)
    meta = jnp.take(jnp.concatenate([before[:, None], words], axis=1), blk,
                    axis=0)
    slot = jnp.arange(b, dtype=jnp.int32)
    k = slot - meta[:, 0]
    ws = [jax.lax.bitcast_convert_type(meta[:, 1 + q], jnp.uint32)
          for q in range(4)]
    word, lane = ws[0], jnp.zeros(b, jnp.int32)
    for q in range(1, 4):
        c = jax.lax.population_count(word).astype(jnp.int32)
        go = k >= c
        k = jnp.where(go, k - c, k)
        word = jnp.where(go, ws[q], word)
        lane = jnp.where(go, 32 * q, lane)
    bit = jnp.zeros(b, jnp.int32)
    for w in (16, 8, 4, 2, 1):
        c = jax.lax.population_count(
            (word >> bit.astype(jnp.uint32)) & jnp.uint32((1 << w) - 1)
        ).astype(jnp.int32)
        go = k >= c
        k = jnp.where(go, k - c, k)
        bit = jnp.where(go, bit + w, bit)
    live = slot < jnp.sum(counts)
    return jnp.where(live, blk * 128 + lane + bit, 0), live


class _Layout:
    """The accumulator's columns: 0 counts joined rows; then non-NULL
    counts of arguments that can be NULL; then each summed part's limbs
    (as many as its range needs at the program's limb width; two's
    complement plus a negative count where it can be negative). Min/max
    run beside it."""

    def __init__(self, never_null):
        self.never_null = never_null
        self.masks: list = []          # exprs whose NULLs need a count
        self.parts: list = []          # (expr, lo, hi)
        self.mm: list = []             # (expr, func, mask slot)
        self._mask_at: dict = {}
        self._part_at: dict = {}

    def _nullable(self, e: BoundExpr) -> bool:
        for x in e.walk():
            if isinstance(x, BoundLiteral) and x.value is None:
                return True
            if isinstance(x, BoundCase) and x.else_ is None:
                return True
            if isinstance(x, BoundColumn) and not self.never_null(x.index):
                return True
        return False

    def _mask_slot(self, e: BoundExpr) -> int:
        """Index into the program's masks: 0 the joined rows, k the k-th
        nullable argument's."""
        if not self._nullable(e):
            return 0
        key = _expr_key(e)
        if key not in self._mask_at:
            self.masks.append(e)
            self._mask_at[key] = len(self.masks)
        return self._mask_at[key]

    def count_of(self, e: BoundExpr) -> int:
        """Accumulator column of the argument's non-NULL row count."""
        return self._mask_slot(e)

    def minmax(self, e: BoundExpr, func: str) -> int:
        self.mm.append((e, func, self._mask_slot(e)))
        return len(self.mm) - 1

    def part(self, e: BoundExpr, lo: int, hi: int) -> int:
        key = _expr_key(e)
        if key not in self._part_at:
            self.parts.append((e, lo, hi))
            self._part_at[key] = len(self.parts) - 1
        return self._part_at[key]

    @staticmethod
    def limb_width(group_rows: int) -> int:
        """The widest limb whose per-group int32 sum is exact: 8 bits for
        2^23 rows, 28 for TPC-H's 7 lines an order."""
        w = 1
        while w < 31 and ((1 << (w + 1)) - 1) * group_rows < (1 << 31):
            w += 1
        return w

    def _start(self, k: int, w: int) -> int:
        at = 1 + len(self.masks)
        for _e, lo, hi in self.parts[:k]:
            n, neg = ops_agg.limb_count(lo, hi, w)
            at += n + neg
        return at

    def combine(self, acc: np.ndarray, k: int, w: int) -> np.ndarray:
        """Exact int64 sums of part k from its w-bit limb columns."""
        at = self._start(k, w)
        _e, lo, hi = self.parts[k]
        n, neg = ops_agg.limb_count(lo, hi, w)
        return ops_agg.combine_limbs(
            [acc[:, at + c] for c in range(n + neg)], lo, hi, w)

    def signature(self) -> tuple:
        return (tuple(_expr_key(e) for e in self.masks),
                tuple((_expr_key(e), lo, hi) for e, lo, hi in self.parts),
                tuple((_expr_key(e), f, m) for e, f, m in self.mm))


def _finalize(node, rels, group, aggs, agg_plans, layout, results,
              group_mode, probe, limb_w: int, rungs: tuple) -> Batch:
    acc = np.asarray(results[0])
    rep = np.asarray(results[1])
    mm = [np.asarray(x) for x in results[2:]]
    counts = acc[:, 0].astype(np.int64)
    if rungs:
        if int(counts.sum()) <= rungs[-1]:
            metrics.DEVICE_CHAIN_COMPACTED.add()
        else:
            metrics.DEVICE_CHAIN_SCATTERED_FULL.add()
    present = np.flatnonzero(counts > 0) if group_mode else np.asarray([0])
    cols: list[Column] = []
    if group_mode:
        rows = {0: rep[present].astype(np.int64) + probe.lo}
        for k in range(1, len(rels)):
            r = rels[k]
            idx = join_index(rels[r.parent].side, r.side,
                             [_name(rels, i) for i in r.pkeys],
                             [r.scan.columns[i] for i in r.bkeys])
            rows[k] = idx[rows[r.parent]].astype(np.int64)
        needed = {x.index for g in group for x in g.walk()
                  if isinstance(x, BoundColumn)}
        placeholders = []
        for r in rels:
            for ci, name in enumerate(r.scan.columns):
                ji = r.offset + ci
                if ji in needed:
                    k = _owner(rels, ji)
                    placeholders.append(
                        r.side.host_col(name).take(rows[k]))
                else:
                    placeholders.append(Column(dt.INT, np.zeros(
                        len(present), np.int32)))
        batch = Batch([f"c{i}" for i in range(len(placeholders))],
                      placeholders)
        cols = [g.eval(batch) for g in group]
    part_sums = [layout.combine(acc[present], k, limb_w)
                 for k in range(len(layout.parts))]
    out_aggs = []
    for s, (mode, vcnt, what) in zip(aggs, agg_plans):
        cnt = acc[present, vcnt].astype(np.int64)
        if mode in ("star", "count"):
            out_aggs.append(_agg_col(s, cnt, None, group_mode))
        elif mode in ("min", "max"):
            out_aggs.append(_agg_col(s, cnt, mm[what][present]
                                     .astype(np.int64), group_mode))
        else:
            total = None
            for k, w in what:
                part = part_sums[k] * w if w != 1 else part_sums[k]
                if w != 1 and np.any(np.abs(part_sums[k].astype(
                        np.float64)) * w >= 9.2e18):
                    from .. import errors
                    raise errors.SqlError("22003", "numeric field overflow")
                total = part if total is None else total + part
            out_aggs.append(_agg_col(s, cnt, total, group_mode))
    cols += out_aggs
    out = Batch(list(node.names), cols)
    if group_mode and out.num_rows > 1:
        # the host aggregate's order: keys ascending, NULL last
        keys = []
        for c in reversed(cols[:len(group)]):
            _, ranks = np.unique(c.data, return_inverse=True)
            nulls = ~c.valid_mask()
            keys.append(np.where(nulls, 0, ranks))
            keys.append(nulls.astype(np.int8))
        out = out.take(np.lexsort(tuple(keys)))
    return out


def _name(rels, ji: int) -> str:
    r = rels[_owner(rels, ji)]
    return r.scan.columns[ji - r.offset]


def _agg_col(s, cnt, vals, group_mode: bool) -> Column:
    t = s.type
    if s.func in ("count_star", "count"):
        return Column(dt.BIGINT, cnt) if group_mode else \
            Column.from_pylist([int(cnt[0])], t)
    empty = cnt == 0
    if s.func == "avg":
        with np.errstate(invalid="ignore", divide="ignore"):
            data = np.where(empty, 0.0, vals / np.maximum(cnt, 1))
    else:
        data = np.where(empty, 0, vals).astype(t.np_dtype)
    if not group_mode:
        return Column.from_pylist(
            [None if empty[0] else data[0].item()], t)
    return Column(t, data, ~empty if empty.any() else None)
