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

A subquery the planner flattened (sql/decorrelate.py: a semi or anti
join, or an aggregate grouped by the correlation keys and left-joined
back) is a chain of its own inside the same program: its rows reduce
into the row space of the relation of this chain its keys are a unique
key of (a reduction edge: TPC-H Q4's lineitem rows into one count per
orders row, Q17's into a sum and a count per part row, Q21's into a
least and a greatest supplier per order), through the join row index
from its probe to that relation, with the ladder above; this chain reads
the result at each of its rows beside the relation's matrix, as a
column (a left-joined aggregate) or a flag (semi / anti: a count, a
HAVING over the reduced aggregates, or `min != x or max != x` for a
correlated `<>`). A semi join over a key the subquery's one relation is
unique on reads that relation's surviving rows through a join index
instead (a lookup: Q20's `ps_partkey IN (SELECT p_partkey ...)`). One
relation with a flattened subquery is the chain program's (Q4, Q22),
since device_agg has no join row index. A statement that returns rows
(Q2, Q20) compacts its survivors on the device (`ROWS_RUNG`); the host
gathers what its projection reads at them.

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
from ..sql.expr import (AggSpec, BoundCase, BoundColumn, BoundExpr,
                        BoundFunc, BoundLiteral)
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
    """(rels, post predicates, group exprs, agg specs, reductions) of an
    Aggregate over Filter* [Project] Filter* over a chain (`_chain_of`),
    or None. Expressions come back over the joined schema (a derived
    table's projection substituted in)."""
    group = list(node.group_exprs)
    aggs = [copy.copy(s) for s in node.aggs]
    post: list = []
    child = node.child
    projected = False
    from .plan import FilterNode, ProjectNode
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
    shape = _chain_of(child)
    if shape is None:
        return None
    rels, more, reds = shape
    return rels, post + more, group, aggs, reds


def _chain_of(plan):
    """(rels, predicates, reductions) of Filter* over flattened subquery
    joins (`_reduction`) over a left-deep inner key-join chain of
    Filter*(Scan), or None. Predicates come back over the plan's schema:
    the relations' columns, then the columns each left-joined aggregate
    appends."""
    from .device_pipeline import _unwrap_side
    from .plan import FilterNode, JoinNode
    post: list = []
    reds: list = []

    def unwind(plan):
        if isinstance(plan, FilterNode) and isinstance(plan.child,
                                                       (JoinNode,
                                                        FilterNode)):
            post.extend(_split_and(plan.pred))
            return unwind(plan.child)
        if not isinstance(plan, JoinNode):
            side = _unwrap_side(plan)
            return None if side is None else [_Rel(side[0], side[1], 0)]
        if plan.kind in ("semi", "anti", "left"):
            left = unwind(plan.left)
            red = _reduction(plan) if left is not None else None
            if red is None:
                return None
            reds.append(red)
            return left
        if plan.kind != "inner" or not plan.left_keys or \
                plan.residual is not None or plan.merge_pairs or reds:
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

    rels = unwind(plan)
    if rels is None or len(rels) > MAX_RELATIONS:
        return None
    return rels, post, reds


class _Red:
    """A flattened subquery the chain program runs (sql/decorrelate.py's
    semi, anti or left-joined aggregate): a sub-chain whose rows reduce
    into the row space of one relation of the chain it hangs off (a
    reduction edge), or, for a semi join over a key the sub-chain's one
    relation is unique on, that relation's filter read through a join
    index (a lookup)."""

    def __init__(self, kind, sub, lkeys, skeys, aggs, having, outs,
                 residual):
        self.kind = kind          # "semi" | "anti" | "agg"
        self.sub = sub            # _chain_of of the sub-plan
        self.lkeys = lkeys        # the outer key columns (joined index)
        self.skeys = skeys        # the sub-chain's key columns
        self.aggs = aggs          # AggSpecs over the sub-chain's schema
        self.having = having      # predicates over (keys, aggs) slots
        self.outs = outs          # agg: plan column -> output slot
        self.residual = residual  # (sub, outer) columns of `sub <> outer`


def _reduction(join):
    """A `_Red` of a semi / anti join, or of a left join to an aggregate
    grouped by its keys, whose right side is Project? Filter* Aggregate?
    over a chain; None for any other shape."""
    from .plan import AggregateNode, FilterNode, ProjectNode
    n = len(join.left.names)
    right = join.right
    proj = None
    if isinstance(right, ProjectNode):
        proj = []
        for e in right.exprs:
            # a DECIMAL aggregate reads back as its scaled integer
            if isinstance(e, BoundFunc) and e.name == "decimal_of":
                e = e.args[0]
            if not isinstance(e, BoundColumn):
                return None
            proj.append(e.index)
        right = right.child
    having: list = []
    while isinstance(right, FilterNode) and isinstance(
            right.child, (FilterNode, AggregateNode)):
        having.extend(_split_and(right.pred))
        right = right.child
    agg = right if isinstance(right, AggregateNode) else None
    if having and agg is None:
        return None
    if agg is not None:
        right = agg.child
    sub = _chain_of(right)
    if sub is None or join.kind == "mark" or \
            (join.kind == "left") != (agg is not None and not having):
        return None

    def below(j: int) -> int:
        return proj[j] if proj is not None else j
    skeys = []
    for lk, rk in zip(join.left_keys, join.right_keys):
        if not (isinstance(lk, BoundColumn) and isinstance(rk, BoundColumn)
                and lk.index < n):
            return None
        slot = below(rk.index)
        if agg is not None:
            if slot >= len(agg.group_exprs):
                return None
            g = agg.group_exprs[slot]
            if not isinstance(g, BoundColumn) or slot != len(skeys):
                return None
            slot = g.index
        skeys.append(slot)
    if not skeys or (agg is not None and
                     len(agg.group_exprs) != len(skeys)):
        return None
    residual = None
    if join.residual is not None:
        r = join.residual
        if agg is not None or not isinstance(r, BoundFunc) or \
                r.name not in ("op<>", "op!=") or len(r.args) != 2:
            return None
        a, b = r.args
        if not (isinstance(a, BoundColumn) and isinstance(b, BoundColumn)):
            return None
        if a.index < n:
            a, b = b, a
        if b.index >= n or a.index < n:
            return None
        residual = (below(a.index - n), b.index)
    outs = {}
    if join.kind == "left":
        for j in range(len(join.right.names)):
            outs[n + j] = below(j)
    return _Red("agg" if join.kind == "left" else join.kind, sub,
                [lk.index for lk in join.left_keys], skeys,
                list(agg.aggs) if agg is not None else [], having, outs,
                residual)


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
    three relations or more, or one with a flattened subquery? A
    two-table join may be the pair-count program's too, and one table is
    device_agg's."""
    shape = _recognize(node)
    return shape is not None and (len(shape[0]) > 2 or bool(shape[4]))


# -- join row indexes: key -> row, per pair of publications --------------------

_INDEX: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
_INDEX_MAX_BYTES = 1 << 30
_index_bytes = 0
_index_lock = threading.Lock()
#: (child publication, key columns) found not unique
_NOT_UNIQUE: set = set()


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
        if (ck[1], ck[3]) in _NOT_UNIQUE:
            raise NotCompilable("build side not unique on its key",
                                "build_not_unique")
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
            _not_unique(ck)
        idx = np.where(pcode >= 0, table[np.clip(pcode, 0, None)], -1)
    else:
        order = live[np.argsort(bcode[live], kind="stable")]
        sorted_codes = bcode[order]
        if len(sorted_codes) > 1 and \
                (np.diff(sorted_codes) == 0).any():
            _not_unique(ck)
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


def _not_unique(ck: tuple):
    with _index_lock:
        if len(_NOT_UNIQUE) > 256:
            _NOT_UNIQUE.clear()
        _NOT_UNIQUE.add((ck[1], ck[3]))
    raise NotCompilable("build side not unique on its key",
                        "build_not_unique")


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
    rels, post, group, aggs, reds = shape
    if len(rels) == 1 and not reds:
        return None, False           # device_agg's
    from .shard import shard_count
    if len(rels) == 2 and not reds and shard_count(settings) > 1:
        # the pair-count program runs a probe's shards side by side
        return None, False
    if settings.get("serene_device") == "auto" and \
            _rows_of(rels, reds) < settings.get("serene_device_min_rows"):
        return None, False
    try:
        with stage("device_prepare", op="chain"):
            return _run(node, rels, post, group, aggs, reds, ctx), False
    except (NotCompilable, DeviceNarrowingError) as e:
        reason = getattr(e, "reason", "not_compilable")
        log.debug("device", f"join chain fell back: {e}")
        if len(rels) < 3 and not reds:
            return None, False       # the pair-count program's
        _note_decline(reason, ctx, node)
        return None, True


def _rows_of(rels: list, reds: list) -> int:
    """The chain's probe rows, or, with flattened subqueries, the most
    rows any of its chains' probes holds (0 where one cannot say)."""
    try:
        n = rels[0].scan.provider.row_count()
        for red in reds:
            n = max(n, _rows_of(red.sub[0], red.sub[2]))
        return n
    except NotImplementedError:
        return 0


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
        # a group key: each value's rank among the values taken
        _, ranks = np.unique(np.asarray(vals.to_pylist(), dtype=object)
                             .astype(str), return_inverse=True)
        return int(lo), np.where(vals.valid_mask(), ranks, 0).astype(
            np.int32), dt.INT
    table = np.where(vals.valid_mask(), data, 0).astype(np.int32)
    t = dt.BOOL if vals.type.id is dt.TypeId.BOOL else dt.INT
    return int(lo), table, t


def _pow2(n: int, floor: int = 1024) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


def _run(node, rels, post, group, aggs, reds, ctx) -> Batch:
    chain = _Chain(rels, post, group, aggs, reds, ctx, "group")
    results = chain.dispatch(node, ctx)
    with stage("device_finalize"):
        return _finalize(node, rels, group, aggs, chain.agg_plans,
                         chain.layout, results, bool(group), chain.probe,
                         chain.limb_w, chain.rungs)


#: survivors a statement that returns rows (not groups) compacts on the
#: device; past it the statement runs on the host
ROWS_RUNG = 1 << 14


def try_device_chain_rows(node, ctx) -> Optional[Batch]:
    """A Project over a chain with a flattened subquery (TPC-H Q2, Q20)
    as ONE program: its surviving rows are compacted on the device, and
    the host gathers what the projection reads at them. None where the
    program did not run (a decline is noted)."""
    from .device_pipeline import _note_decline, fused_enabled
    settings = ctx.settings
    if settings.get("serene_device") == "cpu" or not fused_enabled(settings):
        return None
    shape = _chain_of(node.child)
    if shape is None or not shape[2]:
        return None
    rels, post, reds = shape
    if settings.get("serene_device") == "auto" and \
            _rows_of(rels, reds) < settings.get("serene_device_min_rows"):
        return None
    try:
        with stage("device_prepare", op="chain"):
            reads = {x.index for e in node.exprs for x in e.walk()
                     if isinstance(x, BoundColumn)}
            chain = _Chain(rels, post, [], [], reds, ctx, "rows",
                           reads=reads)
            out = chain.dispatch(node, ctx)
            total, at = int(out[0]), out[1]
            vals = {ji: (out[2 + 2 * i], out[3 + 2 * i])
                    for i, ji in enumerate(chain.synth_reads)}
            if total > ROWS_RUNG:
                raise NotCompilable("surviving rows past the rung",
                                    "rows_rung")
    except (NotCompilable, DeviceNarrowingError) as e:
        log.debug("device", f"join chain rows fell back: {e}")
        _note_decline(getattr(e, "reason", "not_compilable"), ctx, node)
        return None
    with stage("device_finalize"):
        at = np.asarray(at)[:total].astype(np.int64)
        rows = {0: at + chain.probe.lo}
        for k in range(1, len(rels)):
            r = rels[k]
            idx = chain.edge_of[k][2]
            rows[k] = idx[rows[r.parent]].astype(np.int64)
        cols = []
        width = chain.width
        for ji in range(chain.vis):
            if ji in reads and ji < width:
                k = _owner(rels, ji)
                r = rels[k]
                cols.append(r.side.host_col(
                    r.scan.columns[ji - r.offset]).take(rows[k]))
            elif ji in reads:
                v, ok = vals[ji]
                v = np.asarray(v)[:total].astype(np.int64)
                ok = np.asarray(ok)[:total].astype(bool)
                t = node.child.types[ji]
                cols.append(Column(t, v.astype(t.np_dtype),
                                   None if ok.all() else ok))
            else:
                cols.append(Column(dt.INT, np.zeros(total, np.int32)))
        batch = Batch([f"c{i}" for i in range(len(cols))], cols)
        return Batch(list(node.names), [e.eval(batch) for e in node.exprs])


class _Chain:
    """One chain's part of ONE program: its relations, predicates,
    aggregates and flattened subqueries (`_Red`), each a `_Chain` of its
    own whose result stays in HBM. `mode`: "group" (an aggregate's
    groups), "rows" (the surviving rows, compacted), "reduce" (the rows
    reduced into `target`'s row space: a count, and each aggregate with
    its validity, per target row), "mask" (the probe's surviving rows,
    for a lookup)."""

    def __init__(self, rels, post, group, aggs, reds, ctx, mode,
                 target=None, reads=()):
        import jax.numpy as jnp

        from .device_pipeline import DEVICE_CACHE, _Side, _col_stats
        self.mode, self.rels, self.reds = mode, rels, reds
        for s in aggs:
            if s.func not in _AGG_FUNCS:
                raise NotCompilable(s.func, "agg_func")
            if s.distinct or s.filter is not None or s.order_by:
                raise NotCompilable("DISTINCT / FILTER / ORDER BY aggregate",
                                    "agg_modifier")
        for k, r in enumerate(rels):
            # zone maps shrink the probe's upload; build sides are read
            # whole, as is a probe whose row space a lookup reads
            r.side = _Side(r.scan, r.preds if k == 0 and mode != "mask"
                           else [], ctx)
        probe = self.probe = rels[0].side
        if probe.n_live > MAX_ROWS_EXACT:
            raise NotCompilable("probe rows past the exact-scatter bound",
                                "probe_rows")
        types: list = [t for r in rels for t in r.scan.types]
        width = self.width = len(types)
        vis = width
        for red in reds:
            if red.outs:
                vis = max(vis, max(red.outs) + 1)
        self.vis = vis
        self.next_si = vis

        def new_si() -> int:
            self.next_si += 1
            return self.next_si - 1

        def host_col_of(ji: int) -> Column:
            r = rels[_owner(rels, ji)]
            return r.side.host_col(r.scan.columns[ji - r.offset])

        def col_name(ji: int) -> str:
            r = rels[_owner(rels, ji)]
            return r.scan.columns[ji - r.offset]

        preds = [_shift(p, r.offset) for r in rels for p in r.preds] + post
        # single-column functions the device has no form for: a host
        # table over the column's domain, read by code (`lookups`:
        # synthetic joined index -> (source index, lo, table, type, key))
        lookups: dict[int, tuple] = {}
        # what a flattened subquery gives the chain, per synthetic joined
        # index: (sub, output, (lo, hi))
        synth: dict[int, tuple] = {}

        def rewrite(e: BoundExpr, key: bool = False) -> BoundExpr:
            if isinstance(e, BoundFunc) and (
                    e.name in _LOOKUP_FUNCS or e.type.id is dt.TypeId.BOOL
                    and any(isinstance(x, BoundFunc) and x.name in
                            _LOOKUP_FUNCS for x in e.walk())):
                # the whole one-column predicate (`substring(c_phone FROM
                # 1 FOR 2) IN (...)`) where it holds such a function; a
                # text-valued one only as a group key, by its value's rank
                src = {x.index for x in e.walk() if isinstance(x, BoundColumn)}
                if len(src) == 1 and next(iter(src)) < width and \
                        (key or not e.type.is_string):
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
            si = new_si()
            lookups[si] = (ji, lo, table, t, key[1:])
            return BoundColumn(si, t, f"#lookup{si}")

        def never_null(ji: int) -> bool:
            if ji in synth:
                return False
            if ji >= width:
                ji = lookups[ji][0]
            return _col_stats(rels[_owner(rels, ji)].side, col_name(ji))[0]

        stats_cache: dict[int, tuple] = {}

        def col_bounds(ji: int) -> tuple[int, int]:
            if ji in synth:
                return synth[ji][2]
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

        # -- join row indexes (the target's, for a reduction) ----------------
        edge_of = self.edge_of = {}  # child k -> (pcols, bcols, host index)
        for k in range(1, len(rels)):
            r = rels[k]
            pcols = [col_name(i) for i in r.pkeys]
            bcols = [r.scan.columns[i] for i in r.bkeys]
            edge_of[k] = (pcols, bcols, join_index(rels[r.parent].side,
                                                   r.side, pcols, bcols))
        p_pad = _pow2(probe.n_live)
        self.referenced: set = set()
        for e in preds + list(group) + [s.arg for s in aggs
                                        if s.arg is not None]:
            self.referenced |= {x.index for x in e.walk()
                                if isinstance(x, BoundColumn)
                                and x.index < width}
        for r in rels[1:]:
            self.referenced |= set(r.pkeys) | {r.offset + b for b in r.bkeys}
        self.referenced |= {ji for ji in reads if ji < width}

        def probe_tiles(host_idx, tag):
            """A host int32 array over the probe's rows as device tiles
            of its zone range (-1 past its rows)."""
            def build(h=host_idx, zr=probe.zrange):
                h = h if zr is None else h[zr[0]:zr[1]]
                out = np.full(p_pad, -1, np.int32)
                out[:len(h)] = h
                return jnp.asarray(out.reshape(-1, LANES))
            return DEVICE_CACHE.array(probe.pub, "__join_index__",
                                      tag + (probe.zrange, p_pad), build)

        self.fanin = 1
        self.t_dev = None
        if mode == "reduce":
            tside, tcols, skeys = target
            if any(_owner(rels, ji) != 0 for ji in skeys):
                raise NotCompilable("reduction key off the sub-chain's probe",
                                    "reduce_key")
            snames = [col_name(ji) for ji in skeys]
            t_idx = join_index(probe, tside, snames, tcols)
            self.referenced |= set(skeys)
            self.space = tside.nrows
            self.t_dev = probe_tiles(t_idx, (tside.pub, tuple(snames),
                                             tuple(tcols)))
            self.fanin = _fanin((probe.pub, tside.pub, tuple(snames),
                                 tuple(tcols), probe.zrange),
                                lambda: t_idx, probe.zrange)

        # -- flattened subqueries: each a chain of its own -------------------
        self.subs: list = []          # (red, chain, how, gather)
        flags: list = []              # (sub i, kind, cnt si, having, res)
        for red in reds:
            with stage("device_prepare", op="chain_reduce"):
                sub, how, gather = self._sub_chain(red, rels, col_name, ctx,
                                                   probe_tiles)
            i = len(self.subs)
            self.subs.append((red, sub, how, gather))
            self.referenced |= set(red.lkeys)
            if how[0] == "rel":
                k = how[1]
                self.referenced |= {rels[k].offset + rels[k].scan.columns
                                    .index(c) for c in how[2]}
            if red.kind == "agg":
                for ji, slot in red.outs.items():
                    if slot >= len(red.skeys):
                        j = slot - len(red.skeys)
                        synth[ji] = (i, j, sub.out_bounds[j])
                continue
            if how[0] == "lookup":
                flags.append((i, red.kind, None, [], None))
                continue
            # `min != x or max != x` needs no count: an empty row reads
            # min > max
            cnt = None
            if red.residual is None:
                cnt = new_si()
                synth[cnt] = (i, "count", (0, sub.fanin))
            having = []
            if red.having:
                remap = {}
                for j in range(len(red.aggs)):
                    si = new_si()
                    synth[si] = (i, j, sub.out_bounds[j])
                    remap[len(red.skeys) + j] = si
                for h in red.having:
                    h = copy.deepcopy(h)
                    for x in h.walk():
                        if isinstance(x, BoundColumn):
                            if x.index not in remap:
                                raise NotCompilable("HAVING over a key",
                                                    "reduce_having")
                            x.index = remap[x.index]
                    having.append(h)
            res = None
            if red.residual is not None:
                mn, mx = new_si(), new_si()
                j = len(red.aggs)
                synth[mn] = (i, j, sub.out_bounds[j])
                synth[mx] = (i, j + 1, sub.out_bounds[j + 1])
                res = (mn, mx, red.residual[1])
                self.referenced.add(red.residual[1])
            flags.append((i, red.kind, cnt, having, res))

        used = set(reads)
        for e in preds + list(group) + [s.arg for s in aggs
                                        if s.arg is not None]:
            used |= {x.index for x in e.walk() if isinstance(x, BoundColumn)}
        if any(ji >= width and ji not in synth for ji in used):
            raise NotCompilable("a read of a subquery's key", "reduce_key")
        dev_preds = [rewrite(p) for p in preds]
        dev_group = [rewrite(g, key=True) for g in group]
        dev_args = [rewrite(s.arg) if s.arg is not None else None
                    for s in aggs]
        for e in dev_group:
            if any(isinstance(x, BoundColumn) and x.index in synth
                   for x in e.walk()):
                raise NotCompilable("group key over a subquery's value",
                                    "group_key")
        all_types = types

        # dictionaries of the string columns any expression reads
        dictionaries: dict[int, np.ndarray] = {}
        for e in dev_preds + dev_group + [a for a in dev_args
                                          if a is not None]:
            for x in e.walk():
                if isinstance(x, BoundColumn) and x.index < width and \
                        x.type.is_string and x.index not in dictionaries:
                    d = host_col_of(x.index).dictionary
                    if d is not None:
                        dictionaries[x.index] = d

        compiled_preds = []
        for p in dev_preds:
            expr_bounds(p, col_bounds)
            compiled_preds.append(compile_expr(p, all_types, dictionaries))
        compiled_flags = []
        for i, kind, cnt, having, res in flags:
            hs = []
            for h in having:
                expr_bounds(h, col_bounds)
                hs.append(compile_expr(h, all_types, dictionaries))
            compiled_flags.append((i, kind, cnt, hs, res))

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
        row_of: dict = {}                # group expr standing for a row id
        if mode == "reduce":
            key_plans.append(("target", None, 0, self.space))
        for gi, g in enumerate(dev_group):
            if isinstance(g, BoundColumn) and g.index < width:
                for k, r in enumerate(rels[1:], 1):
                    if len(r.bkeys) == 1 and g.index in (
                            r.pkeys[0], r.offset + r.bkeys[0]):
                        row_of[gi] = k
                        break
        # a row under another key's row adds nothing to the code space
        # (Q18's customer under its order)
        for k in dict.fromkeys(row_of.values()):
            if not any(j != k and k in below(j) for j in row_of.values()):
                key_plans.append(("row", k, 0, rels[k].side.nrows))
                determined |= below(k)
        row_keys = set(row_of)
        covered = set()
        for k in determined:
            covered |= set(range(rels[k].offset,
                                 rels[k].offset + rels[k].width))
        for gi, g in enumerate(dev_group):
            if gi in row_keys:
                continue
            reads_g = {x.index for x in g.walk() if isinstance(x, BoundColumn)}
            srcs = {lookups[i][0] if i in lookups else i for i in reads_g}
            if srcs and srcs <= covered:
                continue                 # a function of a determined row
            if not isinstance(g, BoundColumn):
                raise NotCompilable("computed group key", "group_key")
            if g.index in lookups:
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
                    raise NotCompilable("group key range too wide",
                                        "group_key")
                key_plans.append(("int", g.index, lo, hi - lo + 2))
            else:
                raise NotCompilable(f"group key of type {g.type}",
                                    "group_key")
        space = 1
        for kp in key_plans:
            space *= kp[3]
            if space > MAX_GROUPS:
                raise NotCompilable("group code space too large",
                                    "group_space")

        # -- aggregate plans -----------------------------------------------------
        # one accumulator column layout for all aggregates: col 0 counts
        # the joined rows; a count of an argument's non-NULL rows only
        # where it can be NULL; each distinct summed part once (SUM and
        # AVG of one argument share it), as the limbs its range needs
        layout = self.layout = _Layout(never_null)
        agg_plans: list[tuple] = []      # (mode, vcnt col, parts | mm slot)
        out_bounds: list = []
        limb_rows = self.fanin if mode == "reduce" else None
        for s, a in zip(aggs, dev_args):
            if s.func == "count_star":
                agg_plans.append(("star", 0, None))
                out_bounds.append((0, self.fanin))
                continue
            if a.type.is_string and s.func != "count":
                raise NotCompilable(f"{s.func} over strings", "agg_type")
            if a.type.is_float:
                raise NotCompilable(f"{s.func} over {a.type}", "agg_type")
            vcnt = layout.count_of(a)
            if s.func == "count":
                agg_plans.append(("count", vcnt, None))
                out_bounds.append((0, self.fanin))
                continue
            if s.func in ("min", "max"):
                out_bounds.append(expr_bounds(a, col_bounds))
                agg_plans.append((s.func, vcnt, layout.minmax(a, s.func)))
                continue
            try:
                lo, hi = expr_bounds(a, col_bounds)
                parts = [(a, 1, lo, hi)]
            except NotCompilable:
                if mode == "reduce":
                    raise
                parts = wide_parts(a, col_bounds)
            if mode == "reduce":
                if s.func != "sum" or ops_agg.limb_count(
                        lo, hi, layout.limb_width(limb_rows)) != (1, 0):
                    raise NotCompilable("reduction past one int32 limb",
                                        "reduce_sum")
                out_bounds.append((min(0, lo * self.fanin),
                                   max(0, hi * self.fanin)))
            agg_plans.append(("sum", vcnt, [(layout.part(p, lo, hi), w)
                                            for p, w, lo, hi in parts]))
        self.agg_plans, self.out_bounds = agg_plans, out_bounds
        # a reduction of only min / max over never-NULL values that never
        # reach the identities scatters no count: a target row with no
        # rows reads the identity (Q21's least and greatest supplier)
        info = np.iinfo(np.int32)
        no_count = mode == "reduce" and bool(aggs) and \
            space + 1 > ops_agg.SMALL_SPACE and not layout.masks and \
            all(pl[0] in ("min", "max") and info.min < b[0] and
                b[1] < info.max for pl, b in zip(agg_plans, out_bounds))
        compiled_parts = [(compile_expr(e, all_types, dictionaries), lo, hi)
                          for e, lo, hi in layout.parts]
        compiled_masks = [compile_expr(e, all_types, dictionaries)
                          for e in layout.masks]
        compiled_mm = [(compile_expr(e, all_types, dictionaries), f, m)
                       for e, f, m in layout.mm]

        # -- the device environment ----------------------------------------------
        needed: set[int] = set()
        for ce in compiled_preds + [c for c, _, _ in compiled_parts] + \
                compiled_masks + [c for c, _, _ in compiled_mm] + \
                [c for f in compiled_flags for c in f[3]]:
            needed.update(ce.inputs)
        for _i, _kind, _cnt, _hs, res in compiled_flags:
            if res is not None:
                needed.add(res[2])
        for kind, ref, _lo, _size in key_plans:
            if kind not in ("row", "target"):
                needed.add(ref)
        needed -= set(synth)
        lk_needed = sorted(i for i in needed if i in lookups)
        for si in lk_needed:
            # a lookup over the probe gathers its table by the column's
            # codes on the device; one over a build relation is applied
            # on the host and packed with the relation's rows
            if _owner(rels, lookups[si][0]) == 0:
                needed.add(lookups[si][0])
        if mode == "rows":
            needed |= {ji for ji in reads if ji in synth}
        col_needed = sorted(i for i in needed if i < width)
        owner = {ji: _owner(rels, ji) for ji in col_needed + [
            lookups[si][0] for si in lk_needed]}
        whole = {ji: never_null(ji) for ji in owner}
        parents = [r.parent for r in rels]
        # the probe's columns: device tiles of its zone range, as every tier
        probe_cols = [ji for ji in col_needed if owner[ji] == 0]
        env_cols = {ji: DEVICE_CACHE.column(
            probe.provider, probe.pub, col_name(ji),
            (lambda n=col_name(ji): probe.host_col(n)), probe.zrange,
            pad=p_pad) for ji in probe_cols}
        decode = {ji: (env_cols[ji].scheme, env_cols[ji].offset)
                  for ji in probe_cols}
        lk_probe = [si for si in lk_needed if owner[lookups[si][0]] == 0]
        # a probe edge: the child's row of each probe row, probe-aligned
        probe_edges = [k for k in range(1, len(rels)) if parents[k] == 0]
        edge_dev = {}
        for k in probe_edges:
            pcols, bcols, host_idx = edge_of[k]
            edge_dev[k] = probe_tiles(host_idx, (rels[k].side.pub,
                                                 tuple(pcols), tuple(bcols)))
        # a build relation: ONE int32 matrix of its rows holding what the
        # statement reads of it — its columns (decoded), the masks of
        # those that hold NULLs, its children's row indexes (the next hop
        # of the chain) and the host tables of lookups over its columns —
        # so that a probe row reads it with one row gather
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
            packs[k] = (DEVICE_CACHE.array(r.side.pub, "__packed__", tag,
                                           build),
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
        slots = {k: dict(packs[k][1]) for k in pack_rels}
        small_rel = {k: packs[k][2] for k in pack_rels}
        # a flattened subquery's values over a build relation's rows ride
        # as more columns of its matrix: one row gather reads them all
        red_at: dict = {}            # rel -> [(synthetic index, masked)]
        for ji, (i, what, _b) in synth.items():
            k = self.subs[i][2][1]
            if k:
                red_at.setdefault(k, []).append((ji, what != "count"))
        for k, jis in red_at.items():
            at = slots.setdefault(k, {})
            for ji, masked in jis:
                at[("red", ji)] = len(at)
                if masked:
                    at[("redok", ji)] = len(at)
            small_rel.setdefault(k, rels[k].side.nrows <= SMALL_TABLE)
        mat_rels = sorted(slots)
        red_pad = {k: (rels[k].side.nrows,
                       _pow2(rels[k].side.nrows, floor=8)) for k in red_at}
        if mode == "reduce":
            self.limb_w = layout.limb_width(self.fanin)
        else:
            self.limb_w = layout.limb_width(_group_rows(rels, key_plans,
                                                        edge_of, probe))
        limb_w = self.limb_w
        rungs = self.rungs = _rungs(p_pad, space)
        probe_lo, probe_n = probe.lo, probe.n_live
        subs = self.subs
        synth_reads = self.synth_reads = sorted(ji for ji in reads
                                                if ji in synth)

        def body(it):
            rowmask = next(it)
            edge_in = {k: next(it) for k in probe_edges}
            raw = {ji: (next(it), next(it)) for ji in probe_cols}
            packed = {k: next(it) for k in pack_rels}
            tables = {si: next(it) for si in lk_probe}
            t_in = next(it) if mode == "reduce" else None
            sub_out = []
            for _red, sub, how, _g in subs:
                out = sub.body(it)
                gix = next(it) if how[0] == "lookup" else None
                sub_out.append((out, gix))
            for k, jis in red_at.items():
                cols_ = []
                for ji, masked in jis:
                    i, what, _b = synth[ji]
                    out = sub_out[i][0]
                    v, ok = (out[0], None) if what == "count" \
                        else out[1][what]
                    cols_.append(v.astype(jnp.int32))
                    if masked:
                        cols_.append(ok.astype(jnp.int32))
                n, n_pad = red_pad[k]
                extra = jnp.pad(jnp.stack(cols_, axis=1),
                                ((0, n_pad - n), (0, 0)))
                packed[k] = extra if k not in packed else \
                    jnp.concatenate([packed[k], extra], axis=1)
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
                if k not in slots:
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
                                            oks[k],
                                            got[k][("mask", src)] != 0))
                    elif d[0] == "red":
                        ok = got[k].get(("redok", d[1]))
                        arrays[d[1]] = (v, oks[k] if ok is None else
                                        jnp.logical_and(oks[k], ok != 0))
            for si in lk_probe:
                src, lo = lk_src[si]
                codes, mask = arrays[src]
                t = tables[si]
                v = jnp.take(t, jnp.clip(codes.astype(jnp.int32) - lo, 0,
                                         t.shape[0] - 1))
                arrays[si] = (v, mask)

            def at_probe(x):
                """A per-row array of this chain's probe, as its tiles."""
                seg = jnp.pad(x[probe_lo:probe_lo + probe_n],
                              (0, p_pad - probe_n))
                return seg.reshape(-1, LANES)
            for ji, (i, what, _b) in synth.items():
                if subs[i][2][1]:
                    continue             # read with its relation's matrix
                out = sub_out[i][0]
                if what == "count":
                    arrays[ji] = (at_probe(out[0]), rowmask)
                else:
                    val, vok = out[1][what]
                    arrays[ji] = (at_probe(val), jnp.logical_and(
                        rowmask, at_probe(vok)))

            def env(ce):
                return [arrays[i] for i in ce.inputs]
            for ce in compiled_preds:
                v, ok = ce.fn(env(ce))
                b = v if v.dtype == jnp.bool_ else (v != 0)
                valid = jnp.logical_and(valid, jnp.logical_and(b, ok))
            for i, kind, cnt, hs, res in compiled_flags:
                out, gix = sub_out[i]
                if gix is not None:
                    # a lookup: the sub-chain's probe row of each row
                    n = out.shape[0]
                    flag = jnp.logical_and(
                        gix >= 0, jnp.take(out, jnp.clip(gix, 0, n - 1)))
                elif res is not None:
                    mn, mx, o = res
                    ov, ook = arrays[o]
                    other = jnp.logical_or(arrays[mn][0] != ov,
                                           arrays[mx][0] != ov)
                    flag = jnp.logical_and(
                        ook, jnp.logical_and(arrays[mn][1], other))
                else:
                    flag = arrays[cnt][0] > 0
                    for ce in hs:
                        v, ok = ce.fn(env(ce))
                        b = v if v.dtype == jnp.bool_ else (v != 0)
                        flag = jnp.logical_and(flag, jnp.logical_and(b, ok))
                valid = jnp.logical_and(valid, flag if kind == "semi"
                                        else jnp.logical_not(flag))
            if mode == "mask":
                return valid.ravel()
            if mode == "rows":
                blocks = _survivor_blocks(valid.ravel())
                at, live = _compact_positions(blocks, ROWS_RUNG)
                outs = [jnp.sum(blocks[1]), at]
                for ji in synth_reads:
                    v, ok = arrays[ji]
                    outs += [jnp.take(v.ravel(), at),
                             jnp.logical_and(live, jnp.take(ok.ravel(), at))]
                return tuple(outs)
            code = jnp.zeros(valid.shape, jnp.int32)
            for kind, ref, lo, size in key_plans:
                if kind == "target":
                    valid = jnp.logical_and(valid, t_in >= 0)
                    c = jnp.maximum(t_in, 0)
                elif kind == "row":
                    c = rows[ref]
                else:
                    data, ok = arrays[ref]
                    c = jnp.where(ok, jnp.clip(data.astype(jnp.int32) - lo,
                                               0, size - 2), size - 1)
                code = code * jnp.int32(size) + c
            code = jnp.where(valid, code, jnp.int32(space)).ravel()
            cols = [] if no_count else [valid.astype(jnp.int32)]
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
            red = _reduce(code, cols, pos, extremes, space, rungs,
                          rep=mode != "reduce")
            if mode != "reduce":
                return red
            acc, mm = red[0], red[2:]
            return None if acc is None else acc[:, 0], [
                _reduced(plan, acc, mm, layout, limb_w)
                for plan in agg_plans]

        self.body = body
        consts = tuple(ce.consts for ce in compiled_preds + compiled_masks +
                       [c for c, _, _ in compiled_parts + compiled_mm] +
                       [c for f in compiled_flags for c in f[3]])
        self.key = (mode, tuple(parents), tuple(col_needed),
                    tuple(sorted(whole.items())),
                    tuple(sorted(decode.items())), p_pad, probe_lo,
                    probe_n,
                    tuple((k, tuple(slots[k]), small_rel[k],
                           packs[k][0].shape if k in packs else None)
                          for k in mat_rels),
                    tuple(key_plans), space, rungs, limb_w,
                    tuple((si, lk_src[si], len(lookups[si][2]))
                          for si in lk_needed),
                    tuple(_expr_key(p) for p in dev_preds),
                    layout.signature(), consts, no_count,
                    tuple(sorted((ji, v[0], v[1]) for ji, v in
                                 synth.items())),
                    tuple((i, kind, cnt, res) for i, kind, cnt, _h, res
                          in compiled_flags), tuple(synth_reads),
                    tuple((how[:2], sub.key) for _r, sub, how, _g in subs))
        self.flat = [prow] + [edge_dev[k] for k in probe_edges]
        for ji in probe_cols:
            self.flat.extend([env_cols[ji].data, env_cols[ji].mask])
        self.flat.extend(packs[k][0] for k in pack_rels)
        self.flat.extend(lk_dev[si] for si in lk_probe)
        if mode == "reduce":
            self.flat.append(self.t_dev)
        for _red, sub, how, gather in subs:
            self.flat.extend(sub.flat)
            if gather is not None:
                self.flat.append(gather)

    def _sub_chain(self, red, rels, col_name, ctx, probe_tiles):
        """(the sub-chain of a flattened subquery, how this chain reads
        it, a lookup's join index tiles): reduced into the relation of
        this chain its keys are a unique key of (("rel", k, key
        columns)), else, for a semi / anti join over a key the
        sub-chain's one relation is unique on, its probe's surviving rows
        read through a join index (("lookup",))."""
        srels, spost, sreds = red.sub
        aggs = list(red.aggs)
        if red.residual is not None:
            si = red.residual[0]
            r = srels[_owner(srels, si)]
            t = r.scan.types[si - r.offset]
            col = BoundColumn(si, t, r.scan.columns[si - r.offset])
            aggs += [AggSpec("min", col, False, t),
                     AggSpec("max", col, False, t)]
        # a relation a key edge reaches is unique on that key; else the
        # one that owns the keys, if it is unique on them
        cands = []
        if len(red.lkeys) == 1:
            for k, r in enumerate(rels[1:], 1):
                if r.pkeys == red.lkeys:
                    cands.append((k, [r.scan.columns[b] for b in r.bkeys]))
        owners = {_owner(rels, ji) for ji in red.lkeys}
        if len(owners) == 1:
            k = owners.pop()
            r = rels[k]
            cands.append((k, [r.scan.columns[ji - r.offset]
                              for ji in red.lkeys]))
        err = None
        for k, tcols in cands:
            try:
                sub = _Chain(srels, spost, [], aggs, sreds, ctx, "reduce",
                             target=(rels[k].side, tcols, red.skeys))
            except NotCompilable as e:
                if getattr(e, "reason", None) != "build_not_unique":
                    raise
                err = e
                continue
            return sub, ("rel", k, tcols), None
        if red.kind == "agg" or red.having or red.residual is not None or \
                len(srels) != 1 or sreds or \
                any(_owner(rels, ji) != 0 for ji in red.lkeys):
            raise err or NotCompilable("no relation to reduce into",
                                       "reduce_target")
        sub = _Chain(srels, spost, [], [], [], ctx, "mask")
        pcols = [col_name(ji) for ji in red.lkeys]
        bcols = [srels[0].scan.columns[ji] for ji in red.skeys]
        idx = join_index(rels[0].side, sub.probe, pcols, bcols)
        self.referenced |= set(red.lkeys)
        sub.referenced |= set(red.skeys)
        return sub, ("lookup",), probe_tiles(
            idx, (sub.probe.pub, tuple(pcols), tuple(bcols)))

    def chains(self) -> list:
        out = [self]
        for _r, sub, _h, _g in self.subs:
            out += sub.chains()
        return out

    def dispatch(self, node, ctx):
        """Build (once) and run the program; count its work."""
        def program(*flat):
            return self.body(iter(flat))
        prof = getattr(ctx, "profile", None)
        jitted = obs_device.compiled("join_chain", ("join_chain",) + self.key,
                                     lambda: program, profile=prof,
                                     node_key=id(node))
        from .plan import check_cancel
        check_cancel()
        chains = self.chains()
        metrics.DEVICE_OFFLOADS.add()
        metrics.DEVICE_JOINS_FUSED.add(sum(len(c.rels) - 1 for c in chains))
        metrics.DEVICE_REDUCTIONS_FUSED.add(sum(len(c.subs) for c in chains))
        metrics.DEVICE_JOIN_BYTES.add(_chains_bytes(chains))
        return obs_device.dispatch(jitted, self.flat, profile=prof,
                                   node_key=id(node))


def _reduced(plan, acc, mm, layout, limb_w: int):
    """(per-target-row value, validity) of one aggregate of a reduction:
    NULL where the target row has no rows (a left join's NULL), and, for
    sum / min / max, where none of them holds a value."""
    mode, vcnt, what = plan
    if acc is None:
        info = np.iinfo(np.int32)
        return mm[what], mm[what] != (info.max if mode == "min" else info.min)
    rows = acc[:, 0] > 0
    if mode in ("star", "count"):
        return acc[:, vcnt], rows
    has = acc[:, vcnt] > 0
    if mode in ("min", "max"):
        return mm[what], has
    (k, _w), = what
    return acc[:, layout._start(k, limb_w)], has


def _chains_bytes(chains: list) -> int:
    """`work_bytes` over every chain of one program, each (table,
    column) once."""
    from .device_pipeline import _col_stats
    widths: dict = {}
    rows: dict = {}
    for c in chains:
        for ji in c.referenced:
            r = c.rels[_owner(c.rels, ji)]
            name = r.scan.columns[ji - r.offset]
            table = r.scan.provider.name
            rows[table] = r.side.nrows
            if (table, name) in widths:
                continue
            col = r.side.host_col(name)
            if col.type.is_string:
                d = col.dictionary
                widths[(table, name)] = _width(
                    0, max((0 if d is None else len(d)) - 1, 0))
            else:
                _, _, lo, hi = _col_stats(r.side, name)
                widths[(table, name)] = 8 if lo is None else _width(lo, hi)
    return sum(rows[t] * w for (t, _n), w in widths.items())


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


def _fanin(key: tuple, rows, zrange) -> int:
    """Most probe rows that reach one row of a relation, from `rows()`:
    that row of each probe row (-1: none); cached by `key`."""
    if key not in _FANIN:
        got = rows()
        if zrange is not None:
            got = got[zrange[0]:zrange[1]]
        live = got[got >= 0]
        while len(_FANIN) >= 64:
            _FANIN.popitem(last=False)
        _FANIN[key] = int(np.bincount(live).max()) if len(live) else 1
    return max(_FANIN[key], 1)


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

        def compose(path=path):
            rows = None
            for j in reversed(path):
                idx = edge_of[j][2]
                rows = idx if rows is None else np.where(
                    rows >= 0, idx[np.clip(rows, 0, None)], -1)
            return rows
        bound = min(bound, _fanin(key, compose, probe.zrange))
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


def _reduce(code, cols, pos, extremes, space: int, rungs: tuple,
            rep: bool = True):
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
                         [:space] for c in cols], axis=1) if cols else None
        last = jnp.full(space + 1, -1, jnp.int32).at[code].max(pos)[:space] \
            if rep else None
        mm = []
        for (f, ident), v in zip(funcs, vals):
            t = jnp.full(space + 1, ident, jnp.int32)
            mm.append((t.at[code].min(v) if f == "min"
                       else t.at[code].max(v))[:space])
        return (acc, last) + tuple(mm)
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
