"""Subqueries in a WHERE flattened into joins (decorrelation).

Reference analog: DuckDB's flattening of dependent joins (Neumann and
Kemper, "Unnesting Arbitrary Queries", BTW 2015). A WHERE conjunct that
holds a subquery expression is planned as a join of the FROM list's plan
with the subquery's, whenever the subquery's correlation is a
conjunction of equalities to outer expressions (its join keys) plus
other conjuncts:

- A correlated `EXISTS` is a semi join and `NOT EXISTS` an anti join,
  on at least one equality key; a correlated conjunct that is not an
  equality (TPC-H Q21's `l2.l_suppkey <> l1.l_suppkey`) is the join's
  residual.
- `x IN (q)` is a semi join on `x`; `x NOT IN (q)` a mark join whose
  BOOL column is SQL's `x IN (q)`, NULLs included, under `NOT`.
- A correlated scalar aggregate is the aggregate grouped by the
  correlation's inner keys, left-joined back on them: a key with no rows
  reads NULL, and a `count` reads 0 there (COALESCE: the "count bug").
  Compared with an `avg` (or a literal times one), the comparison is
  cross-multiplied in integers: `q < 0.2 * avg(x)` is `q * count(x) * 10
  < 2 * sum(x)`, as exact as PostgreSQL's `numeric`.
- An uncorrelated scalar subquery compared with an integer or DECIMAL
  column through an `avg` is the exact threshold, computed at plan time
  (`c > S / N` is `c > floor(S / N)` for an integer-scaled `c`). Any
  other uncorrelated scalar subquery, and an uncorrelated `EXISTS`, is
  the binder's: computed once a statement (sql/binder.py).

What cannot be flattened (a correlation in the select list, a correlated
GROUP BY or LIMIT, a non-aggregate scalar that may return two rows, a
correlated `NOT IN` with a residual, an `EXISTS` correlated by no
equality) stays the binder's per-row substitution.
`SubqueriesFlattened` / `SubqueriesPerRow` count each correlated
subquery expression (and each exact average threshold) by the path it
took; a host execution of a flattened join ticks `HostFlattenedJoins`.
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction

from .. import errors
from ..columnar import dtypes as dt
from ..utils import metrics
from . import ast
from .binder import AGG_FUNCS, Scope, ScopeColumn
from .expr import BoundColumn, BoundFunc, BoundLiteral, kleene_and

_SUBQUERY_NODES = (ast.Subquery, ast.InSubquery, ast.Exists,
                   ast.ArraySubquery)
_CMP = {"=", "<>", "!=", "<", "<=", ">", ">="}
_MIRROR = {"=": "=", "<>": "<>", "!=": "!=", "<": ">", "<=": ">=",
           ">": "<", ">=": "<="}


def _children(e):
    """The expressions directly under `e` (not into a subquery's query)."""
    if isinstance(e, _SUBQUERY_NODES):
        op = getattr(e, "operand", None)
        return [op] if op is not None else []
    out = []
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, ast.Expr):
            out.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, ast.Expr):
                    out.append(x)
                elif isinstance(x, tuple):
                    out.extend(y for y in x if isinstance(y, ast.Expr))
    return out


def has_subquery(e) -> bool:
    if isinstance(e, _SUBQUERY_NODES):
        return True
    return any(has_subquery(c) for c in _children(e))


def _replace(e, fn):
    """Copy of `e` with each node for which fn returns non-None replaced
    (not descending into a replaced node or a subquery's query)."""
    got = fn(e)
    if got is not None:
        return got
    if isinstance(e, _SUBQUERY_NODES) or not dataclasses.is_dataclass(e):
        return e
    changes = {}
    for f in dataclasses.fields(e):
        v = getattr(e, f.name)
        if isinstance(v, ast.Expr):
            changes[f.name] = _replace(v, fn)
        elif isinstance(v, list):
            changes[f.name] = [
                _replace(x, fn) if isinstance(x, ast.Expr) else
                tuple(_replace(y, fn) if isinstance(y, ast.Expr) else y
                      for y in x) if isinstance(x, tuple) else x
                for x in v]
    return dataclasses.replace(e, **changes) if changes else e


def bound_and(preds: list):
    """The AND of bound predicates."""
    if len(preds) == 1:
        return preds[0]
    return BoundFunc("and", list(preds), dt.BOOL,
                     lambda cols, b: kleene_and(cols))


def conjoin(parts: list):
    """The AND of AST conjuncts; None for none."""
    if not parts:
        return None
    return parts[0] if len(parts) == 1 else ast.Logical("AND", list(parts))


class _Nested(Scope):
    """Inner scope first, then the outer one (SQL's scoping)."""

    def __init__(self, inner: Scope, outer: Scope):
        super().__init__(inner.columns)
        self.outer = outer

    def resolve(self, parts):
        try:
            return super().resolve(parts)
        except errors.SqlError as e:
            if e.sqlstate != errors.UNDEFINED_COLUMN:
                raise
            return self.outer.resolve(parts)


class _NoFlatten(Exception):
    """The subquery keeps the per-row path."""


def _binds(planner, scope: Scope, e) -> bool:
    try:
        planner._binder(scope).bind(e)
        return True
    except errors.SqlError as err:
        if err.sqlstate in (errors.UNDEFINED_COLUMN, errors.UNDEFINED_TABLE):
            return False
        raise


def _agg_calls(e, out: list):
    """Aggregate calls of a select item, outermost first."""
    if isinstance(e, ast.FuncCall) and (e.name in AGG_FUNCS or e.star):
        out.append(e)
        return
    for c in _children(e):
        _agg_calls(c, out)


def _avg_form(item):
    """(avg call, literal multiplier as (num, den)) of `avg(a)`,
    `c * avg(a)` or `avg(a) * c`, else None."""
    def is_avg(x):
        return isinstance(x, ast.FuncCall) and x.name == "avg" and \
            not x.distinct and len(x.args) == 1 and x.filter is None

    if is_avg(item):
        return item, (1, 1)
    if isinstance(item, ast.BinaryOp) and item.op == "*":
        for a, b in ((item.left, item.right), (item.right, item.left)):
            if is_avg(a) and isinstance(b, ast.Literal):
                ex = dt.exact_decimal(b.value)
                if ex is not None:
                    return a, (ex[0], 10 ** ex[1])
    return None


class Flattener:
    """Flattens one SELECT's subquery conjuncts into joins over `plan`."""

    def __init__(self, planner):
        self.planner = planner

    def apply(self, plan, scope: Scope, conjuncts: list):
        """(plan, scope, bound predicates, conjuncts left to the binder)."""
        preds, rest = [], []
        for c in conjuncts:
            try:
                plan, scope, pred = self._one(plan, scope, c)
            except _NoFlatten:
                rest.append(c)
                continue
            except errors.SqlError:
                rest.append(c)
                continue
            metrics.SUBQUERIES_FLATTENED.add()
            if pred is not None:
                preds.append(pred)
        return plan, scope, preds, rest

    # -- one conjunct ----------------------------------------------------------

    def _one(self, plan, scope, c):
        neg, e = False, c
        while isinstance(e, ast.UnaryOp) and e.op == "NOT":
            neg, e = not neg, e.operand
        if isinstance(e, ast.Exists):
            kind = "anti" if e.negated != neg else "semi"
            return self._semi(plan, scope, e.query, kind, None)
        if isinstance(e, ast.InSubquery) and not has_subquery(e.operand):
            kind = "mark" if e.negated != neg else "semi"
            return self._semi(plan, scope, e.query, kind, e.operand)
        if neg or not _only_scalars(c):
            raise _NoFlatten()
        return self._scalars(plan, scope, c)

    def _inner(self, q, outer: Scope):
        """(inner FROM scope, local conjuncts, equality keys as (inner
        ast, outer ast), other correlated conjuncts) of subquery q."""
        p = self.planner
        if not isinstance(q, ast.Select) or q.from_ is None or q.ctes or \
                q.distinct_on or q.limit is not None or \
                q.offset is not None:
            raise _NoFlatten()
        from .planner import _from_list, _split_conjuncts
        cols, at = [], 0
        for ref in _from_list(q.from_):
            _, sc = p._plan_from(ref)
            cols += [ScopeColumn(x.table, x.name, x.type, x.index + at,
                                 x.hidden) for x in sc.columns]
            at += len(sc.columns)
        inner = Scope(cols)
        both = _Nested(inner, outer)
        local, keys, other = [], [], []
        for cj in (_split_conjuncts(q.where) if q.where is not None else []):
            if has_subquery(cj) or _binds(p, inner, cj):
                local.append(cj)
                continue
            if not _binds(p, both, cj):
                raise _NoFlatten()
            if isinstance(cj, ast.BinaryOp) and cj.op == "=":
                li, ri = _binds(p, inner, cj.left), _binds(p, inner, cj.right)
                if li != ri:
                    ie, oe = (cj.left, cj.right) if li else \
                        (cj.right, cj.left)
                    if _binds(p, outer, oe) and \
                            not _refs_inner(p, inner, oe):
                        keys.append((ie, oe))
                        continue
            other.append(cj)
        return inner, local, keys, other

    def _semi(self, plan, scope, q, kind, operand):
        from ..exec.plan import JoinNode
        p = self.planner
        inner, local, keys, other = self._inner(q, scope)
        if kind == "mark" and other:
            raise _NoFlatten()
        if operand is None and not keys:
            # uncorrelated: the binder's, once a statement; correlated by
            # no equality: every pair of rows, so per row
            raise _NoFlatten()
        for it in q.items:
            if isinstance(it.expr, ast.Star):
                continue
            calls: list = []
            _agg_calls(it.expr, calls)
            if calls and not q.group_by:
                raise _NoFlatten()       # one row, whatever matches
            if operand is None:
                # EXISTS drops its select list, which must still bind
                p._binder(_Nested(inner, scope)).bind(it.expr)
        if operand is not None:
            if len(q.items) != 1 or isinstance(q.items[0].expr, ast.Star):
                raise _NoFlatten()
            if (keys or other) and (q.group_by or q.having is not None):
                raise _NoFlatten()
            value = q.items[0].expr
        elif q.group_by or q.having is not None:
            raise _NoFlatten()
        # the residual's inner columns ride the inner select's items
        res_cols: list = []
        for cj in other:
            for ref in _colrefs(cj):
                if _binds(p, inner, ref):
                    sc = inner.resolve(ref.parts)
                    if all(sc is not r[1] for r in res_cols):
                        res_cols.append((ref, sc))
        items = [ie for ie, _ in keys]
        if operand is not None:
            items.append(value)
        items += [ref for ref, _ in res_cols]
        sub = dataclasses.replace(
            q, items=[ast.SelectItem(x, f"#k{i}") for i, x in
                      enumerate(items)],
            where=conjoin(local), order_by=[], distinct=False)
        right = p.plan_select(sub)
        ob = p._binder(scope)
        lkeys = [ob.bind(oe) for _, oe in keys]
        rkeys = [BoundColumn(i, right.types[i], f"#k{i}")
                 for i in range(len(keys))]
        n = len(scope.columns)
        names = list(plan.names)
        types = list(plan.types)
        mark = None
        if operand is not None:
            x = ob.bind(operand)
            v = BoundColumn(len(keys), right.types[len(keys)], "#v")
            if kind == "semi":
                lkeys.append(x)
                rkeys.append(v)
            else:
                mark = (x, v)
        residual = None
        if other:
            base = len(keys) + (operand is not None)
            rscope = Scope([ScopeColumn(sc.table, sc.name, sc.type,
                                        n + base + j)
                            for j, (_, sc) in enumerate(res_cols)])
            rb = p._binder(_Nested(rscope, scope))
            residual = bound_and([rb.bind(cj) for cj in other])
        pred = None
        if kind == "mark":
            name = f"#sq{n}"
            names.append(name)
            types.append(dt.BOOL)
            scope = Scope(scope.columns + [ScopeColumn(None, name, dt.BOOL,
                                                       n, True)])
            col = BoundColumn(n, dt.BOOL, name)
            pred = BoundFunc("not", [col], dt.BOOL, _not_impl)
        plan = JoinNode(kind, plan, right, lkeys, rkeys, residual, names,
                        types, mark=mark, flattened=True)
        return plan, scope, pred

    def _scalars(self, plan, scope, c):
        """Each correlated scalar subquery of conjunct c as a left-joined
        grouped aggregate; an uncorrelated one is the binder's literal,
        or the exact threshold of a comparison with an avg."""
        from ..exec.plan import JoinNode
        p = self.planner
        subs: list = []
        _collect(c, subs)
        if not subs:
            raise _NoFlatten()
        cmp = c if isinstance(c, ast.BinaryOp) and c.op in _CMP else None
        repl: dict = {}
        for s in subs:
            q = s.query
            if not isinstance(q, ast.Select) or len(q.items) != 1 or \
                    isinstance(q.items[0].expr, ast.Star):
                raise _NoFlatten()
            other_side = None
            if cmp is not None and len(subs) == 1:
                other_side = cmp.right if cmp.left is s else \
                    cmp.left if cmp.right is s else None
            _, local, keys, other = self._inner(q, scope)
            if not keys and not other:
                pred = self._threshold(scope, c, q, other_side) \
                    if other_side is not None else None
                if pred is not None:
                    return plan, scope, pred
                continue                  # the binder's literal
            if other or q.group_by or q.having is not None or q.distinct:
                raise _NoFlatten()
            item = q.items[0].expr
            calls: list = []
            _agg_calls(item, calls)
            if not calls:
                raise _NoFlatten()      # may return two rows: 21000
            avg = _avg_form(item) if other_side is not None else None
            if avg is not None:
                call, (num, den) = avg
                calls = [ast.FuncCall("sum", list(call.args)),
                         ast.FuncCall("count", list(call.args))]
            n = len(scope.columns)
            names = [f"#sq{n}_{i}" for i in range(len(calls))]
            sub = dataclasses.replace(
                q, items=[ast.SelectItem(ie, f"#k{i}")
                          for i, (ie, _) in enumerate(keys)] +
                [ast.SelectItem(call, nm) for call, nm in zip(calls, names)],
                where=conjoin(local), group_by=[ie for ie, _ in keys],
                order_by=[])
            right = p.plan_select(sub)
            ob = p._binder(scope)
            lkeys = [ob.bind(oe) for _, oe in keys]
            rkeys = [BoundColumn(i, right.types[i], f"#k{i}")
                     for i in range(len(keys))]
            cols = list(scope.columns)
            for i in range(len(right.types)):
                nm = f"#sq{n}_k{i}" if i < len(keys) else \
                    names[i - len(keys)]
                cols.append(ScopeColumn(None, nm, right.types[i], n + i,
                                        True))
            plan = JoinNode("left", plan, right, lkeys, rkeys, None,
                            list(plan.names) + [c.name for c in cols[n:]],
                            list(plan.types) + list(right.types),
                            flattened=True)
            scope = Scope(cols)
            if avg is not None:
                s_ref = ast.ColumnRef([names[0]])
                n_ref = ast.ColumnRef([names[1]])
                op = cmp.op if cmp.left is other_side else _MIRROR[cmp.op]
                lhs = ast.BinaryOp("*", ast.BinaryOp("*", other_side, n_ref),
                                   ast.Literal(den))
                rhs = ast.BinaryOp("*", ast.Literal(num), s_ref)
                return plan, scope, p._binder(scope).bind(
                    ast.BinaryOp(op, lhs, rhs))
            by_call = {id(call): nm for call, nm in zip(calls, names)}

            def agg_ref(x, _m=by_call):
                nm = _m.get(id(x))
                if nm is None:
                    return None
                ref = ast.ColumnRef([nm])
                if x.name == "count" or x.star:
                    return ast.FuncCall("coalesce", [ref, ast.Literal(0)])
                return ref
            repl[id(s)] = _replace(item, agg_ref)
        if not repl:
            raise _NoFlatten()
        out = _replace(c, lambda x: repl.get(id(x)))
        return plan, scope, p._binder(scope).bind(out)

    def _threshold(self, scope, c, q, other_side):
        """Conjunct c, `other_side op [k *] avg(x)` over an uncorrelated
        subquery q, as the exact integer comparison of an integer or
        DECIMAL other_side with a literal; None for any other form."""
        from ..exec.plan import ExecContext
        p = self.planner
        avg = _avg_form(q.items[0].expr)
        if avg is None or q.group_by or q.having is not None or q.distinct:
            return None
        lhs = p._binder(scope).bind(other_side)
        if not (lhs.type.is_decimal or lhs.type.is_integer):
            return None
        call, (num, den) = avg
        sub = dataclasses.replace(q, items=[
            ast.SelectItem(ast.FuncCall("sum", list(call.args))),
            ast.SelectItem(ast.FuncCall("count", list(call.args)))])
        r = p.plan_select(sub)
        b = r.execute(ExecContext())
        total, cnt = b.columns[0].decode(0), b.columns[1].decode(0)
        s_scale = r.types[0].scale if r.types[0].is_decimal else 0
        op = c.op if c.left is other_side else _MIRROR[c.op]
        return _exact_threshold(p, lhs, op, total, cnt, s_scale, num, den)


def _exact_threshold(p, lhs, op: str, total, cnt, s_scale: int,
                     num: int, den: int):
    """`lhs op num / den * total / cnt` (total at scale s_scale) as an
    integer comparison of lhs's scaled value with a literal. In a WHERE
    conjunct a NULL and a false keep the same rows."""
    if total is None or not cnt:
        return BoundLiteral(False, dt.BOOL)
    scale = lhs.type.scale if lhs.type.is_decimal else 0
    f = Fraction(num * total * 10 ** scale, den * cnt * 10 ** s_scale)
    lo = f.numerator // f.denominator
    hi = -((-f.numerator) // f.denominator)
    lit_t = dt.decimal_of(dt.MAX_DECIMAL_PRECISION, scale) \
        if lhs.type.is_decimal else dt.BIGINT
    op = "<>" if op == "!=" else op
    if op in ("=", "<>") and f.denominator != 1:
        # no value lhs can take equals S / N
        if op == "=":
            return BoundLiteral(False, dt.BOOL)
        return BoundFunc("is_not_null", [lhs], dt.BOOL, _is_not_null)
    bound = {"=": lo, "<>": lo, ">": lo, "<=": lo, ">=": hi, "<": hi}[op]
    return p._binder(Scope([]))._call("op" + op,
                                      [lhs, BoundLiteral(bound, lit_t)])


def _is_not_null(cols, batch):
    from ..columnar.column import Column
    return Column(dt.BOOL, cols[0].valid_mask())


def _not_impl(cols, batch):
    from ..columnar.column import Column
    c = cols[0]
    return Column(dt.BOOL, ~c.data.astype(bool), c.validity)


def _only_scalars(e) -> bool:
    if isinstance(e, ast.Subquery):
        return True
    if isinstance(e, _SUBQUERY_NODES):
        return False
    return all(_only_scalars(c) for c in _children(e))


def _collect(e, out: list):
    if isinstance(e, ast.Subquery):
        out.append(e)
        return
    for c in _children(e):
        _collect(c, out)


def _colrefs(e) -> list:
    if isinstance(e, ast.ColumnRef):
        return [e]
    return [r for c in _children(e) for r in _colrefs(c)]


def _refs_inner(p, inner: Scope, e) -> bool:
    return any(_binds(p, inner, r) for r in _colrefs(e))


def flatten(planner, plan, scope: Scope, conjuncts: list):
    return Flattener(planner).apply(plan, scope, conjuncts)

