"""Device (TPU) compilation of bound expressions over cached HBM columns.

This is the offload seam the reference doesn't have (SURVEY.md §5.8): the
planner's Scan→Filter→Aggregate chains compile to one jitted XLA program per
(table, query) pair — predicate, mask logic, and reduction fuse into a single
HBM pass. Strings participate as sorted-dictionary codes: literal
comparisons are resolved to code thresholds on host at compile time
(code order == string order, columnar/column.py).

Expressions evaluate to (value, valid) pairs — SQL three-valued logic on
device, matching the CPU oracle in sql/expr.py.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.device import DeviceColumn
from ..sql.expr import (BoundCase, BoundColumn, BoundExpr, BoundFunc,
                        BoundLiteral)

_NUMERIC_IDS = {dt.TypeId.BOOL, dt.TypeId.TINYINT, dt.TypeId.SMALLINT,
                dt.TypeId.INT, dt.TypeId.BIGINT, dt.TypeId.FLOAT,
                dt.TypeId.DOUBLE, dt.TypeId.TIMESTAMP, dt.TypeId.DATE,
                dt.TypeId.DECIMAL}

#: functions that pass their argument's physical value through: a
#: DECIMAL's scaled integer read as the BIGINT it is, and back
_IDENTITY_FUNCS = {"decimal_raw", "decimal_of"}


def _scale(t: dt.SqlType) -> int:
    return t.scale if t.is_decimal else 0


def _rescale(fn, k: int):
    """A compiled operand times 10^k (a DECIMAL brought to a larger
    scale; k >= 0)."""
    if k == 0:
        return fn
    f = 10 ** k

    def scaled(env, _fn=fn, _f=f):
        v, ok = _fn(env)
        return v * jnp.int32(_f), ok
    return scaled

_CMP = {"op=", "op<>", "op!=", "op<", "op<=", "op>", "op>="}
_ARITH = {"op+", "op-", "op*", "op/", "op%"}


class NotCompilable(Exception):
    """Expression/plan shape the device compiler declines — the caller
    falls back to the host path. `reason` is a short category slug for
    the per-reason decline gauges (never query text)."""

    def __init__(self, msg: str = "", reason: str = "not_compilable"):
        super().__init__(msg)
        self.reason = reason


class DeviceExpr:
    """Compiled closure producing (value, valid) given the env of device
    columns; env maps scan-column index → DeviceColumn."""

    def __init__(self, fn: Callable, inputs: list[int],
                 consts: tuple = ()):
        self.fn = fn          # (list of (data, mask)) -> (value, valid)
        self.inputs = inputs  # scan column indices, order matches fn args
        #: every DATA-DEPENDENT constant the closure bakes into its trace
        #: (today: the dictionary-code thresholds of string comparisons).
        #: A program cache key that drops the publication tuple MUST
        #: include these, or a stale executable could serve a new
        #: dictionary generation with the old thresholds.
        self.consts = consts


def compile_expr(expr: BoundExpr, col_types: list[dt.SqlType],
                 dictionaries: dict[int, np.ndarray]) -> DeviceExpr:
    """Compile a bound expression to a device closure.

    dictionaries: scan column index → sorted dictionary (VARCHAR columns),
    used to resolve string literals to code thresholds at compile time.
    Raises NotCompilable for unsupported shapes (caller falls back to CPU).
    """
    inputs: list[int] = []
    index_of: dict[int, int] = {}
    consts: list = []

    def slot(col_index: int) -> int:
        if col_index not in index_of:
            index_of[col_index] = len(inputs)
            inputs.append(col_index)
        return index_of[col_index]

    def rec(e: BoundExpr):
        if isinstance(e, BoundLiteral):
            if e.value is None:
                return lambda env: (jnp.int32(0), False)
            if isinstance(e.value, bool):
                v = jnp.int32(1 if e.value else 0)
            elif isinstance(e.value, int):
                if not (-2**31 <= e.value < 2**31):
                    raise NotCompilable("int64 literal")
                v = jnp.int32(e.value)
            elif isinstance(e.value, float):
                v = jnp.float32(e.value)
            else:
                raise NotCompilable("string literal outside comparison")
            return lambda env, _v=v: (_v, True)
        if isinstance(e, BoundColumn):
            if e.type.id not in _NUMERIC_IDS and not e.type.is_string:
                raise NotCompilable(f"column type {e.type}")
            s = slot(e.index)
            return lambda env, _s=s: env[_s]
        if isinstance(e, BoundFunc):
            return rec_func(e)
        if isinstance(e, BoundCase):
            return compile_case(e)
        raise NotCompilable(type(e).__name__)

    def compile_case(e: BoundCase):
        """First TRUE branch wins (SQL drops FALSE and NULL alike); no
        branch and no ELSE is NULL."""
        if e.type.is_string or e.type.is_float:
            raise NotCompilable(f"CASE of {e.type}")
        arms = [(rec(c), rec(v)) for c, v in e.branches]
        other = rec(e.else_) if e.else_ is not None else None

        def fn(env, _arms=arms, _other=other):
            if _other is None:
                val, ok = jnp.int32(0), jnp.bool_(False)
            else:
                val, ok = _other(env)
            for cf, vf in reversed(_arms):
                cv, cok = cf(env)
                hit = jnp.logical_and(_as_bool(cv), _m(cok))
                v, vok = vf(env)
                val = jnp.where(hit, v, val)
                ok = jnp.where(hit, _m(vok), _m(ok))
            return val, ok
        return fn

    def rec_func(e: BoundFunc):
        name = e.name
        if name in _CMP:
            return compile_compare(e)
        if name in _ARITH:
            return compile_arith(e)
        if name in ("and", "or"):
            subs = [rec(a) for a in e.args]
            is_and = name == "and"

            def fn(env, _subs=subs, _and=is_and):
                vals = [s(env) for s in _subs]
                bools = [_as_bool(v) for v, _ in vals]
                oks = [_m(ok) for _, ok in vals]
                any_null = functools.reduce(jnp.logical_or,
                                            [~ok for ok in oks])
                if _and:
                    any_false = functools.reduce(
                        jnp.logical_or,
                        [jnp.logical_and(ok, ~b) for b, ok in zip(bools, oks)])
                    return ~any_false, jnp.logical_or(any_false, ~any_null)
                any_true = functools.reduce(
                    jnp.logical_or,
                    [jnp.logical_and(ok, b) for b, ok in zip(bools, oks)])
                return any_true, jnp.logical_or(any_true, ~any_null)
            return fn
        if name == "not":
            sub = rec(e.args[0])

            def fn(env, _sub=sub):
                v, ok = _sub(env)
                return ~_as_bool(v), ok
            return fn
        if name in ("is_null", "is_not_null"):
            sub = rec(e.args[0])
            neg = name == "is_not_null"

            def fn(env, _sub=sub, _neg=neg):
                v, ok = _sub(env)
                m = _m(ok)
                return (m if _neg else ~m), True
            return fn
        if name in _IDENTITY_FUNCS:
            return rec(e.args[0])
        if name in ("int32_hi16", "int32_lo16"):
            # the halves of an int32: x = (x >> 16) * 2^16 + (x & 0xFFFF)
            sub = rec(e.args[0])
            hi = name == "int32_hi16"

            def fn(env, _sub=sub, _hi=hi):
                v, ok = _sub(env)
                v = v.astype(jnp.int32)
                return (jnp.right_shift(v, 16) if _hi
                        else jnp.bitwise_and(v, 0xFFFF)), ok
            return fn
        if name == "cast":
            sub = rec(e.args[0])
            src = e.args[0].type
            if e.type.is_decimal:
                k = e.type.scale - _scale(src)
                if k < 0 or not (src.is_decimal or src.is_integer or
                                 src.id is dt.TypeId.BOOL):
                    raise NotCompilable("cast to DECIMAL rounds")
                return _rescale(sub, k)
            if src.is_decimal:
                raise NotCompilable("cast from DECIMAL")
            if e.type.is_float:
                def fn(env, _sub=sub):
                    v, ok = _sub(env)
                    return v.astype(jnp.float32), ok
                return fn
            if e.type.is_integer:
                def fn(env, _sub=sub):
                    v, ok = _sub(env)
                    if jnp.issubdtype(v.dtype, jnp.floating):
                        # PG: round half away from zero
                        r = jnp.sign(v) * jnp.floor(jnp.abs(v) + 0.5)
                        return r.astype(jnp.int32), ok
                    return v.astype(jnp.int32), ok
                return fn
            raise NotCompilable("cast target")
        raise NotCompilable(f"function {name}")

    def compile_compare(e: BoundFunc):
        a, b = e.args
        name = e.name
        # string vs literal → code threshold
        for col, lit, flip in ((a, b, False), (b, a, True)):
            if isinstance(col, BoundColumn) and col.type.is_string and \
                    isinstance(lit, BoundLiteral) and isinstance(lit.value, str):
                d = dictionaries.get(col.index)
                if d is None:
                    raise NotCompilable("no dictionary for string column")
                return compile_str_cmp(col, lit.value, name, flip, d)
        if (isinstance(a, BoundColumn) and a.type.is_string) or \
                (isinstance(b, BoundColumn) and b.type.is_string):
            raise NotCompilable("string-string comparison on device")
        fa, fb = rec(a), rec(b)
        if a.type.is_decimal or b.type.is_decimal:
            if a.type.is_float or b.type.is_float:
                raise NotCompilable("DECIMAL against a float")
            s = max(_scale(a.type), _scale(b.type))
            fa, fb = _rescale(fa, s - _scale(a.type)), \
                _rescale(fb, s - _scale(b.type))
        op = name[2:]

        def fn(env, _fa=fa, _fb=fb, _op=op):
            (va, oka), (vb, okb) = _fa(env), _fb(env)
            va, vb = _unify(va, vb)
            if _op == "=":
                v = va == vb
            elif _op in ("<>", "!="):
                v = va != vb
            elif _op == "<":
                v = va < vb
            elif _op == "<=":
                v = va <= vb
            elif _op == ">":
                v = va > vb
            else:
                v = va >= vb
            return v, jnp.logical_and(_m(oka), _m(okb))
        return fn

    def compile_str_cmp(col: BoundColumn, s: str, name: str, flip: bool,
                        d: np.ndarray):
        """col OP 'literal' on sorted dictionary codes."""
        op = name[2:]
        if flip:  # 'literal' OP col  →  col FLIP(OP) literal
            op = {"=": "=", "<>": "<>", "!=": "<>", "<": ">", "<=": ">=",
                  ">": "<", ">=": "<="}[op]
        ds = d.astype(str)
        lo = int(np.searchsorted(ds, s, side="left"))
        hi = int(np.searchsorted(ds, s, side="right"))
        exact = lo < len(ds) and ds[lo] == s
        sl = slot(col.index)
        consts.append((col.index, op, lo, hi, exact))

        def fn(env, _sl=sl, _op=op, _lo=lo, _hi=hi, _exact=exact):
            codes, ok = env[_sl]
            if _op == "=":
                v = (codes == _lo) if _exact else jnp.zeros_like(codes, dtype=bool)
            elif _op == "<>":
                v = (codes != _lo) if _exact else jnp.ones_like(codes, dtype=bool)
            elif _op == "<":
                v = codes < _lo
            elif _op == "<=":
                v = codes < _hi
            elif _op == ">":
                v = codes >= _hi
            else:
                v = codes >= _lo
            return v, _m(ok)
        return fn

    def compile_arith(e: BoundFunc):
        fa, fb = rec(e.args[0]), rec(e.args[1])
        op = e.name[2:]
        if e.type.is_decimal and op in ("+", "-"):
            s = e.type.scale
            fa = _rescale(fa, s - _scale(e.args[0].type))
            fb = _rescale(fb, s - _scale(e.args[1].type))
        int_result = e.type.is_integer

        def fn(env, _fa=fa, _fb=fb, _op=op, _int=int_result):
            (va, oka), (vb, okb) = _fa(env), _fb(env)
            va, vb = _unify(va, vb)
            ok = jnp.logical_and(_m(oka), _m(okb))
            if _op == "+":
                return va + vb, ok
            if _op == "-":
                return va - vb, ok
            if _op == "*":
                return va * vb, ok
            raise NotCompilable("device division")  # PG trunc semantics: CPU
        return fn

    top = rec(expr)
    return DeviceExpr(top, inputs, tuple(consts))


def _m(ok):
    return ok if not isinstance(ok, bool) else jnp.bool_(ok)


INT32 = (-(1 << 31), (1 << 31) - 1)


def expr_bounds(e: BoundExpr, col_bounds) -> tuple[int, int]:
    """Interval of an integer expression's physical values (a DECIMAL's
    scaled integer) from `col_bounds(column index) -> (lo, hi)`, checking
    that every node stays inside int32: the device runs with x64 off, so
    a product that would leave int32 wraps silently there. Raises
    NotCompilable('int_range') where a node can leave int32."""
    def rec(x):
        if isinstance(x, BoundLiteral):
            if x.value is None or isinstance(x.value, bool):
                return 0, 1
            if isinstance(x.value, int):
                return x.value, x.value
            raise NotCompilable("non-integer literal", "int_range")
        if isinstance(x, BoundColumn):
            if x.type.is_float or x.type.is_string:
                return 0, 0          # floats do not wrap; codes never add
            lo, hi = col_bounds(x.index)
        elif isinstance(x, BoundCase):
            parts = [rec(v) for _, v in x.branches]
            parts.append(rec(x.else_) if x.else_ is not None else (0, 0))
            for c, _ in x.branches:
                rec(c)
            lo, hi = min(p[0] for p in parts), max(p[1] for p in parts)
        elif isinstance(x, BoundFunc):
            if x.type.id is dt.TypeId.BOOL:
                for a in x.args:
                    if not a.type.is_string:
                        rec(a)
                return 0, 1
            if x.name in _IDENTITY_FUNCS:
                return rec(x.args[0])
            if x.name == "cast" and x.type.is_decimal:
                lo, hi = rec(x.args[0])
                f = 10 ** (x.type.scale - _scale(x.args[0].type))
                lo, hi = lo * f, hi * f
            elif x.name == "cast" and x.type.is_integer:
                lo, hi = rec(x.args[0])
            elif x.name in ("op+", "op-", "op*"):
                (al, ah), (bl, bh) = rec(x.args[0]), rec(x.args[1])
                if x.type.is_decimal and x.name != "op*":
                    fa = 10 ** (x.type.scale - _scale(x.args[0].type))
                    fb = 10 ** (x.type.scale - _scale(x.args[1].type))
                    al, ah, bl, bh = al * fa, ah * fa, bl * fb, bh * fb
                if x.name == "op+":
                    lo, hi = al + bl, ah + bh
                elif x.name == "op-":
                    lo, hi = al - bh, ah - bl
                else:
                    ps = (al * bl, al * bh, ah * bl, ah * bh)
                    lo, hi = min(ps), max(ps)
            else:
                raise NotCompilable(f"bounds of {x.name}", "int_range")
        else:
            raise NotCompilable(type(x).__name__, "int_range")
        if lo < INT32[0] or hi > INT32[1]:
            raise NotCompilable("int32 overflow on device", "int_range")
        return lo, hi
    return rec(e)


def _as_bool(v):
    if v.dtype == jnp.bool_:
        return v
    return v != 0


def _unify(va, vb):
    fa = hasattr(va, "dtype") and jnp.issubdtype(va.dtype, jnp.floating)
    fb = hasattr(vb, "dtype") and jnp.issubdtype(vb.dtype, jnp.floating)
    if fa or fb:
        return (va.astype(jnp.float32) if hasattr(va, "astype") else jnp.float32(va),
                vb.astype(jnp.float32) if hasattr(vb, "astype") else jnp.float32(vb))
    return va, vb


# The per-(provider, query-shape) jitted program cache that used to
# live here (an unbounded module dict — one leaked executable per novel
# query shape for process lifetime) is now the obs/device.py compile
# ledger: a BOUNDED LRU (serene_program_cache_entries) with per-family
# compile/hit/miss accounting. Call sites go through
# obs.device.compiled(family, key, builder).
