"""Plain reference for the `hits` statements: one numpy evaluator of a
small declarative spec (filter -> group -> aggregate -> order -> limit).

Independent of `serenedb_tpu`: it reads only the arrays the dataset
generator made. A query-set file gives each statement its SQL text AND its
spec, so a later PR adds a statement by adding data, not code:

    {"where":    [["AdvEngineID", "<>", 0], ...],          # conjunction
     "group_by": ["RegionID"],
     "select":   [["key", "RegionID"], ["sum", "AdvEngineID"],
                  ["count", "*"], ["avg", "ResolutionWidth"],
                  ["count_distinct", "UserID"]],
     "order_by": [[2, "desc"], [0, "asc"]],                # select index
     "limit":    10}

`select` kinds: key, col (projection, no grouping: the answer is a
multiset), count, sum, avg, min, max, count_distinct. Integer answers are
exact (python ints); `avg` is the exact integer sum over the exact count,
rounded once to float64. String columns are dictionary codes plus their
dictionary; they compare and order as their strings (byte order).

A reference is a module of its own under `benchmark/references/`, found
by the query set's `reference`; the harness calls its `check` (below)
once the window has closed and the server is gone.
"""

from __future__ import annotations

from fractions import Fraction

_OPS = {
    "=": lambda a, v: a == v, "<>": lambda a, v: a != v,
    "<": lambda a, v: a < v, "<=": lambda a, v: a <= v,
    ">": lambda a, v: a > v, ">=": lambda a, v: a >= v,
}


class Table:
    """The generated columns, with string columns' orderings prepared."""

    def __init__(self, columns: dict, dictionaries: dict):
        import numpy as np
        self.columns = columns
        self.dicts = dictionaries
        self.n = len(next(iter(columns.values())))
        self.rank = {}
        for name, pool in dictionaries.items():
            order = sorted(range(len(pool)), key=lambda i: pool[i].encode())
            r = np.empty(len(pool), np.int64)
            r[order] = np.arange(len(pool))
            self.rank[name] = r
        self._code = {name: {s: i for i, s in enumerate(pool)}
                      for name, pool in dictionaries.items()}

    def literal(self, col: str, v):
        if col in self.dicts:
            # a string never generated matches no row
            return self._code[col].get(v, -1)
        return int(v)


def _exact_sum(a) -> int:
    """Sum of an integer array as a python int, whatever its range."""
    import numpy as np
    a = a.astype(np.int64, copy=False)
    if len(a) == 0:
        return 0
    if max(abs(int(a.min())), abs(int(a.max()))) * len(a) < (1 << 62):
        return int(a.sum(dtype=np.int64))
    hi, lo = a >> 31, a & ((1 << 31) - 1)
    return (int(hi.sum(dtype=np.int64)) << 31) + int(lo.sum(dtype=np.int64))


def _group_sum(ginv, a, ng: int, accum_dtype):
    """Per-group integer sums (int64 array). Exact unless `accum_dtype`
    asks for the control's narrow accumulator."""
    import numpy as np
    a = a.astype(np.int64)
    if accum_dtype is None:
        if len(a) and int(np.abs(a).max()) * len(a) >= (1 << 53):
            raise ValueError("grouped sum is not exact in this reference")
        return np.bincount(ginv, weights=a.astype(np.float64),
                           minlength=ng).astype(np.int64)
    order = np.argsort(ginv, kind="stable")
    starts = np.searchsorted(ginv[order], np.arange(ng))
    return np.add.reduceat(a[order].astype(accum_dtype), starts).astype(
        accum_dtype).astype(np.int64)


def _dense(a):
    """(distinct values ascending, index of each row's value)."""
    import numpy as np
    vals, inv = np.unique(a, return_inverse=True)
    return vals, inv.astype(np.int64, copy=False)


def evaluate(table: Table, spec: dict, accum_dtype=None) -> dict:
    """Rows of the statement the spec describes. Returns
    {"rows": [tuple, ...], "ordered": bool, "types": [...]}; types are
    'int' | 'float' | 'str' per output column.

    `accum_dtype` is for the CONTROL only (see `correctness.py`): sums
    and counts accumulated in that numpy dtype, as a device path that
    gave up exactness would (counts stay exact: a float32 counts to 2^24
    without loss, which is above the configuration's row count)."""
    import numpy as np
    cols = table.columns
    mask = None
    for col, op, val in spec.get("where", []):
        m = _OPS[op](cols[col], table.literal(col, val))
        mask = m if mask is None else (mask & m)

    def sel(name):
        a = cols[name]
        return a if mask is None else a[mask]

    def total(a):
        if accum_dtype is None:
            return _exact_sum(a)
        return int(a.astype(accum_dtype).sum(dtype=accum_dtype))

    select = spec["select"]
    group_by = spec.get("group_by", [])
    types = []
    for kind, arg in select:
        if kind in ("key", "col"):
            types.append("str" if arg in table.dicts else "int")
        else:
            types.append("float" if kind == "avg" else "int")

    if any(kind == "col" for kind, _ in select):
        # projection of the filtered rows: an unordered multiset
        out = [sel(arg) for _, arg in select]
        rows = list(zip(*[_to_py(table, arg, a)
                          for (_, arg), a in zip(select, out)]))
        return {"rows": rows, "ordered": False, "types": types}

    n_sel = table.n if mask is None else int(mask.sum())
    if not group_by:
        row = []
        for kind, arg in select:
            if kind == "count":
                row.append(n_sel)
            elif kind in ("sum", "min", "max") and not n_sel:
                row.append(None)            # SQL: NULL over no rows
            elif kind == "sum":
                row.append(total(sel(arg)))
            elif kind == "avg":
                row.append(float(Fraction(total(sel(arg)), n_sel))
                           if n_sel else None)
            elif kind == "min":
                row.append(int(sel(arg).min()))
            elif kind == "max":
                row.append(int(sel(arg).max()))
            elif kind == "count_distinct":
                row.append(int(len(np.unique(sel(arg)))))
            else:
                raise ValueError(f"select kind {kind!r} without GROUP BY")
        return {"rows": [tuple(row)], "ordered": True, "types": types}

    # group ids: each key densified, then combined
    key_vals, gid, width = [], None, 1
    for k in group_by:
        vals, inv = _dense(sel(k))
        key_vals.append(vals)
        gid = inv if gid is None else gid * len(vals) + inv
        width *= len(vals)
        if width >= (1 << 62):
            raise ValueError("group key space overflows int64")
    gvals, ginv = _dense(gid)
    ng = len(gvals)
    key_of = {}
    rem = gvals.copy()
    for k, vals in reversed(list(zip(group_by, key_vals))):
        key_of[k] = vals[rem % len(vals)]
        rem //= len(vals)
    cnt = np.bincount(ginv, minlength=ng)
    out = []
    for kind, arg in select:
        if kind == "key":
            out.append(key_of[arg])
        elif kind == "count":
            out.append(cnt)
        elif kind in ("sum", "avg"):
            s = _group_sum(ginv, sel(arg), ng, accum_dtype)
            out.append(s if kind == "sum" else
                       np.array([float(Fraction(int(x), int(c)))
                                 for x, c in zip(s, cnt)]))
        elif kind == "count_distinct":
            vals, inv = _dense(sel(arg))
            pair = np.unique(ginv * len(vals) + inv)
            out.append(np.bincount(pair // len(vals), minlength=ng))
        elif kind in ("min", "max"):
            a = sel(arg)
            order = np.argsort(ginv, kind="stable")
            starts = np.searchsorted(ginv[order], np.arange(ng))
            f = np.minimum if kind == "min" else np.maximum
            out.append(f.reduceat(a[order], starts))
        else:
            raise ValueError(f"unknown select kind {kind!r}")
    idx = np.arange(ng)
    order_by = spec.get("order_by", [])
    if order_by:
        keys = []
        for i, direction in reversed(order_by):
            kind, arg = select[i]
            a = out[i]
            if kind == "key" and arg in table.rank:
                a = table.rank[arg][a]
            keys.append(-a if direction == "desc" else a)
        idx = np.lexsort(keys)
    if spec.get("limit") is not None:
        idx = idx[:int(spec["limit"])]
    rows = list(zip(*[_to_py(table, arg if kind == "key" else None, a[idx])
                      for (kind, arg), a in zip(select, out)]))
    return {"rows": rows, "ordered": bool(order_by), "types": types}


def _to_py(table: Table, col, a) -> list:
    if col is not None and col in table.dicts:
        pool = table.dicts[col]
        return [pool[int(c)] for c in a]
    return a.tolist()


# -- the comparison -----------------------------------------------------------


def _float_err(got: str, want: float) -> float:
    g = float(got)
    if want == 0.0:
        return abs(g)
    return abs(g - want) / abs(want)


def compare_rows(got_rows, ref: dict) -> tuple[bool, float]:
    """(exact parts equal, largest relative error over float columns) of
    one answer. `got_rows` are text tuples off the wire; the reference's
    rows are python values. An unordered answer compares as a multiset."""
    want = ref["rows"]
    if len(got_rows) != len(want):
        return False, 0.0
    types = ref["types"]
    if not ref["ordered"]:
        got_rows = sorted(got_rows)
        want = sorted(tuple(str(v) for v in r) for r in want)
        return got_rows == want, 0.0
    worst = 0.0
    for g, w in zip(got_rows, want):
        if len(g) != len(w):
            return False, worst
        for gv, wv, t in zip(g, w, types):
            if wv is None or gv is None:
                if not (wv is None and gv is None):
                    return False, worst
            elif t == "float":
                try:
                    worst = max(worst, _float_err(gv, wv))
                except ValueError:
                    return False, worst
            elif gv != str(wv):
                return False, worst
    return True, worst


def check(ops: list, source, dataset: dict, seed: int, check,
          control: bool = False, cfg: dict = None):
    """Every answer of the window against `evaluate`: what a reference
    module gives the harness, ({number: value}, answers compared). With
    `control`, the reference's own answers computed with float32
    accumulators stand in for the program's."""
    import numpy as np
    table = Table(dataset["columns"], dataset["dictionaries"])
    distinct: dict = {}
    for op in ops:
        if op["ok"]:
            k = (op["key"], tuple(op["answer"]))
            distinct[k] = distinct.get(k, 0) + 1
    refs: dict = {}
    wrong, worst = 0, 0.0
    for (key, answer), n in distinct.items():
        if key not in refs:
            refs[key] = evaluate(table, source.by_key[key][1])
        ref = refs[key]
        if control:
            low = evaluate(table, source.by_key[key][1],
                           accum_dtype=np.float32)
            answer = tuple(tuple(None if v is None else
                                 (repr(v) if isinstance(v, float) else str(v))
                                 for v in r) for r in low["rows"])
        ok, err = compare_rows(list(answer), ref)
        worst = max(worst, err)
        if not ok:
            wrong += n
    return {"wrong_answers": wrong, "float_rel_err_max": worst}, \
        sum(distinct.values())
