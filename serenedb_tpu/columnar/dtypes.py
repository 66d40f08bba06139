"""SQL type system mapped onto TPU-friendly physical dtypes.

The reference models types through DuckDB's LogicalType plus PG pseudo-types
(reference: server/pg/pg_types.cpp, server/query/server_engine.cpp:61-216).
Here the logical SQL type system is small and explicit, and every type has a
*physical* representation chosen for the TPU compute path:

- integers/floats/bools/timestamps: native numpy/jax dtypes
- VARCHAR: dictionary-encoded int32 codes on device; the dictionary
  (per-column, per-segment) stays host-side. String predicates are resolved
  against the dictionary on CPU and become integer-code predicates on device.
- DECIMAL(p, s), p <= 18: the value times 10^s as int64 (DuckDB's own
  physical form for such widths), so zone maps and the device tiers see
  an integer. `+` and `-` align scales, `*` adds them, an int64 overflow
  raises 22003; SUM is DECIMAL(18, s), MIN/MAX keep the type. `/` and
  AVG return DOUBLE: a departure from PG's `numeric`, whose quotient is
  another exact numeric.
"""

from __future__ import annotations

import enum
import re
from dataclasses import dataclass

import numpy as np


class TypeId(enum.Enum):
    BOOL = "BOOLEAN"
    TINYINT = "TINYINT"
    SMALLINT = "SMALLINT"
    INT = "INTEGER"
    BIGINT = "BIGINT"
    FLOAT = "FLOAT"
    DOUBLE = "DOUBLE"
    VARCHAR = "VARCHAR"
    TIMESTAMP = "TIMESTAMP"  # micros since epoch, int64
    DATE = "DATE"            # days since epoch, int32
    INTERVAL = "INTERVAL"    # duration in micros, int64 (fixed units only)
    NULL = "NULL"            # type of bare NULL literal
    # PG pseudo-types for catalog introspection (reference:
    # server/query/server_engine.cpp:61-216). Physically int64 object ids;
    # casting to/from text resolves names against the live catalog.
    OID = "OID"
    REGCLASS = "REGCLASS"
    REGTYPE = "REGTYPE"
    REGPROC = "REGPROC"
    REGNAMESPACE = "REGNAMESPACE"
    ARRAY = "ARRAY"          # element-typed; physically JSON text in a
                             # dictionary column (wire layer renders/encodes
                             # PG {…} text and the binary array format)
    RECORD = "RECORD"        # anonymous composite (ROW(...)); physically
                             # JSON {"o":[oid,...],"v":[...]} text in a
                             # dictionary column; wire layer renders PG
                             # (…) text / the binary record format (2249)
    DECIMAL = "DECIMAL"      # DECIMAL(p, s), p <= 18: value * 10^s, int64
    VECTOR = "VECTOR"        # pgvector's VECTOR(n): n float32 a row,
                             # physically ONE contiguous (rows, n) float32
                             # array (the only 2-D column); text form
                             # '[v1,v2,...]' on the wire and in literals


_NUMPY_OF = {
    TypeId.BOOL: np.dtype(np.bool_),
    TypeId.TINYINT: np.dtype(np.int8),
    TypeId.SMALLINT: np.dtype(np.int16),
    TypeId.INT: np.dtype(np.int32),
    TypeId.BIGINT: np.dtype(np.int64),
    TypeId.FLOAT: np.dtype(np.float32),
    TypeId.DOUBLE: np.dtype(np.float64),
    TypeId.VARCHAR: np.dtype(np.int32),   # dictionary codes
    TypeId.TIMESTAMP: np.dtype(np.int64),
    TypeId.DATE: np.dtype(np.int32),
    TypeId.INTERVAL: np.dtype(np.int64),
    TypeId.NULL: np.dtype(np.int32),
    TypeId.ARRAY: np.dtype(np.int32),     # dictionary codes (JSON text)
    TypeId.RECORD: np.dtype(np.int32),    # dictionary codes (JSON text)
    TypeId.VECTOR: np.dtype(np.float32),  # (rows, dim)
    TypeId.DECIMAL: np.dtype(np.int64),   # scaled by 10^scale
    TypeId.OID: np.dtype(np.int64),
    TypeId.REGCLASS: np.dtype(np.int64),
    TypeId.REGTYPE: np.dtype(np.int64),
    TypeId.REGPROC: np.dtype(np.int64),
    TypeId.REGNAMESPACE: np.dtype(np.int64),
}

_INTEGERS = {TypeId.TINYINT, TypeId.SMALLINT, TypeId.INT, TypeId.BIGINT,
             TypeId.OID, TypeId.REGCLASS, TypeId.REGTYPE, TypeId.REGPROC,
             TypeId.REGNAMESPACE}
_FLOATS = {TypeId.FLOAT, TypeId.DOUBLE}


@dataclass(frozen=True)
class SqlType:
    """A logical SQL type. Kept as a dataclass so parametric types
    (DECIMAL(p,s), VARCHAR(n)) can be added without changing call sites."""

    id: TypeId
    #: ARRAY element type (None elsewhere); frozen+defaulted so equality
    #: and hashing of existing scalar types are unchanged
    elem: "TypeId | None" = None
    #: VECTOR dimension count (0 elsewhere)
    dim: int = 0
    #: DECIMAL precision and scale (0 elsewhere)
    prec: int = 0
    scale: int = 0

    @property
    def is_vector(self) -> bool:
        return self.id is TypeId.VECTOR

    @property
    def np_dtype(self) -> np.dtype:
        return _NUMPY_OF[self.id]

    @property
    def is_integer(self) -> bool:
        return self.id in _INTEGERS

    @property
    def is_float(self) -> bool:
        return self.id in _FLOATS

    @property
    def is_decimal(self) -> bool:
        return self.id is TypeId.DECIMAL

    @property
    def is_numeric(self) -> bool:
        return self.is_integer or self.is_float or \
            self.id in (TypeId.BOOL, TypeId.DECIMAL)

    @property
    def is_string(self) -> bool:
        # ARRAY/RECORD share the dictionary-string physical representation
        return self.id in (TypeId.VARCHAR, TypeId.ARRAY, TypeId.RECORD)

    def __str__(self) -> str:  # PG-style rendering
        if self.id is TypeId.ARRAY:
            return f"{(self.elem or TypeId.VARCHAR).value}[]"
        if self.id is TypeId.RECORD:
            return "record"
        if self.id is TypeId.VECTOR:
            return f"VECTOR({self.dim})"
        if self.id is TypeId.DECIMAL:
            return f"DECIMAL({self.prec},{self.scale})"
        return self.id.value


BOOL = SqlType(TypeId.BOOL)
TINYINT = SqlType(TypeId.TINYINT)
SMALLINT = SqlType(TypeId.SMALLINT)
INT = SqlType(TypeId.INT)
BIGINT = SqlType(TypeId.BIGINT)
FLOAT = SqlType(TypeId.FLOAT)
DOUBLE = SqlType(TypeId.DOUBLE)
VARCHAR = SqlType(TypeId.VARCHAR)
TIMESTAMP = SqlType(TypeId.TIMESTAMP)
DATE = SqlType(TypeId.DATE)
INTERVAL = SqlType(TypeId.INTERVAL)
OID = SqlType(TypeId.OID)
REGCLASS = SqlType(TypeId.REGCLASS)
REGTYPE = SqlType(TypeId.REGTYPE)
REGPROC = SqlType(TypeId.REGPROC)
REGNAMESPACE = SqlType(TypeId.REGNAMESPACE)
NULLTYPE = SqlType(TypeId.NULL)
RECORD = SqlType(TypeId.RECORD)


def array_of(elem: "SqlType | TypeId | None") -> SqlType:
    """Element-typed array (TEXT elements when unknown)."""
    if isinstance(elem, SqlType):
        elem = elem.id
    if elem in (None, TypeId.NULL, TypeId.ARRAY):
        elem = TypeId.VARCHAR
    return SqlType(TypeId.ARRAY, elem)

def vector_of(dim: int) -> SqlType:
    """pgvector's VECTOR(dim): dim float32 a row, stored contiguously."""
    dim = int(dim)
    if not 1 <= dim <= 16000:          # pgvector's own ceiling
        raise ValueError(f"vector dimensions must be 1..16000, got {dim}")
    return SqlType(TypeId.VECTOR, None, dim)


#: widest DECIMAL an int64 holds: 18 digits
MAX_DECIMAL_PRECISION = 18


def decimal_of(prec: int, scale: int) -> SqlType:
    """DECIMAL(prec, scale) as a scaled int64 (prec <= 18)."""
    prec, scale = int(prec), int(scale)
    if not 1 <= prec <= MAX_DECIMAL_PRECISION:
        raise ValueError(f"DECIMAL precision {prec} must be between 1 and "
                         f"{MAX_DECIMAL_PRECISION}")
    if not 0 <= scale <= prec:
        raise ValueError(f"DECIMAL scale {scale} must be between 0 and "
                         f"the precision {prec}")
    return SqlType(TypeId.DECIMAL, None, 0, prec, scale)


def decimal_text(v: int, scale: int) -> str:
    """Text of a scaled integer with exactly `scale` fraction digits."""
    v = int(v)
    if not scale:
        return str(v)
    sign = "-" if v < 0 else ""
    whole, frac = divmod(abs(v), 10 ** scale)
    return f"{sign}{whole}.{frac:0{scale}d}"


_DECIMAL_NAME = re.compile(
    r"^(?:DECIMAL|NUMERIC|DEC)\s*(?:\(\s*(\d+)\s*(?:,\s*(\d+)\s*)?\))?$")


#: VECTOR(n), pgvector's spelling — and FLOAT4[n] / REAL[n] / FLOAT[n],
#: what a Postgres user without the extension writes for the same thing
_VECTOR_NAME = re.compile(
    r"^(?:VECTOR\s*\(\s*(\d+)\s*\)|(?:FLOAT4|REAL|FLOAT)\s*\[\s*(\d+)\s*\])$")

_BY_NAME = {
    "BOOLEAN": BOOL, "BOOL": BOOL,
    "TINYINT": TINYINT, "INT1": TINYINT,
    "SMALLINT": SMALLINT, "INT2": SMALLINT,
    "INTEGER": INT, "INT": INT, "INT4": INT,
    "BIGINT": BIGINT, "INT8": BIGINT, "LONG": BIGINT,
    "FLOAT": FLOAT, "REAL": FLOAT, "FLOAT4": FLOAT,
    "DOUBLE": DOUBLE, "FLOAT8": DOUBLE, "DOUBLE PRECISION": DOUBLE,
    "VARCHAR": VARCHAR, "TEXT": VARCHAR, "STRING": VARCHAR, "CHAR": VARCHAR,
    "TIMESTAMP": TIMESTAMP, "TIMESTAMPTZ": TIMESTAMP, "DATETIME": TIMESTAMP,
    "DATE": DATE,
    "INTERVAL": INTERVAL,
    "OID": OID, "REGCLASS": REGCLASS, "REGTYPE": REGTYPE,
    "REGPROC": REGPROC, "REGPROCEDURE": REGPROC,
    "REGNAMESPACE": REGNAMESPACE,
    "NAME": VARCHAR, "BPCHAR": VARCHAR, "JSON": VARCHAR, "JSONB": VARCHAR,
    "UUID": VARCHAR, "XID": BIGINT, "CID": BIGINT,
}

# numeric widening lattice for binary-op result typing
_RANK = {
    TypeId.BOOL: 0, TypeId.TINYINT: 1, TypeId.SMALLINT: 2, TypeId.INT: 3,
    TypeId.DATE: 3, TypeId.BIGINT: 4, TypeId.TIMESTAMP: 4,
    TypeId.OID: 4, TypeId.REGCLASS: 4, TypeId.REGTYPE: 4, TypeId.REGPROC: 4,
    TypeId.REGNAMESPACE: 4, TypeId.DECIMAL: 4.5,
    TypeId.FLOAT: 5, TypeId.DOUBLE: 6,
}


def type_from_name(name: str) -> SqlType:
    key = name.upper().strip()
    m = _VECTOR_NAME.match(key)
    if m:
        return vector_of(int(m.group(1) or m.group(2)))
    m = _DECIMAL_NAME.match(key)
    if m:
        # DECIMAL alone is DuckDB's DECIMAL(18,3); DECIMAL(p) has scale 0
        if m.group(1) is None:
            return decimal_of(18, 3)
        return decimal_of(int(m.group(1)), int(m.group(2) or 0))
    key = re.sub(r"\[\d+\]$", "[]", key)   # PG ignores a declared array size
    if key.endswith("[]"):
        return array_of(type_from_name(key[:-2]))
    if key == "ARRAY":          # legacy/unparameterized
        return array_of(None)
    t = _BY_NAME.get(key)
    if t is None:
        raise ValueError(f"unknown type name: {name!r}")
    return t


def unify_pair(a: SqlType, b: SqlType) -> SqlType:
    """Branch-type unification (CASE/COALESCE/VALUES arms): NULL yields
    the other side, equal types stay, numerics widen via common_numeric,
    and any other mix keeps the first typed side (text-vs-x arms render
    through the first type, matching the engine's historical behavior)."""
    if a.id is TypeId.NULL:
        return b
    if b.id is TypeId.NULL or a == b:
        return a
    if a.is_numeric and b.is_numeric:
        return common_numeric(a, b)
    return a


def unify_all(types) -> SqlType:
    t = NULLTYPE
    for x in types:
        t = unify_pair(t, x)
    return t


def common_numeric(a: SqlType, b: SqlType) -> SqlType:
    """Widening for arithmetic/comparison between numeric types."""
    if a.id is TypeId.NULL:
        return b
    if b.id is TypeId.NULL:
        return a
    if not (a.is_numeric or a.id in (TypeId.TIMESTAMP, TypeId.DATE)):
        raise TypeError(f"non-numeric type {a}")
    if not (b.is_numeric or b.id in (TypeId.TIMESTAMP, TypeId.DATE)):
        raise TypeError(f"non-numeric type {b}")
    if a.is_decimal or b.is_decimal:
        if a.is_float or b.is_float:
            return DOUBLE
        return decimal_of(MAX_DECIMAL_PRECISION, max(a.scale, b.scale))
    return a if _RANK[a.id] >= _RANK[b.id] else b


class ExactFloat(float):
    """A numeric literal written with a decimal point: the float the
    engine has always used, plus its exact decimal text, so that beside a
    DECIMAL it is typed exactly (`0.05` is 5 at scale 2, never a float
    rounded back). A constant folded from two such literals keeps the
    binary float's value and the exact text (`0.06 - 0.01`)."""

    def __new__(cls, text: str, value: "float | None" = None):
        obj = super().__new__(cls, text if value is None else value)
        obj.text = text
        return obj

    def __reduce__(self):
        return (ExactFloat, (self.text, float(self)))


def exact_decimal(v) -> "tuple[int, int] | None":
    """(scaled integer, scale) of an int or an ExactFloat literal written
    without an exponent; None for any other value."""
    if isinstance(v, bool):
        return None
    if isinstance(v, int):
        return v, 0
    if isinstance(v, ExactFloat):
        m = re.fullmatch(r"([+-]?)(\d*)\.(\d*)", v.text.strip())
        if m is None:
            return None
        frac = m.group(3)
        n = int((m.group(2) or "0") + frac)
        return (-n if m.group(1) == "-" else n), len(frac)
    return None


def type_of_numpy(dt: np.dtype) -> SqlType:
    for tid, nd in _NUMPY_OF.items():
        if tid in (TypeId.VARCHAR, TypeId.NULL, TypeId.DATE, TypeId.VECTOR,
                   TypeId.DECIMAL):
            continue
        if nd == dt:
            return SqlType(tid)
    if np.issubdtype(dt, np.integer):
        return BIGINT
    if np.issubdtype(dt, np.floating):
        return DOUBLE
    raise TypeError(f"unsupported numpy dtype {dt}")
