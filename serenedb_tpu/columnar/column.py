"""Column batch ABI — the unit of data exchange across the whole framework.

Reference analog: DuckDB's DataChunk/Vector flowing between physical operators
(the reference moves DataChunks through morsel-driven pipelines; see
SURVEY.md §3.2). Here the layout is chosen for HBM/TPU:

- struct-of-arrays: one contiguous numpy array per column
- validity as a separate bool array (None ⇒ all valid)
- VARCHAR is dictionary-encoded: `data` holds int32 codes into a host-side
  `dictionary` (numpy object array of python str), kept **lexicographically
  sorted** so integer code order == string order and device-side comparisons
  (<, <=, =, >, >=, GROUP BY, ORDER BY) are exact on codes.
- a NULL code of -1 is never used; validity carries nullness so codes stay
  non-negative and usable as gather indices.
- VECTOR(n) is the one 2-D column: `data` is ONE contiguous float32
  (rows, n) array (a NULL row is zeros under its validity bit), so take /
  slice / concat are the same axis-0 operations and an index reads the
  array as it stands, with no per-row parse.

Columns are immutable by convention: operators build new ones.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Optional, Sequence

import numpy as np

from ..obs.trace import stage
from ..utils import metrics
from . import dtypes as dt


@dataclass
class Column:
    type: dt.SqlType
    data: np.ndarray                       # 1-D, physical dtype of `type`
    validity: Optional[np.ndarray] = None  # 1-D bool; None ⇒ all valid
    dictionary: Optional[np.ndarray] = None  # VARCHAR only: sorted unique strs

    def __post_init__(self):
        if self.type.is_vector:
            if self.data.ndim == 1 and not len(self.data):
                # the many `np.empty(0, dtype=t.np_dtype)` of empty tables
                self.data = self.data.reshape(0, self.type.dim)
            assert self.data.ndim == 2 and \
                self.data.shape[1] == self.type.dim
        else:
            assert self.data.ndim == 1
        if self.validity is not None:
            assert self.validity.shape == self.data.shape[:1]
            if bool(self.validity.all()):
                self.validity = None

    def __len__(self) -> int:
        return len(self.data)

    @property
    def has_nulls(self) -> bool:
        return self.validity is not None

    def valid_mask(self) -> np.ndarray:
        if self.validity is None:
            return np.ones(len(self.data), dtype=bool)
        return self.validity

    # -- construction ------------------------------------------------------

    @staticmethod
    def from_pylist(values: Sequence, typ: Optional[dt.SqlType] = None) -> "Column":
        """Build from python values (None ⇒ NULL). Infers type if not given."""
        non_null = [v for v in values if v is not None]
        if typ is None:
            typ = _infer_type(non_null)
        validity = np.array([v is not None for v in values], dtype=bool)
        n = len(values)
        if typ.is_vector:
            data = np.zeros((n, typ.dim), dtype=np.float32)
            for i, v in enumerate(values):
                if v is not None:
                    data[i] = vector_value(v, typ.dim)
            col = Column(typ, data, validity)
        elif typ.is_string:
            strs = [("" if v is None else str(v)) for v in values]
            dictionary, codes = _encode_dictionary(strs)
            col = Column(typ, codes.astype(np.int32), validity, dictionary)
        elif typ.id is dt.TypeId.BOOL:
            data = np.array([bool(v) if v is not None else False for v in values],
                            dtype=np.bool_)
            col = Column(typ, data, validity)
        else:
            fill = 0
            try:
                data = np.array([fill if v is None else v for v in values],
                                dtype=typ.np_dtype)
            except OverflowError:
                from .. import errors
                raise errors.SqlError(
                    "22003",
                    f"value out of range for type "
                    f"{typ.id.name.lower()}")
            col = Column(typ, data, validity)
        if n == 0:
            col.validity = None
        return col

    @staticmethod
    def from_numpy(arr: np.ndarray, typ: Optional[dt.SqlType] = None,
                   validity: Optional[np.ndarray] = None) -> "Column":
        if arr.dtype.kind in ("U", "S", "O"):
            strs = [("" if v is None else str(v)) for v in arr.tolist()]
            dictionary, codes = _encode_dictionary(strs)
            return Column(dt.VARCHAR, codes.astype(np.int32), validity, dictionary)
        if typ is None:
            typ = dt.type_of_numpy(arr.dtype)
        return Column(typ, np.ascontiguousarray(arr, dtype=typ.np_dtype), validity)

    @staticmethod
    def const(value, n: int, typ: Optional[dt.SqlType] = None) -> "Column":
        """Constant column without the python-list round-trip: literals
        sit in EVERY expression eval, so this is np.full/np.zeros (which
        release the GIL) instead of from_pylist's per-element list build
        — the difference between host pipelines scaling and serializing
        on literal materialization."""
        if typ is None:
            typ = _infer_type([] if value is None else [value])
        if typ.is_vector:
            row = np.zeros(typ.dim, np.float32) if value is None \
                else vector_value(value, typ.dim)
            return Column(typ, np.broadcast_to(row, (n, typ.dim)),
                          None if value is not None
                          else np.zeros(n, dtype=bool))
        if value is None:
            if typ.is_string:
                return Column(typ, np.zeros(n, dtype=np.int32),
                              np.zeros(n, dtype=bool),
                              np.asarray([""], dtype=object))
            return Column(typ, np.zeros(n, dtype=typ.np_dtype),
                          np.zeros(n, dtype=bool))
        if typ.is_string:
            return Column(typ, np.zeros(n, dtype=np.int32), None,
                          np.asarray([str(value)], dtype=object))
        if typ.id is dt.TypeId.BOOL:
            return Column(typ, np.full(n, bool(value), dtype=np.bool_))
        try:
            npd = np.dtype(typ.np_dtype)
            if npd.kind in "iu" and isinstance(value, int) and \
                    not (np.iinfo(npd).min <= value <= np.iinfo(npd).max):
                # np.full would silently wrap (np.array raises) — keep
                # from_pylist's 22003 out-of-range behavior
                raise OverflowError(value)
            return Column(typ, np.full(n, value, dtype=typ.np_dtype))
        except (OverflowError, ValueError, TypeError):
            return Column.from_pylist([value] * n, typ)

    # -- accessors ---------------------------------------------------------

    def to_pylist(self) -> list:
        out = []
        valid = self.valid_mask()
        if self.type.is_vector:
            for i in range(len(self.data)):
                out.append(vector_text(self.data[i]) if valid[i] else None)
        elif self.type.is_string:
            d = self.dictionary
            for i in range(len(self.data)):
                out.append(str(d[self.data[i]]) if valid[i] else None)
        else:
            for i in range(len(self.data)):
                v = self.data[i]
                out.append(v.item() if valid[i] else None)
        return out

    def decode(self, i: int):
        """Single-value accessor (python value or None)."""
        if self.validity is not None and not self.validity[i]:
            return None
        if self.type.is_string:
            return str(self.dictionary[self.data[i]])
        if self.type.is_vector:
            return vector_text(self.data[i])
        return self.data[i].item()

    def take(self, indices: np.ndarray) -> "Column":
        v = None if self.validity is None else self.validity[indices]
        return Column(self.type, self.data[indices], v, self.dictionary)

    def filter(self, mask: np.ndarray) -> "Column":
        return self.take(np.flatnonzero(mask))

    def slice(self, start: int, stop: int) -> "Column":
        v = None if self.validity is None else self.validity[start:stop]
        return Column(self.type, self.data[start:stop], v, self.dictionary)

    def re_dictionary(self) -> "Column":
        """Rebuild the dictionary to only the codes in use (post-filter)."""
        if not self.type.is_string or self.dictionary is None:
            return self
        used = np.unique(self.data)
        new_dict = self.dictionary[used]
        remap = np.zeros(len(self.dictionary), dtype=np.int32)
        remap[used] = np.arange(len(used), dtype=np.int32)
        return Column(self.type, remap[self.data], self.validity, new_dict)


def vector_text(row: np.ndarray) -> str:
    """pgvector's text form of one float32 row, '[v1,v2,...]': the
    shortest digits that read back as the same float32 bits."""
    return "[" + ",".join(map(str, np.asarray(row, np.float32))) + "]"


def vector_value(v, dim: Optional[int] = None) -> np.ndarray:
    """One vector value as float32 from its text form ('[v1,v2,...]':
    pgvector's, which is also a JSON array), a sequence or an array; of
    `dim` dimensions when that is given. THE parser of a vector literal:
    `search/ivf.parse_vector` is this."""
    from .. import errors
    if isinstance(v, str):
        import json
        text = v
        try:
            v = np.asarray(json.loads(text), dtype=np.float32)
        except (ValueError, TypeError):
            raise errors.SqlError(errors.INVALID_TEXT_REPRESENTATION,
                                  f"invalid vector literal: {text[:40]!r}")
    else:
        try:
            v = np.asarray(v, dtype=np.float32)
        except (ValueError, TypeError):
            raise errors.SqlError(errors.INVALID_TEXT_REPRESENTATION,
                                  "vector literal must be a flat array")
    if v.ndim != 1:
        raise errors.SqlError(errors.INVALID_TEXT_REPRESENTATION,
                              "vector literal must be a flat array")
    if dim is not None and len(v) != dim:
        raise errors.SqlError(errors.DATATYPE_MISMATCH,
                              f"expected {dim} dimensions, got {len(v)}")
    return v


def _infer_type(non_null: list) -> dt.SqlType:
    if not non_null:
        return dt.NULLTYPE
    if all(isinstance(v, bool) for v in non_null):
        return dt.BOOL
    if all(isinstance(v, int) and not isinstance(v, bool) for v in non_null):
        return dt.BIGINT
    if all(isinstance(v, (int, float)) and not isinstance(v, bool) for v in non_null):
        return dt.DOUBLE
    return dt.VARCHAR


def _encode_dictionary(strs: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
    """Sorted-unique dictionary encode: codes compare like the strings."""
    arr = np.asarray(strs, dtype=object)
    uniq, codes = np.unique(arr.astype(str), return_inverse=True)
    return uniq.astype(object), codes.astype(np.int32)


def merge_dictionaries(cols: Iterable[Column]) -> list[Column]:
    """Put VARCHAR columns on one shared sorted dictionary (needed before
    concatenating or comparing code spaces).

    The work is per DISTINCT dictionary object, not per column: slices,
    takes and filters of one column all carry the very same array, and a
    dictionary is sorted and unique by `Column`'s invariant, so when the
    columns hold one object between them they already share a code space
    and come back as they are — same `Column` objects, same dictionary
    object (caches keyed on it stay warm). With k > 1 distinct objects
    each is cast and remapped once, and every re-encoded output holds the
    one merged array. Columns with no dictionary pass through."""
    cols = list(cols)
    distinct = {id(c.dictionary): c.dictionary
                for c in cols if c.dictionary is not None}
    if not distinct:
        return cols
    if len(distinct) == 1:
        metrics.HOST_CONCAT_DICT_SHARED.add()
        return cols
    metrics.HOST_CONCAT_DICT_MERGED.add(len(distinct))
    as_str = {k: d.astype(str) for k, d in distinct.items()}
    merged = np.unique(np.concatenate(list(as_str.values())))
    remaps = {k: np.searchsorted(merged, s).astype(np.int32)
              for k, s in as_str.items()}
    merged = merged.astype(object)
    return [c if c.dictionary is None else
            Column(c.type, remaps[id(c.dictionary)][c.data], c.validity,
                   merged)
            for c in cols]


@dataclass
class Batch:
    """An ordered set of equal-length named columns."""

    names: list[str]
    columns: list[Column]
    _index: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        assert len(self.names) == len(self.columns)
        if self.columns:
            n = len(self.columns[0])
            assert all(len(c) == n for c in self.columns), "ragged batch"
        self._index = {n: i for i, n in enumerate(self.names)}

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, name: str) -> Column:
        return self.columns[self._index[name]]

    def __contains__(self, name: str) -> bool:
        return name in self._index

    @staticmethod
    def from_pydict(d: dict) -> "Batch":
        names = list(d.keys())
        cols = [v if isinstance(v, Column)
                else (Column.from_numpy(v) if isinstance(v, np.ndarray)
                      else Column.from_pylist(v))
                for v in d.values()]
        return Batch(names, cols)

    def to_pydict(self) -> dict:
        return {n: c.to_pylist() for n, c in zip(self.names, self.columns)}

    def take(self, indices: np.ndarray) -> "Batch":
        return Batch(list(self.names), [c.take(indices) for c in self.columns])

    def filter(self, mask: np.ndarray) -> "Batch":
        return self.take(np.flatnonzero(mask))

    def slice(self, start: int, stop: int) -> "Batch":
        return Batch(list(self.names), [c.slice(start, stop) for c in self.columns])

    def rows(self) -> list[tuple]:
        cols = [c.to_pylist() for c in self.columns]
        return list(zip(*cols)) if cols else []


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """One batch of all the rows, in order. A string column whose pieces
    share one dictionary object (the slices a scan yields) keeps that
    object and costs one copy of its codes; only pieces that bring
    distinct dictionaries are re-encoded (`merge_dictionaries`)."""
    batches = [b for b in batches if b.num_rows > 0] or list(batches[:1])
    if len(batches) == 1:
        return batches[0]
    # the request's `host_concat` stage: the copy of every column, read
    # or not, and the merge of string columns' distinct dictionaries
    with stage("host_concat"):
        names = batches[0].names
        out_cols = []
        for i, name in enumerate(names):
            cols = merge_dictionaries([b.columns[i] for b in batches])
            data = np.concatenate([c.data for c in cols])
            if any(c.validity is not None for c in cols):
                validity = np.concatenate([c.valid_mask() for c in cols])
            else:
                validity = None
            typ = next((c.type for c in cols
                        if c.type.id is not dt.TypeId.NULL), cols[0].type)
            # like the type: an untyped NULL piece has no dictionary
            dictionary = next((c.dictionary for c in cols
                               if c.dictionary is not None), None)
            out_cols.append(Column(typ, data, validity, dictionary))
        return Batch(list(names), out_cols)
