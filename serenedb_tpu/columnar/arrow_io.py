"""Batch ⇄ Arrow IPC bytes (WAL payloads, parquet snapshots).

Reference analog: DataChunk zstd-1 serde inside WAL INLINE ops
(reference: server/search/search_db_wal.h:50-205). Arrow IPC gives a
well-defined binary frame with zero-copy numeric columns; zstd applied by
the WAL layer."""

from __future__ import annotations

import io

import numpy as np
import pyarrow as pa

from . import dtypes as dt
from .column import Batch, Column


def batch_to_arrow(batch: Batch) -> pa.RecordBatch:
    arrays = []
    fields = []
    for name, col in zip(batch.names, batch.columns):
        mask = ~col.validity if col.validity is not None else None
        if col.type.is_string and col.dictionary is not None:
            arr = _decode_dictionary(col, mask)
        elif col.type.is_string:
            arr = pa.array(col.data.astype(str), type=pa.string(),
                           mask=mask)
        elif col.type.is_vector:
            arr = _vector_to_arrow(col, mask)
        elif col.type.id is dt.TypeId.TIMESTAMP:
            arr = pa.array(col.data, type=pa.timestamp("us"), mask=mask)
        elif col.type.id is dt.TypeId.DATE:
            arr = pa.array(col.data, type=pa.date32(), mask=mask)
        else:
            arr = pa.array(col.data, mask=mask)
        arrays.append(arr)
        fields.append(pa.field(name, arr.type))
    return pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields))


def _vector_to_arrow(col: Column, mask) -> pa.Array:
    """A VECTOR(n) column as FixedSizeList<float32>[n] over the column's
    own (rows, n) array: arrow wraps the buffer, no copy, no per-row
    object. A NULL row keeps its n zero slots, as the format wants."""
    flat = pa.array(np.ascontiguousarray(col.data).reshape(-1))
    # the ELEMENTS are declared non-nullable (a row is NULL whole or not
    # at all): parquet then keeps no definition level per number, which
    # reads twelve times and writes four times faster
    return pa.FixedSizeListArray.from_arrays(
        flat, type=pa.list_(pa.field("element", pa.float32(),
                                     nullable=False), col.type.dim),
        mask=None if mask is None else pa.array(mask))


def _decode_dictionary(col: Column, mask) -> pa.Array:
    """A dictionary-coded VARCHAR column as ONE arrow string array,
    decoded by arrow from (codes, dictionary) — work proportional to
    the distinct values plus the output, never a fixed-width unicode
    gather over every row (which pyarrow also hands back CHUNKED past a
    size threshold, and a RecordBatch takes no chunked column). 32-bit
    offsets cap a `string` array at 2 GiB of data; past that the column
    travels as `large_string`, which every reader here accepts."""
    values = pa.array(col.dictionary, type=pa.large_string())
    arr = values.take(pa.array(col.data, mask=mask))  # null code → null
    data = arr.buffers()[2]
    if data is None or data.size < (1 << 31) - 1:
        arr = arr.cast(pa.string())
    return arr


def batch_to_bytes(batch: Batch) -> bytes:
    rb = batch_to_arrow(batch)
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue()


def bytes_to_batch(data: bytes) -> Batch:
    from ..exec.tables import _arrow_to_column
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        tbl = r.read_all()
    names = list(tbl.schema.names)
    cols = [_arrow_to_column(tbl.column(n)) for n in names]
    return Batch(names, cols)


def write_parquet_snapshot(path: str, batch: Batch) -> None:
    import pyarrow.parquet as pq
    rb = batch_to_arrow(batch)
    pq.write_table(pa.Table.from_batches([rb]), path)


def read_parquet_snapshot(path: str) -> Batch:
    import pyarrow.parquet as pq
    from ..exec.tables import columns_parallel
    tbl = pq.read_table(path, use_threads=False)
    names = list(tbl.schema.names)
    cols = columns_parallel(tbl, names)
    return Batch(names, [cols[n] for n in names])
