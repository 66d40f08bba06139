"""Host↔device movement of column batches.

The reference has no device boundary (single-process C++; SURVEY.md §5.8) —
this module *is* the new architecture's offload seam. Columns go to HBM as
2-D (rows/LANES, LANES) tiles so Pallas kernels see lane-aligned data:

- 1-D column of n rows → padded to a multiple of BLOCK_ROWS = 8*128 = 1024,
  reshaped to (n_pad // 128, 128). float64 is narrowed to float32 on device
  (analytics kernels accumulate in f32/i64; exact-parity paths stay on CPU).
- validity travels as a mask array of the same shape (True = valid row);
  padding rows are invalid.

`DeviceColumn` carries the logical length so kernels can mask the tail.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtypes as dt
from .column import Batch, Column

LANES = 128
SUBLANES = 8
BLOCK_ROWS = LANES * SUBLANES  # 1024: one (8,128) f32 tile worth of rows


def pad_len(n: int, multiple: int = BLOCK_ROWS) -> int:
    return max(multiple, ((n + multiple - 1) // multiple) * multiple)


_DEVICE_DTYPE = {
    np.dtype(np.bool_): jnp.int8,     # bool as i8 lanes (mask math)
    np.dtype(np.int8): jnp.int8,
    np.dtype(np.int16): jnp.int32,
    np.dtype(np.int32): jnp.int32,
    np.dtype(np.int64): jnp.int32,    # see note below
    np.dtype(np.float32): jnp.float32,
    np.dtype(np.float64): jnp.float32,
}


@dataclass
class DeviceColumn:
    """A column resident on device as (n_pad/128, 128) tiles.

    Integer tiles whose value RANGE fits 8/16 bits ship compressed as
    frame-of-reference deltas (scheme 'for8'/'for16': stored = value -
    offset in uint8/uint16) and decode in-kernel with one add — a 2-4×
    HBM footprint cut on the analytics working set (reference analog:
    the adaptive-compressed column formats of
    libs/iresearch/include/iresearch/formats/column/). Consumers that
    need the logical values call decode(x) on the gathered tiles."""

    type: dt.SqlType
    data: jax.Array                 # 2-D (rows, LANES)
    mask: jax.Array                 # 2-D bool, same shape; False on padding
    length: int                     # logical row count
    scheme: str = "raw"             # raw | for8 | for16
    offset: int = 0                 # frame of reference (for8/for16)
    wide: Optional[jax.Array] = None  # optional i64-precision residual (unused yet)

    @property
    def padded_rows(self) -> int:
        return self.data.shape[0] * LANES

    def decode(self, tiles: jax.Array) -> jax.Array:
        """Decompress (a slice of) this column's tiles to logical values
        — traced inside jitted programs; one widen + add."""
        if self.scheme == "raw":
            return tiles
        return tiles.astype(jnp.int32) + jnp.int32(self.offset)


class DeviceNarrowingError(ValueError):
    """A column cannot be represented exactly on device (e.g. int64 values
    outside int32 range with x64 off). Callers treat this like a
    NotCompilable: fall back to the exact CPU path. Silently narrowing to
    f32 would make device SUM/compare results diverge from CPU — a parity
    violation, not an optimization."""


def _narrow_exact(arr: np.ndarray, n: int) -> np.ndarray:
    """int64 → int32 when provably exact (TPU x64 is off); raises
    DeviceNarrowingError otherwise — shared by tile conversion paths."""
    if arr.dtype == np.dtype(np.int64):
        if n == 0 or (np.abs(arr, dtype=np.float64).max(initial=0.0) < 2**31):
            return arr.astype(np.int32)
        raise DeviceNarrowingError(
            "int64 column with |values| >= 2^31: no exact device "
            "representation")
    return arr


#: raw-scheme host dtype per source dtype (the numpy mirror of
#: _DEVICE_DTYPE, for tiles built host-side before a stacked upload)
_HOST_TILE_DTYPE = {
    np.dtype(np.bool_): np.int8,
    np.dtype(np.int8): np.int8,
    np.dtype(np.int16): np.int32,
    np.dtype(np.int32): np.int32,
    np.dtype(np.float32): np.float32,
    np.dtype(np.float64): np.float32,
}


def host_tile_arrays(col: Column, rows_pad: int, scheme: str = "raw",
                     offset: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """HOST-side tile arrays of one column padded to exactly `rows_pad`
    rows: (data (rows_pad/LANES, LANES), mask bool same shape). The
    sharded tier's stacked collective programs need an IDENTICAL
    dtype/offset for every shard slice of a column, so the caller
    decides the frame-of-reference scheme ONCE (from whole-column
    stats) and passes it in — 'for8'/'for16' store value - offset as
    uint8/uint16 (the to_device_column compression, decoded in-kernel
    with one widen + add), 'raw' ships the device dtype unchanged."""
    n = len(col)
    assert rows_pad % LANES == 0 and rows_pad >= n
    arr = _narrow_exact(col.data, n)
    if scheme == "for8":
        arr = (arr.astype(np.int64) - offset).astype(np.uint8)
        np_dt = np.uint8
    elif scheme == "for16":
        arr = (arr.astype(np.int64) - offset).astype(np.uint16)
        np_dt = np.uint16
    else:
        np_dt = _HOST_TILE_DTYPE.get(arr.dtype, np.float32)
    padded = np.zeros(rows_pad, dtype=np_dt)
    padded[:n] = arr.astype(np_dt, copy=False)
    mask = np.zeros(rows_pad, dtype=bool)
    mask[:n] = col.valid_mask()
    return padded.reshape(-1, LANES), mask.reshape(-1, LANES)


def to_device_column(col: Column, pad_multiple: int = BLOCK_ROWS) -> DeviceColumn:
    n = len(col)
    n_pad = pad_len(n, pad_multiple)
    arr = _narrow_exact(col.data, n)
    dev_dt = _DEVICE_DTYPE.get(arr.dtype, jnp.float32)
    scheme, offset = "raw", 0
    if arr.dtype.kind == "i" and arr.dtype.itemsize > 1 and n:
        # frame-of-reference narrowing: range-fitting int tiles ship as
        # uint8/uint16 deltas and decode in-kernel (+offset)
        vmin = int(arr.min())
        vmax = int(arr.max())
        rng = vmax - vmin
        if rng < (1 << 8):
            scheme, offset, dev_dt = "for8", vmin, jnp.uint8
            arr = (arr.astype(np.int64) - vmin).astype(np.uint8)
        elif rng < (1 << 16):
            scheme, offset, dev_dt = "for16", vmin, jnp.uint16
            arr = (arr.astype(np.int64) - vmin).astype(np.uint16)
    padded = np.zeros(n_pad, dtype=arr.dtype)
    padded[:n] = arr
    mask = np.zeros(n_pad, dtype=bool)
    mask[:n] = col.valid_mask()
    import time as _time

    from ..obs import device as _obsdev
    t0 = _time.perf_counter_ns() if _obsdev.enabled() else 0
    data2d = jnp.asarray(padded.reshape(-1, LANES), dtype=dev_dt)
    mask2d = jnp.asarray(mask.reshape(-1, LANES))
    if t0:
        # every device path funnels through this upload: per-device
        # transfer byte/time attribution happens exactly once, here
        _obsdev.note_upload(
            int(data2d.size * data2d.dtype.itemsize) + int(mask2d.size),
            _obsdev.array_device_ids(data2d),
            _time.perf_counter_ns() - t0)
    # note that the backend is up: serene_shard_combine=auto's PASSIVE
    # device-count probe (parallel/mesh.py) reads this flag
    from ..parallel import mesh as _mesh
    _mesh.note_backend_initialized()
    return DeviceColumn(col.type, data2d, mask2d, n, scheme, offset)


def commit_host_array(arr: np.ndarray):
    """Upload one raw host array through the accounted choke point —
    the non-Column sibling of to_device_column for device subsystems
    that ship bare numpy payloads (the posting pool's staged pages and
    batch descriptor tables). Same ledger contract: per-device transfer
    byte/time attribution happens exactly once, here."""
    import time as _time

    from ..obs import device as _obsdev
    t0 = _time.perf_counter_ns() if _obsdev.enabled() else 0
    dev = jnp.asarray(arr)
    if t0:
        _obsdev.note_upload(int(dev.size * dev.dtype.itemsize),
                            _obsdev.array_device_ids(dev),
                            _time.perf_counter_ns() - t0)
    return dev


def to_device_batch(batch: Batch, columns: Optional[list[str]] = None) -> dict:
    names = columns if columns is not None else batch.names
    return {name: to_device_column(batch.column(name)) for name in names}
