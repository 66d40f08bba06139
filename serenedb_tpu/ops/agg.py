"""Aggregation kernels: scalar reductions and hash GROUP BY.

Reference analog: DuckDB's vectorized (perfect-)hash aggregate operators (the
reference gets these from its DuckDB fork; SURVEY.md §1 L3). TPU re-design:

- Scalar aggregates are XLA reductions over (rows, 128) tiles with the
  validity mask folded in — XLA fuses predicate + mask + reduce into one HBM
  pass, the ClickBench Q1 shape.
- GROUP BY operates on *group codes* (dense ints in [0, G)). Dictionary
  VARCHAR columns already carry dense codes; other keys are factorized
  host-side per batch (np.unique-style).
- Exactness policy (PG parity: SUM(int) is BIGINT): JAX x64 stays off and
  TPU has no fast int64, so device kernels produce int32/f32 partials that
  are provably exact for their shapes, and the host combines them in numpy
  int64. Integer SUM scatters four 8-bit limbs into int32 group accumulators
  (exact while each group sees < 2^31/255 ≈ 8.4M rows per call; the executor
  chunks input below that), or, for at most SMALL_SPACE groups, sums them
  as masked reductions (`group_reduce_masked`). Grouped COUNT (and the count half of SUM / AVG,
  and the DISTINCT presence table) over at most HIST_MAX_CELLS cells rides
  the MXU as a two-digit one-hot product (`group_count_hist`): 0/1 int8
  operands, int32 accumulation, so the counts are the scatter's integers.

All device entry points are jit-compiled with static group counts/ops.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

#: most cells (padded to `hist_shape`'s ladder) a grouped count takes as
#: the MXU histogram; past it the serial scatter stays. Measured on one
#: TPU v5e over 1,000,448 rows (chip run, PR 30), ms per execution,
#: histogram | scatter: 4,096 cells 0.22 | 8.40, 32,768 0.38 | 6.82,
#: 131,072 0.88 | 6.72, 262,144 1.58 | 6.72, 524,288 2.92 | 6.73,
#: 1,048,576 5.61 | 6.73 (and 2.3 s to compile): at the limit the
#: histogram is still twice as fast, one step past it a wash. Its MACs
#: grow as rows x cells and the scatter's time as rows alone, so the
#: crossover is a cell count whatever the table's size.
HIST_MAX_CELLS = 1 << 19
HIST_TILE_ROWS = 2048         # rows per grid step of the histogram kernel
SCATTER_SUM_MAX_ROWS = 4 << 20  # executor must chunk int-sum calls below this


# -- scalar reductions -----------------------------------------------------

@jax.jit
def masked_count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask, dtype=jnp.int32)


@jax.jit
def masked_sum_float(vals: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.sum(jnp.where(mask, vals, 0.0).astype(jnp.float32))


@jax.jit
def masked_sum_int_partials(vals: jax.Array, mask: jax.Array) -> jax.Array:
    """Per-tile-row int32 partial sums, split into 16-bit halves so each
    128-lane partial is exact in int32 for any int32 input (lo ≤ 128·65535,
    hi ≤ 128·2^15). Returns (rows, 2) [hi, lo]; host combines as
    (Σhi << 16) + Σlo in int64."""
    v = jnp.where(mask, vals, 0).astype(jnp.int32)
    lo = (v & 0xFFFF).astype(jnp.int32)
    hi = jnp.right_shift(v, 16)  # arithmetic shift: hi*2^16 + lo == v
    return jnp.stack([jnp.sum(hi, axis=1, dtype=jnp.int32),
                      jnp.sum(lo, axis=1, dtype=jnp.int32)], axis=1)


@functools.partial(jax.jit, static_argnames=("op",))
def masked_minmax(vals: jax.Array, mask: jax.Array, op: str) -> jax.Array:
    ident = _identity(vals.dtype, op)
    v = jnp.where(mask, vals, ident)
    return jnp.min(v) if op == "min" else jnp.max(v)


def masked_sum_int(vals: jax.Array, mask: jax.Array) -> int:
    parts = np.asarray(masked_sum_int_partials(vals, mask)).astype(np.int64)
    return int((parts[:, 0].sum() << 16) + parts[:, 1].sum())


def _identity(dtype, op):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.max if op == "min" else info.min
    return jnp.inf if op == "min" else -jnp.inf


# -- grouped aggregation ---------------------------------------------------

def hist_shape(num_cells: int) -> tuple[int, int]:
    """(H, L) of the accumulator that holds `num_cells` cells: H x L is the
    next power of two (at least 32 x 128, the int8 tile), split as evenly
    as a lane-dense L allows, since the one-hot work per row is H + L
    compares. Powers of two only, so that statements of near-equal group
    space share one kernel shape: eight shapes up to HIST_MAX_CELLS."""
    bits = max(12, (max(num_cells, 1) - 1).bit_length())
    lbits = max(7, (bits + 1) // 2)
    return 1 << (bits - lbits), 1 << lbits


def hist_form(num_cells: int) -> bool:
    """Does a grouped count over `num_cells` cells run as the histogram?
    Decided from the padded cell count alone, on a backend that lowers the
    kernel (Mosaic: a TPU); every other backend runs the scatter, which
    gives the same integers."""
    h, l = hist_shape(num_cells)
    return h * l <= HIST_MAX_CELLS and _lowers_hist()


def _lowers_hist() -> bool:
    return jax.default_backend() == "tpu"


def _hist_kernel(c_ref, out_ref, *, h: int, l: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = c_ref[...]                                   # (1, T) cell codes
    t = c.shape[1]
    hi = jnp.right_shift(c, l.bit_length() - 1)      # -1 (masked) stays -1
    lo = c & (l - 1)
    a = (jax.lax.broadcasted_iota(jnp.int32, (h, t), 0) == hi
         ).astype(jnp.int32).astype(jnp.int8)
    b = (jax.lax.broadcasted_iota(jnp.int32, (l, t), 0) == lo
         ).astype(jnp.int32).astype(jnp.int8)
    # counts[hi, lo] += onehot(hi) . onehot(lo)^T over this tile's rows
    out_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def group_count_hist(codes: jax.Array, mask: jax.Array,
                     num_groups: int) -> jax.Array:
    """Per-cell counts as a tiled MXU histogram: each row's code c splits
    into hi = c >> s and lo = c & (2^s - 1), and a (H, L) int32
    accumulator resident in VMEM across the row loop takes
    onehot(hi)^T . onehot(lo) tile by tile. A masked row's code is -1,
    whose hi matches no row of the accumulator. 0/1 int8 operands and
    int32 accumulation: exact. The kernel is Mosaic on a TPU and the
    Pallas interpreter elsewhere (tests)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    h, l = hist_shape(num_groups)
    c = jnp.where(mask.reshape(-1), codes.reshape(-1), -1).astype(jnp.int32)
    pad = (-c.shape[0]) % HIST_TILE_ROWS
    c = jnp.pad(c, (0, pad), constant_values=-1).reshape(1, -1)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, h=h, l=l),
        # under the mesh wrap's shard_map the counts vary as the rows do
        out_shape=jax.ShapeDtypeStruct((h, l), jnp.int32,
                                       vma=jax.typeof(c).vma),
        grid=(c.shape[1] // HIST_TILE_ROWS,),
        in_specs=[pl.BlockSpec((1, HIST_TILE_ROWS), lambda i: (0, i))],
        out_specs=pl.BlockSpec((h, l), lambda i: (0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=jax.default_backend() != "tpu",
        name="group_count_hist",
    )(c)
    return out.reshape(-1)[:num_groups]


@functools.partial(jax.jit, static_argnames=("num_groups",))
def group_count_scatter(codes: jax.Array, mask: jax.Array, num_groups: int) -> jax.Array:
    flat_codes = codes.reshape(-1)
    flat_mask = mask.reshape(-1)
    safe = jnp.where(flat_mask, flat_codes, 0)
    zero = jnp.zeros((num_groups,), dtype=jnp.int32)
    return zero.at[safe].add(flat_mask.astype(jnp.int32))


def group_count_cells(codes: jax.Array, mask: jax.Array,
                      num_groups: int) -> jax.Array:
    """int32[num_groups] counts of the unmasked rows per code, in the form
    `hist_form` picks: what the device programs call."""
    if hist_form(num_groups):
        return group_count_hist(codes, mask, num_groups)
    return group_count_scatter(codes, mask, num_groups)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def group_sum_float(codes: jax.Array, mask: jax.Array, vals: jax.Array,
                    num_groups: int) -> jax.Array:
    flat_codes = codes.reshape(-1)
    flat_mask = mask.reshape(-1)
    v = jnp.where(flat_mask, vals.reshape(-1), 0.0).astype(jnp.float32)
    safe = jnp.where(flat_mask, flat_codes, 0)
    return jnp.zeros((num_groups,), dtype=jnp.float32).at[safe].add(v)


INT32_RANGE = (-(1 << 31), (1 << 31) - 1)


def limb_count(lo: int, hi: int, w: int) -> tuple[int, bool]:
    """(limbs, whether a negative count rides along) of int32 values in
    [lo, hi] cut into w-bit limbs: a range that can be negative as its
    32-bit two's complement."""
    if lo < 0:
        return -(-32 // w), True
    return max(1, -(-int(hi).bit_length() // w)), False


def int_limbs(v: jax.Array, m: jax.Array, lo: int, hi: int,
              w: int) -> list:
    """The masked w-bit limbs of int32 values in [lo, hi], then the
    masked negative count where the range can be negative: their sums
    over a group are exact in int32 while (2^w - 1) x the group's rows
    stays under 2^31, and `combine_limbs` makes the int64 sum of them."""
    n, neg = limb_count(lo, hi, w)
    vu = jax.lax.bitcast_convert_type(v, jnp.uint32)
    out = [(jnp.right_shift(vu, w * limb) & jnp.uint32((1 << w) - 1))
           .astype(jnp.int32) * m for limb in range(n)]
    if neg:
        out.append((v < 0).astype(jnp.int32) * m)
    return out


def combine_limbs(sums: list, lo: int, hi: int, w: int) -> np.ndarray:
    """Exact int64 sums from the per-group sums of `int_limbs`'s columns."""
    n, neg = limb_count(lo, hi, w)
    total = np.zeros(len(sums[0]), dtype=np.int64)
    for limb in range(n):
        total += np.asarray(sums[limb]).astype(np.int64) << (w * limb)
    if neg:
        total -= np.asarray(sums[n]).astype(np.int64) << 32
    return total


#: group spaces reduced as masked reductions instead of a scatter,
#: PASS_GROUPS groups a pass over the rows
SMALL_SPACE = 256
PASS_GROUPS = 16
#: most rows whose 8-bit limbs one int32 group sum holds exactly
LIMB_ROWS_EXACT = 1 << 23


def group_reduce_masked(code: jax.Array, cols: list, extremes: list,
                        space: int):
    """Per-group sums of the int32 columns `cols` and the min / max of
    `extremes` [(func, identity, values)] over the rows whose code is in
    [0, space), as masked reductions, PASS_GROUPS groups a pass over the
    rows: XLA fuses a pass's reductions into one read of them. For a few
    groups this is many times the speed of a scatter, whose updates to
    one slot serialize (8.4M rows x 5 columns into 26 groups on one TPU
    v5e: 3.1 ms against 65). All arrays flat, one entry a row.
    Returns ((space, len(cols)) int32 sums, tuple of (space,) extremes)."""
    stacked = jnp.stack(cols, axis=1)
    width = min(space, PASS_GROUPS)
    passes = -(-space // width)

    def one_pass(base):
        """Groups base .. base + width - 1, one read of the rows."""
        sels = [code == base + g for g in range(width)]
        acc = jnp.stack([jnp.sum(jnp.where(s[:, None], stacked, 0), axis=0)
                         for s in sels])
        ex = tuple(jnp.stack([(jnp.min if f == "min" else jnp.max)(
            jnp.where(s, v, jnp.int32(ident))) for s in sels])
            for f, ident, v in extremes)
        return acc, ex
    if passes == 1:
        return one_pass(0)

    def body(p, carry):
        acc, ex = carry
        a, e = one_pass(p * width)
        at = p * width
        acc = jax.lax.dynamic_update_slice(acc, a, (at, 0))
        ex = tuple(jax.lax.dynamic_update_slice(x, y, (at,))
                   for x, y in zip(ex, e))
        return acc, ex
    n = passes * width
    init = (jnp.zeros((n, len(cols)), jnp.int32),
            tuple(jnp.full(n, ident, jnp.int32)
                  for _f, ident, _v in extremes))
    acc, ex = jax.lax.fori_loop(0, passes, body, init)
    return acc[:space], tuple(x[:space] for x in ex)


def group_sum_int_limbs_masked(codes: jax.Array, mask: jax.Array,
                               vals: jax.Array, num_groups: int) -> jax.Array:
    """`group_sum_int_limbs`' (G, 5) as masked reductions
    (`group_reduce_masked`), for at most SMALL_SPACE groups. Exact while
    the rows are at most LIMB_ROWS_EXACT."""
    v = vals.reshape(-1).astype(jnp.int32)
    m32 = mask.reshape(-1).astype(jnp.int32)
    cols = int_limbs(v, m32, *INT32_RANGE, 8)
    return group_reduce_masked(codes.reshape(-1), cols, [], num_groups)[0]


@functools.partial(jax.jit, static_argnames=("num_groups",))
def group_sum_int_limbs(codes: jax.Array, mask: jax.Array, vals: jax.Array,
                        num_groups: int) -> jax.Array:
    """Exact int sum via 8-bit limb scatter-adds of the two's-complement
    representation (`int_limbs`): sum(v) = Σ_i (limb_sum_i << 8i) −
    (neg_count << 32).

    Returns (G, 5) int32: four byte-limb sums + count of negative values.
    Exact while each group sees < 2^31/255 ≈ 8.4M rows per call (the
    executor chunks calls at SCATTER_SUM_MAX_ROWS).
    """
    flat_codes = codes.reshape(-1)
    flat_mask = mask.reshape(-1)
    v = vals.reshape(-1).astype(jnp.int32)
    safe = jnp.where(flat_mask, flat_codes, 0)
    m32 = flat_mask.astype(jnp.int32)
    out = jnp.zeros((num_groups, 5), dtype=jnp.int32)
    for limb, col in enumerate(int_limbs(v, m32, *INT32_RANGE, 8)):
        out = out.at[safe, limb].add(col)
    return out


def combine_sum_int_limbs(limbs: np.ndarray) -> np.ndarray:
    """(G,5) limb sums (+neg count) → exact int64 group sums. Accepts a
    chunked (C,G,5) array too (summed in int64 first)."""
    if limbs.ndim == 3:
        limbs = limbs.astype(np.int64).sum(axis=0)
    return combine_limbs([limbs[:, k] for k in range(5)], *INT32_RANGE, 8)


SCATTER_CHUNK_TILES = SCATTER_SUM_MAX_ROWS // 128


def group_sum_int_limbs_chunked(codes: jax.Array, mask: jax.Array,
                                vals: jax.Array, num_groups: int) -> jax.Array:
    """Row-chunked variant of group_sum_int_limbs for inputs whose per-group
    row count could exceed the int32 limb-accumulator bound (~8.4M rows).
    Returns (C, G, 5); combine_sum_int_limbs handles the extra axis."""
    r = codes.shape[0]
    c = -(-r // SCATTER_CHUNK_TILES)
    pad = c * SCATTER_CHUNK_TILES - r
    codes = jnp.pad(codes, ((0, pad), (0, 0)))
    mask = jnp.pad(mask, ((0, pad), (0, 0)))
    vals = jnp.pad(vals, ((0, pad), (0, 0)))
    shape = (c, SCATTER_CHUNK_TILES, codes.shape[1])

    def body(args):
        cc, mm, vv = args
        return group_sum_int_limbs(cc, mm, vv, num_groups)

    return jax.lax.map(body, (codes.reshape(shape), mask.reshape(shape),
                              vals.reshape(shape)))


@functools.partial(jax.jit, static_argnames=("num_groups", "op"))
def group_min_max(codes: jax.Array, mask: jax.Array, vals: jax.Array,
                  num_groups: int, op: str) -> jax.Array:
    flat_codes = codes.reshape(-1)
    flat_mask = mask.reshape(-1)
    v = vals.reshape(-1)
    ident = _identity(v.dtype, op)
    v = jnp.where(flat_mask, v, ident)
    safe = jnp.where(flat_mask, flat_codes, 0)
    init = jnp.full((num_groups,), ident, dtype=v.dtype)
    return init.at[safe].min(v) if op == "min" else init.at[safe].max(v)


# -- host-facing grouped API ----------------------------------------------

def group_count(codes: jax.Array, mask: jax.Array, num_groups: int) -> np.ndarray:
    return np.asarray(group_count_cells(codes, mask, num_groups)).astype(np.int64)


def group_sum_int(codes: jax.Array, mask: jax.Array, vals: jax.Array,
                  num_groups: int) -> np.ndarray:
    """Exact per-group int64 sums (limb decomposition, see
    group_sum_int_limbs)."""
    limbs = group_sum_int_limbs(codes, mask, vals, num_groups)
    return combine_sum_int_limbs(np.asarray(limbs))


def group_min(codes, mask, vals, num_groups) -> np.ndarray:
    return np.asarray(group_min_max(codes, mask, vals, num_groups, "min"))


def group_max(codes, mask, vals, num_groups) -> np.ndarray:
    return np.asarray(group_min_max(codes, mask, vals, num_groups, "max"))


# -- host-side key factorization ------------------------------------------

def factorize_keys(key_arrays: list[np.ndarray],
                   valids: list[Optional[np.ndarray]]) -> tuple[np.ndarray, list[np.ndarray], np.ndarray]:
    """Composite GROUP BY keys → dense codes.

    Returns (codes int32 [n], unique_key_value_columns, unique_valid (k, G)).
    NULL keys group together (PG GROUP BY semantics). Host-side O(n log n).
    """
    n = len(key_arrays[0])
    rows = []
    for arr, valid in zip(key_arrays, valids):
        a = np.asarray(arr)
        if a.dtype == np.bool_:
            a = a.astype(np.int8)
        if valid is not None:
            a = np.where(valid, a, np.zeros((), dtype=a.dtype))
            rows.append((~valid).astype(a.dtype))
        else:
            rows.append(np.zeros(n, dtype=a.dtype))
        rows.append(a)
    composite = np.stack(rows) if rows else np.zeros((0, n))
    first_idx, inverse = _unique_columns(composite)
    codes = inverse.astype(np.int32)
    uniq_cols = [np.asarray(arr)[first_idx] for arr in key_arrays]
    uniq_valid = np.stack(
        [v[first_idx] if v is not None else np.ones(len(first_idx), dtype=bool)
         for v in valids]) if valids else np.ones((0, len(first_idx)), dtype=bool)
    return codes, uniq_cols, uniq_valid


def factorize_codes(key_arrays: list[np.ndarray],
                    valids: list[Optional[np.ndarray]]
                    ) -> tuple[np.ndarray, int]:
    """Composite keys → (dense int64 codes, group count), skipping the
    unique-key-value materialization `factorize_keys` does — the join /
    set-op / DISTINCT ON consumers only need the equality classes.

    Equality semantics match the legacy row-tuple tier exactly: NULL keys
    group together (set ops / DISTINCT ON treat NULL = NULL; the join
    masks NULL-key rows out separately so NULL never matches), and every
    NaN occurrence is its own group (the lexsort `!=` comparison keeps
    NaN ≠ NaN, the same way python tuple equality does). Each key
    factorizes in its OWN dtype and only the resulting int64 code rows
    stack — a composite mixing int64 and float keys must never promote
    the ints to float64, where values beyond 2**53 would collapse.
    """
    code_rows = []
    for arr, valid in zip(key_arrays, valids):
        a = np.asarray(arr)
        if a.dtype == np.bool_:
            a = a.astype(np.int8)
        rows = []
        if valid is not None:
            a = np.where(valid, a, np.zeros((), dtype=a.dtype))
            rows.append((~valid).astype(a.dtype))
        rows.append(a)
        _, codes_k = _unique_columns(np.stack(rows))
        code_rows.append(codes_k)
    if not code_rows:
        return np.zeros(0, dtype=np.int64), 0
    if len(code_rows) == 1:
        inverse = code_rows[0]
        return inverse, int(inverse.max()) + 1 if len(inverse) else 0
    first_idx, inverse = _unique_columns(np.stack(code_rows))
    return inverse, len(first_idx)


def _unique_columns(composite: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Unique over columns of a (k, n) matrix → (first-occurrence idx, inverse)."""
    n = composite.shape[1]
    if n == 0:
        return np.array([], dtype=np.int64), np.array([], dtype=np.int64)
    order = np.lexsort(composite[::-1])
    sorted_cols = composite[:, order]
    neq = np.any(sorted_cols[:, 1:] != sorted_cols[:, :-1], axis=0)
    group_of_sorted = np.concatenate([[0], np.cumsum(neq)])
    inverse = np.empty(n, dtype=np.int64)
    inverse[order] = group_of_sorted
    first_idx = np.empty(int(group_of_sorted[-1]) + 1, dtype=np.int64)
    first_idx[group_of_sorted[::-1]] = order[::-1]
    return first_idx, inverse
