import numpy as np
import pytest

from serenedb_tpu.columnar import Column, to_device_column
from serenedb_tpu.ops import agg


def dev(vals, validity=None):
    c = Column.from_numpy(np.asarray(vals), validity=validity)
    return to_device_column(c)


def test_masked_count_and_sum_int():
    dc = dev(np.arange(1000, dtype=np.int64))
    assert int(agg.masked_count(dc.mask)) == 1000
    assert agg.masked_sum_int(dc.decode(dc.data), dc.mask) == 499500


def test_masked_sum_int_negative_and_large():
    rng = np.random.default_rng(0)
    vals = rng.integers(-2**30, 2**30, size=5000, dtype=np.int64)
    dc = dev(vals)
    assert agg.masked_sum_int(dc.decode(dc.data), dc.mask) == int(vals.sum())


def test_masked_sum_float_and_minmax():
    vals = np.array([1.5, -2.0, 3.25, 100.0], dtype=np.float64)
    dc = dev(vals)
    assert float(agg.masked_sum_float(dc.data, dc.mask)) == pytest.approx(102.75)
    assert float(agg.masked_minmax(dc.data, dc.mask, "min")) == -2.0
    assert float(agg.masked_minmax(dc.data, dc.mask, "max")) == 100.0


def test_nulls_excluded():
    validity = np.array([True, False, True, True])
    dc = dev(np.array([10, 99, 20, 30], dtype=np.int64), validity)
    assert int(agg.masked_count(dc.mask)) == 3
    assert agg.masked_sum_int(dc.decode(dc.data), dc.mask) == 60


@pytest.mark.parametrize("num_groups", [3, 2000])  # onehot path and scatter path
def test_group_count_paths(num_groups):
    rng = np.random.default_rng(1)
    codes_np = rng.integers(0, num_groups, size=4000).astype(np.int64)
    dc = dev(codes_np)
    counts = agg.group_count(dc.decode(dc.data), dc.mask, num_groups)
    expected = np.bincount(codes_np, minlength=num_groups)
    np.testing.assert_array_equal(counts, expected)


def test_group_sum_int_exact_with_negatives():
    rng = np.random.default_rng(2)
    g = 17
    codes_np = rng.integers(0, g, size=3000).astype(np.int64)
    vals_np = rng.integers(-2**30, 2**30, size=3000, dtype=np.int64)
    dcodes, dvals = dev(codes_np), dev(vals_np)
    sums = agg.group_sum_int(dcodes.data, dcodes.mask, dvals.data, g)
    expected = np.zeros(g, dtype=np.int64)
    np.add.at(expected, codes_np, vals_np)
    np.testing.assert_array_equal(sums, expected)


@pytest.mark.parametrize("lo,hi,w,rows", [
    (0, 10_494_950, 28, 7),            # a TPC-H order's lines, 28-bit limbs
    (-500_000_000, 1_049_495_000, 8, 4000),
    (0, 255, 8, 4000),                 # one limb
    (-(1 << 31), (1 << 31) - 1, 8, 4000),
])
def test_int_limbs_sum_exactly(lo, hi, w, rows):
    """`int_limbs` cut int32 values into w-bit limbs (and a negative count)
    whose int32 sums `combine_limbs` turns into the exact int64 sum."""
    import jax.numpy as jnp
    rng = np.random.default_rng(rows + w)
    vals = rng.integers(lo, hi, size=rows, endpoint=True, dtype=np.int64)
    vals[:2] = [lo, hi]
    keep = rng.random(rows) < 0.8
    cols = agg.int_limbs(jnp.asarray(vals.astype(np.int32)),
                         jnp.asarray(keep.astype(np.int32)), lo, hi, w)
    n, neg = agg.limb_count(lo, hi, w)
    assert len(cols) == n + neg
    sums = [np.asarray(c).sum(dtype=np.int32)[None] for c in cols]
    assert int(agg.combine_limbs(sums, lo, hi, w)[0]) == int(vals[keep].sum())


@pytest.mark.parametrize("groups", [1, 12, 16, 208])
def test_masked_group_sums_match_the_scatter(groups):
    """`group_sum_int_limbs_masked` (a pass of masked reductions per 16
    groups) gives `group_sum_int_limbs`' limb sums, NULL rows left out."""
    import jax.numpy as jnp
    rng = np.random.default_rng(groups)
    n = 128 * 40
    codes = jnp.asarray(rng.integers(0, groups, n).astype(np.int32))
    vals = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.7)
    want = np.asarray(agg.group_sum_int_limbs(codes, mask, vals, groups))
    got = np.asarray(agg.group_sum_int_limbs_masked(codes, mask, vals,
                                                    groups))
    assert (got == want).all()
    sums = agg.combine_sum_int_limbs(got)
    v, c, m = np.asarray(vals).astype(np.int64), np.asarray(codes), \
        np.asarray(mask)
    assert list(sums) == [int(v[m & (c == g)].sum()) for g in range(groups)]


def test_group_min_max_and_float_sum():
    codes_np = np.array([0, 1, 0, 1, 2], dtype=np.int64)
    vals_np = np.array([5.0, -1.0, 3.0, 7.0, 0.5])
    dcodes, dvals = dev(codes_np), dev(vals_np)
    mn = agg.group_min(dcodes.data, dcodes.mask, dvals.data, 3)
    mx = agg.group_max(dcodes.data, dcodes.mask, dvals.data, 3)
    s = np.asarray(agg.group_sum_float(dcodes.data, dcodes.mask, dvals.data, 3))
    assert mn[:3].tolist() == [3.0, -1.0, 0.5]
    assert mx[:3].tolist() == [5.0, 7.0, 0.5]
    np.testing.assert_allclose(s[:3], [8.0, 6.0, 0.5])


def test_factorize_composite_keys_with_nulls():
    a = np.array([1, 1, 2, 1], dtype=np.int64)
    b = np.array([7, 7, 7, 8], dtype=np.int64)
    valid_b = np.array([True, True, True, False])
    codes, uniq, uniq_valid = agg.factorize_keys([a, b], [None, valid_b])
    # groups: (1,7), (1,7), (2,7), (1,NULL) → 3 groups
    assert codes[0] == codes[1]
    assert len(set(codes.tolist())) == 3
    assert len(uniq[0]) == 3
    # the NULL group's b-validity is False
    null_group = codes[3]
    assert not uniq_valid[1][null_group]


def test_factorize_empty():
    codes, uniq, uniq_valid = agg.factorize_keys(
        [np.array([], dtype=np.int64)], [None])
    assert len(codes) == 0
    assert len(uniq[0]) == 0
