import numpy as np
import pytest

from serenedb_tpu.columnar import (Batch, Column, concat_batches, dtypes,
                                   to_device_column)


def test_int_column_roundtrip():
    c = Column.from_pylist([1, 2, None, 4])
    assert c.type == dtypes.BIGINT
    assert c.to_pylist() == [1, 2, None, 4]
    assert c.has_nulls


def test_string_dictionary_sorted_codes_compare_like_strings():
    c = Column.from_pylist(["pear", "apple", "pear", None, "banana"])
    assert c.type == dtypes.VARCHAR
    assert c.to_pylist() == ["pear", "apple", "pear", None, "banana"]
    # sorted dictionary: code order == lexicographic order
    d = list(c.dictionary)
    assert d == sorted(d)
    codes = c.data
    assert (codes[0] > codes[1]) == ("pear" > "apple")


def test_filter_take_slice():
    b = Batch.from_pydict({"a": [1, 2, 3, 4], "s": ["x", "y", "z", "w"]})
    f = b.filter(np.array([True, False, True, False]))
    assert f.to_pydict() == {"a": [1, 3], "s": ["x", "z"]}
    assert b.slice(1, 3).to_pydict() == {"a": [2, 3], "s": ["y", "z"]}


def test_concat_merges_dictionaries():
    b1 = Batch.from_pydict({"s": ["b", "a"]})
    b2 = Batch.from_pydict({"s": ["c", "a"]})
    c = concat_batches([b1, b2])
    assert c.to_pydict() == {"s": ["b", "a", "c", "a"]}
    col = c.column("s")
    assert list(col.dictionary) == ["a", "b", "c"]


def test_device_column_padding_and_mask():
    c = Column.from_pylist(list(range(10)))
    dc = to_device_column(c)
    assert dc.data.shape == (8, 128)
    assert dc.length == 10
    assert int(dc.mask.sum()) == 10
    np.testing.assert_array_equal(
        np.asarray(dc.data).reshape(-1)[:10], np.arange(10))


def test_device_column_nulls_not_in_mask():
    c = Column.from_pylist([1, None, 3])
    dc = to_device_column(c)
    m = np.asarray(dc.mask).reshape(-1)
    assert m[:3].tolist() == [True, False, True]


def test_numpy_column_infers_type():
    c = Column.from_numpy(np.array([1.5, 2.5], dtype=np.float64))
    assert c.type == dtypes.DOUBLE
    c32 = Column.from_numpy(np.array([1, 2], dtype=np.int32))
    assert c32.type == dtypes.INT


def test_common_numeric_widening():
    assert dtypes.common_numeric(dtypes.INT, dtypes.DOUBLE) == dtypes.DOUBLE
    assert dtypes.common_numeric(dtypes.BOOL, dtypes.BIGINT) == dtypes.BIGINT
    with pytest.raises(TypeError):
        dtypes.common_numeric(dtypes.VARCHAR, dtypes.INT)


def test_arrow_ipc_roundtrip_of_wide_string_column():
    """A VARCHAR column past pyarrow's ~64 MB numpy-unicode conversion
    chunk (10k rows of 2k-char JSON-ish text — the vector column shape)
    must still serialize as ONE record batch: the WAL and snapshot
    writers take no chunked column. NULLs and repeats survive."""
    from serenedb_tpu.columnar.arrow_io import (batch_to_arrow,
                                                batch_to_bytes,
                                                bytes_to_batch)
    vals = [None if i % 97 == 0 else
            f"{i % 5000:05d}" + "x" * 2000 for i in range(10_000)]
    b = Batch(["k", "s"], [Column.from_numpy(np.arange(10_000)),
                           Column.from_pylist(vals, dtypes.VARCHAR)])
    rb = batch_to_arrow(b)
    assert rb.num_rows == 10_000 and str(rb.schema.field("s").type) == \
        "string"
    back = bytes_to_batch(batch_to_bytes(b))
    assert back.column("s").to_pylist() == vals
    assert back.column("k").to_pylist() == list(range(10_000))
