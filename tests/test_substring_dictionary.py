"""substring(x, start[, length]) of a dictionary-coded column with constant
bounds (functions/scalar.py: `_substring_of_dictionary`): against Python's
slicing row by row, and sized by the batch, not by its dictionary."""

import numpy as np
import pytest

from serenedb_tpu.columnar import dtypes as dt
from serenedb_tpu.columnar.column import Column
from serenedb_tpu.functions import scalar as fnlib

WORDS = np.array(sorted(["", "a", "ab", "héllo", "日本語テキスト",
                         "13-555-101-0101", "31-777-202-0202", "zz"]),
                 dtype=object)


def _substring(src: Column, *bounds):
    args = [src] + [Column(dt.BIGINT, np.full(len(src.data), b, np.int64))
                    for b in bounds]
    res = fnlib.resolve("substring", [dt.VARCHAR] + [dt.BIGINT] * len(bounds))
    return res.impl(args, len(src.data))


def _want(values, start, length=None):
    st = start - 1
    return [None if v is None else
            v[st:] if length is None else v[st:st + length] for v in values]


@pytest.mark.parametrize("bounds", [(1, 2), (2, 3), (1, 0), (9, 4), (1,),
                                    (3,), (20, 1)],
                         ids=["prefix", "middle", "empty", "past_the_end",
                              "from_only", "from_only_inner", "beyond"])
def test_equals_slicing_each_row(bounds):
    codes = np.array([0, 3, 4, 5, 1, 7, 3, 6, 2, 4], dtype=np.int32)
    valid = np.ones(len(codes), dtype=bool)
    valid[6] = False
    src = Column(dt.VARCHAR, codes, valid, WORDS)
    out = _substring(src, *bounds)
    values = [None if not ok else WORDS[c] for c, ok in zip(codes, valid)]
    assert out.to_pylist() == _want(values, *bounds)
    # a dictionary stays sorted and unique
    assert list(out.dictionary) == sorted(set(out.dictionary))


def test_a_small_batch_of_a_long_text_dictionary(monkeypatch):
    """Ten rows of a column whose dictionary holds 200,000 texts of 44
    characters: only the ten rows' texts are sliced."""
    import pyarrow as pa
    rng = np.random.default_rng(7)
    letters = np.array(list("abcdefghij"))
    texts = np.array(sorted({"".join(rng.choice(letters, 44))
                             for _ in range(200_000)}), dtype=object)
    codes = rng.integers(0, len(texts), 10).astype(np.int32)
    src = Column(dt.VARCHAR, codes, None, texts)
    sizes = []
    real = pa.array

    def array(values, *a, **k):
        sizes.append(len(values))
        return real(values, *a, **k)
    with monkeypatch.context() as m:
        m.setattr(pa, "array", array)
        out = _substring(src, 5, 10)
    assert sizes and max(sizes) <= 10
    assert out.to_pylist() == _want(list(texts[codes]), 5, 10)
    assert len(out.dictionary) <= 10


def test_a_non_constant_bound_takes_the_row_path():
    codes = np.array([0, 1, 2], dtype=np.int32)
    words = np.array(["abc", "defg", "hi"], dtype=object)
    src = Column(dt.VARCHAR, codes, None, words)
    start = Column(dt.BIGINT, np.array([1, 2, 1], np.int64))
    res = fnlib.resolve("substring", [dt.VARCHAR, dt.BIGINT])
    assert fnlib._substring_of_dictionary([src, start]) is None
    assert res.impl([src, start], 3).to_pylist() == ["abc", "efg", "hi"]
