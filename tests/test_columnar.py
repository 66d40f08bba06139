import numpy as np
import pytest

from serenedb_tpu.columnar import (Batch, Column, concat_batches, dtypes,
                                   merge_dictionaries, to_device_column)
from serenedb_tpu.engine import Database
from serenedb_tpu.exec.tables import DEFAULT_BATCH_ROWS
from serenedb_tpu.obs.trace import FLIGHT
from serenedb_tpu.utils import metrics as sdb_metrics


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


# -- merge_dictionaries: per distinct dictionary object, not per piece -------


def _reference_merge(cols):
    """merge_dictionaries as it was before it looked at object identity
    (one cast, one searchsorted and one object copy PER COLUMN): the
    plain reference the new one has to agree with."""
    cols = list(cols)
    dicts = [c.dictionary for c in cols if c.dictionary is not None]
    if not dicts:
        return cols
    merged = np.unique(np.concatenate([d.astype(str) for d in dicts]))
    out = []
    for c in cols:
        if c.dictionary is None:
            out.append(c)
            continue
        remap = np.searchsorted(merged, c.dictionary.astype(str)).astype(
            np.int32)
        out.append(Column(c.type, remap[c.data], c.validity,
                          merged.astype(object)))
    return out


def _shared_gauges():
    return (sdb_metrics.HOST_CONCAT_DICT_SHARED.value,
            sdb_metrics.HOST_CONCAT_DICT_MERGED.value)


_PHRASES = ["pear", "apple", None, "fig", "apple", "", "kiwi", "date",
            None, "pear", "lime", "fig"]


def _pieces_of_one_column(how):
    col = Column.from_pylist(_PHRASES)
    n = len(col)
    cuts = {
        "slice": [col.slice(0, 5), col.slice(5, 6), col.slice(6, n)],
        "take": [col.take(np.array([11, 0, 3])), col.take(np.array([2])),
                 col.take(np.array([5, 5, 8, 1]))],
        "filter": [col.filter(np.arange(n) % 2 == 0),
                   col.filter(np.arange(n) % 3 == 1)],
        "mixed": [col.slice(2, 9).filter(np.arange(7) % 2 == 1),
                  col.take(np.array([8, 2])),       # an all-NULL piece
                  col.slice(0, 4).take(np.array([3, 0]))],
    }
    return col, cuts[how]


@pytest.mark.parametrize("how", ["slice", "take", "filter", "mixed"])
def test_pieces_of_one_column_concat_without_reencoding(how):
    col, pieces = _pieces_of_one_column(how)
    shared0, merged0 = _shared_gauges()
    out = merge_dictionaries(pieces)
    assert all(o is p for o, p in zip(out, pieces))
    assert _shared_gauges() == (shared0 + 1, merged0)
    got = concat_batches([Batch(["s"], [p]) for p in pieces]).column("s")
    assert got.dictionary is col.dictionary
    ref = _reference_merge(pieces)
    want = [v for r in ref for v in r.to_pylist()]
    assert got.to_pylist() == want == [v for p in pieces
                                       for v in p.to_pylist()]
    assert got.valid_mask().tolist() == [v is not None for v in want]
    assert list(got.dictionary) == list(ref[0].dictionary)


def _random_string_columns(seed):
    """n columns over k distinct dictionary objects (k = 1..n), with
    strings repeated across dictionaries, empty dictionaries (0 rows),
    all-NULL columns and untyped NULL columns that have no dictionary."""
    rng = np.random.default_rng(seed)
    pool = ["", "a", "ab", "b", "ba", "é", "zz", "Z", " x", "10", "9"]
    n = int(rng.integers(2, 8))
    k = 1 + seed % n                 # every k is reached over the seeds
    sources = []
    for _ in range(k):
        size = int(rng.integers(0, len(pool) + 1))
        words = rng.choice(pool, size=size, replace=False).tolist()
        rows = int(rng.integers(1, 30)) if words else 0
        valid = rng.random(rows) > 0.3
        sources.append(Column.from_pylist(
            [words[int(rng.integers(len(words)))] if v else None
             for v in valid], dtypes.VARCHAR))
    cols = []
    for i in range(n):
        src = sources[i] if i < k else sources[int(rng.integers(k))]
        kind = rng.integers(4)
        if kind == 0 or len(src) == 0:
            cols.append(src)
        elif kind == 1:
            a, b = sorted(rng.integers(0, len(src) + 1, 2).tolist())
            cols.append(src.slice(a, b))
        elif kind == 2:
            cols.append(src.take(rng.integers(0, len(src), 12)))
        else:                        # all NULL, codes still in range
            cols.append(Column(src.type, np.zeros(5, np.int32),
                               np.zeros(5, bool), src.dictionary))
    for _ in range(int(rng.integers(0, 3))):
        cols.insert(int(rng.integers(0, len(cols) + 1)),
                    Column.const(None, 3, dtypes.NULLTYPE))
    return cols, k


@pytest.mark.parametrize("seed", range(28))
def test_merge_matches_reference_for_k_distinct_dictionaries(seed):
    cols, k = _random_string_columns(seed)
    assert len({id(c.dictionary) for c in cols
                if c.dictionary is not None}) == k
    shared0, merged0 = _shared_gauges()
    out = merge_dictionaries(cols)
    ref = _reference_merge(cols)
    assert _shared_gauges() == ((shared0 + 1, merged0) if k == 1
                                else (shared0, merged0 + k))
    assert [o.to_pylist() for o in out] == [r.to_pylist() for r in ref]
    assert [o.type for o in out] == [c.type for c in cols]
    encoded = [o for o in out if o.dictionary is not None]
    merged = encoded[0].dictionary
    assert all(o.dictionary is merged for o in encoded)
    assert merged.dtype == object
    as_list = list(merged)
    assert as_list == sorted(set(as_list))
    assert as_list == list(next(r.dictionary for r in ref
                                if r.dictionary is not None))
    for o, c in zip(out, cols):
        if c.dictionary is None or k == 1:
            assert o is c
    got = concat_batches([Batch(["s"], [c]) for c in cols]).column("s")
    rows = [v for c in cols if len(c) for v in c.to_pylist()]
    assert got.to_pylist() == rows
    if any(len(c) and c.dictionary is not None for c in cols):
        assert got.type == dtypes.VARCHAR


@pytest.mark.parametrize("first", ["null", "text"])
def test_concat_of_untyped_null_and_text_pieces(first):
    pieces = [Column.const(None, 2, dtypes.NULLTYPE),
              Column.from_pylist(["b", "a"])]
    if first == "text":
        pieces.reverse()
    got = concat_batches([Batch(["s"], [p]) for p in pieces]).column("s")
    assert got.type == dtypes.VARCHAR
    assert got.to_pylist() == [v for p in pieces for v in p.to_pylist()]


def test_numeric_columns_move_neither_counter():
    before = _shared_gauges()
    cols = [Column.from_pylist([1, 2]), Column.from_pylist([3])]
    assert merge_dictionaries(cols) == cols
    assert _shared_gauges() == before


def test_dictionary_counters_are_served_on_metrics():
    from serenedb_tpu.obs.export import prometheus_text
    col, pieces = _pieces_of_one_column("slice")
    merge_dictionaries(pieces)
    merge_dictionaries([col, Column.from_pylist(["other"])])
    values = {}
    for line in prometheus_text().splitlines():
        name, _, value = line.partition(" ")
        if name in ("serenedb_host_concat_dict_shared",
                    "serenedb_host_concat_dict_merged"):
            values[name] = int(value)
    assert values == {
        "serenedb_host_concat_dict_shared":
            sdb_metrics.HOST_CONCAT_DICT_SHARED.value,
        "serenedb_host_concat_dict_merged":
            sdb_metrics.HOST_CONCAT_DICT_MERGED.value}
    assert min(values.values()) >= 1


# -- SQL level: ClickBench Q8 / Q9 / Q13 shapes over a table of more than
# -- one scan batch with a TEXT column --------------------------------------

_HITS_ROWS = DEFAULT_BATCH_ROWS + 20_000


@pytest.fixture(scope="module")
def hits_like():
    rng = np.random.default_rng(25)
    n = _HITS_ROWS
    user = rng.integers(1, 4000, n, dtype=np.int64) * 1_000_003
    data = {
        "UserID": user,
        "RegionID": (user % 37).astype(np.int32),
        "AdvEngineID": np.where(rng.random(n) < 0.1,
                                rng.integers(1, 18, n), 0).astype(np.int16),
        "ResolutionWidth": rng.choice(
            np.array([1024, 1366, 1920], np.int16), n),
        "SearchPhrase": np.where(
            rng.random(n) < 0.8, "",
            np.char.add("phrase ", rng.zipf(1.5, n).clip(
                max=900).astype(str))),
    }
    db = Database()
    c = db.connect()
    c.execute('CREATE TABLE hits ("UserID" BIGINT, "RegionID" INT, '
              '"AdvEngineID" SMALLINT, "ResolutionWidth" SMALLINT, '
              '"SearchPhrase" TEXT)')
    db.schemas["main"].tables["hits"].replace(Batch.from_pydict(
        {k: Column.from_numpy(v) for k, v in data.items()}))
    c.execute("SET serene_result_cache = off")
    return c, {k: v.tolist() for k, v in data.items()}


def _oracle(data, key, where_phrase, order_col):
    """Plain python GROUP BY: per key (sum AdvEngineID, count, avg
    ResolutionWidth, distinct UserID), top 10 by `order_col` DESC, key."""
    groups = {}
    for i, k in enumerate(data[key]):
        if where_phrase and data["SearchPhrase"][i] == "":
            continue
        g = groups.setdefault(k, [0, 0, 0, set()])
        g[0] += data["AdvEngineID"][i]
        g[1] += 1
        g[2] += data["ResolutionWidth"][i]
        g[3].add(data["UserID"][i])
    rows = [(k, g[0], g[1], g[2] / g[1], len(g[3]))
            for k, g in groups.items()]
    rows.sort(key=lambda r: (-r[order_col], r[0]))
    return rows[:10]


_SHAPES = {
    "q8": ('SELECT "RegionID", COUNT(DISTINCT "UserID") AS u FROM hits '
           'GROUP BY "RegionID" ORDER BY u DESC, "RegionID" LIMIT 10',
           ("RegionID", False, 4), (0, 4)),
    "q9": ('SELECT "RegionID", SUM("AdvEngineID"), COUNT(*) AS c, '
           'AVG("ResolutionWidth"), COUNT(DISTINCT "UserID") FROM hits '
           'GROUP BY "RegionID" ORDER BY c DESC, "RegionID" LIMIT 10',
           ("RegionID", False, 2), (0, 1, 2, 3, 4)),
    "q13": ('SELECT "SearchPhrase", COUNT(DISTINCT "UserID") AS u FROM hits '
            'WHERE "SearchPhrase" <> \'\' GROUP BY "SearchPhrase" '
            'ORDER BY u DESC, "SearchPhrase" LIMIT 10',
            ("SearchPhrase", True, 4), (0, 4)),
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_distinct_group_by_over_batches_sharing_a_dictionary(hits_like,
                                                             shape):
    c, data = hits_like
    sql, oracle_args, picked = _SHAPES[shape]
    hist = sdb_metrics.STAGE_HISTS["host_concat"]
    seen0 = sum(hist.snapshot()[0])
    shared0, merged0 = _shared_gauges()
    rows = c.execute(sql).rows()
    entry = FLIGHT.get(c._active_trace.trace_id)
    want = [tuple(r[i] for i in picked) for r in _oracle(data, *oracle_args)]
    assert len(rows) == 10
    for got_row, want_row in zip(rows, want):
        assert got_row == pytest.approx(want_row, rel=1e-12, abs=0)
    # the scan's batches went through concat_batches, in this request
    assert entry["stages"]["host_concat"] > 0
    assert sum(hist.snapshot()[0]) == seen0 + 1
    # SearchPhrase's pieces share the table's dictionary: never merged
    shared1, merged1 = _shared_gauges()
    assert shared1 > shared0 and merged1 == merged0
