"""The typed vector column, `emb VECTOR(n)` (pgvector's spelling; `FLOAT4[n]`
is accepted for the same type): ONE contiguous float32 (rows, n) array,
through CREATE TABLE, COPY (parquet `FixedSizeList<float>[n]` and text
`[v1,...]`), INSERT, SELECT back as text, NULLs, take / concat, WAL replay
and restart — every path gives back the same float32 bits — and the index
builds that read it with no per-row parse. A JSON-text VARCHAR vector
column answers exactly as the typed one does.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from serenedb_tpu import errors
from serenedb_tpu.columnar import dtypes as dt
from serenedb_tpu.columnar.arrow_io import batch_to_bytes, bytes_to_batch
from serenedb_tpu.columnar.column import (Batch, Column, concat_batches,
                                          vector_text)
from serenedb_tpu.engine import Database

DIM = 12


def _vectors(n, seed=5, dim=DIM):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, dim)).astype(np.float32)
    # awkward float32s: tiny, huge, negative zero, integers
    x[0, :4] = [1e-30, 3.4e38, -0.0, 7.0]
    return x


def _bits(a):
    return np.ascontiguousarray(a, np.float32).view(np.uint32).tolist()


def _rows(conn, sql):
    return conn.execute(sql).rows()


def _read_back(conn, table="t"):
    """(ids, float32 rows or None) from SELECT's TEXT form."""
    out = _rows(conn, f"SELECT id, emb FROM {table} ORDER BY id")
    return [r[0] for r in out], [
        None if r[1] is None else np.asarray(json.loads(r[1]), np.float32)
        for r in out]


@pytest.mark.parametrize("spelling", ["VECTOR(12)", "vector( 12 )",
                                      "FLOAT4[12]", "REAL[12]"])
def test_type_spellings(spelling):
    t = dt.type_from_name(spelling)
    assert t.is_vector and t.dim == DIM and str(t) == "VECTOR(12)"
    assert dt.type_from_name(str(t)) == t          # catalog round trip
    c = Database().connect()
    c.execute(f"CREATE TABLE t (id INT, emb {spelling})")
    assert c.db.schemas["main"].tables["t"].column_types[1] == t


def test_untyped_arrays_stay_arrays():
    assert dt.type_from_name("FLOAT4[]").id is dt.TypeId.ARRAY
    assert dt.type_from_name("INT[3]").id is dt.TypeId.ARRAY
    with pytest.raises(ValueError):
        dt.type_from_name("VECTOR(0)")


def _load(conn, path, x, how, tmp_path):
    n = len(x)
    if how == "insert":
        conn.execute("INSERT INTO t VALUES " + ", ".join(
            f"({i}, '{vector_text(x[i])}')" for i in range(n)))
    elif how == "copy_parquet":
        tbl = pa.table({
            "id": pa.array(np.arange(n, dtype=np.int32)),
            "emb": pa.FixedSizeListArray.from_arrays(
                pa.array(x.reshape(-1)), x.shape[1])})
        p = os.path.join(tmp_path, "v.parquet")
        pq.write_table(tbl, p, row_group_size=7)    # several chunks
        conn.execute(f"COPY t FROM '{p}' (FORMAT parquet)")
    else:
        p = os.path.join(tmp_path, "v.tsv")
        with open(p, "w") as f:
            for i in range(n):
                f.write(f"{i}\t{vector_text(x[i])}\n")
        conn.execute(f"COPY t FROM '{p}' (FORMAT csv, DELIMITER '\t')")


@pytest.mark.parametrize("how", ["insert", "copy_parquet", "copy_text"])
def test_every_way_in_keeps_the_float32_bits_over_a_restart(how, tmp_path):
    x = _vectors(20)
    d = str(tmp_path / "data")
    db = Database(d)
    c = db.connect()
    c.execute(f"CREATE TABLE t (id INT, emb VECTOR({DIM}))")
    _load(c, d, x, how, str(tmp_path))
    c.execute("INSERT INTO t VALUES (100, NULL)")
    ids, rows = _read_back(c)
    assert ids == list(range(20)) + [100] and rows[-1] is None
    assert _bits(np.stack(rows[:-1])) == _bits(x)
    col = db.schemas["main"].tables["t"].full_batch(["emb"]).column("emb")
    assert col.data.shape == (21, DIM) and col.data.dtype == np.float32
    assert _bits(col.data[:20]) == _bits(x) and not col.valid_mask()[20]
    db.close()
    # WAL replay (nothing was checkpointed by hand), then a second
    # restart from whatever the first one left
    for _ in range(2):
        db = Database(d)
        ids2, rows2 = _read_back(db.connect())
        assert ids2 == ids and rows2[-1] is None
        assert _bits(np.stack(rows2[:-1])) == _bits(x)
        assert db.schemas["main"].tables["t"].column_types[1] == \
            dt.vector_of(DIM)
        db.close()


def test_snapshot_restart_keeps_the_bits(tmp_path):
    x = _vectors(50)
    d = str(tmp_path / "data")
    db = Database(d)
    c = db.connect()
    c.execute(f"CREATE TABLE t (id INT, emb VECTOR({DIM}))")
    _load(c, d, x, "copy_parquet", str(tmp_path))
    c.execute("VACUUM t")          # snapshot + WAL GC
    db.close()
    db = Database(d)
    _, rows = _read_back(db.connect())
    assert _bits(np.stack(rows)) == _bits(x)
    db.close()


def test_arrow_frames_wrap_the_array_and_keep_nulls():
    x = _vectors(9)
    valid = np.ones(9, bool)
    valid[[2, 8]] = False
    col = Column(dt.vector_of(DIM), x.copy(), valid)
    back = bytes_to_batch(batch_to_bytes(Batch(["v"], [col]))).column("v")
    assert back.type == col.type
    assert back.valid_mask().tolist() == valid.tolist()
    assert _bits(back.data[valid]) == _bits(x[valid])
    assert not back.data[~valid].any()            # a NULL row is zeros
    part = bytes_to_batch(batch_to_bytes(
        Batch(["v"], [col.slice(1, 6)]))).column("v")
    assert _bits(part.data[[0, 2, 3, 4]]) == _bits(x[[1, 3, 4, 5]])


def test_take_slice_filter_concat_are_axis_zero():
    x = _vectors(10)
    col = Column(dt.vector_of(DIM), x)
    assert _bits(col.take(np.array([9, 0, 3])).data) == _bits(x[[9, 0, 3]])
    assert _bits(col.slice(2, 5).data) == _bits(x[2:5])
    mask = np.arange(10) % 3 == 0
    assert _bits(col.filter(mask).data) == _bits(x[mask])
    b = Batch(["v"], [col])
    nulls = Batch(["v"], [Column.from_pylist([None, "[" + ",".join(
        ["1"] * DIM) + "]"], dt.vector_of(DIM))])
    both = concat_batches([b, nulls]).column("v")
    assert both.data.shape == (12, DIM)
    assert both.valid_mask().tolist() == [True] * 10 + [False, True]
    assert both.decode(10) is None and both.decode(11).startswith("[1.0,")
    assert len(Column.const(None, 3, dt.vector_of(DIM)).to_pylist()) == 3


@pytest.mark.parametrize("bad, state", [
    ("'[1,2]'", errors.DATATYPE_MISMATCH),
    ("'[[1,2]]'", errors.INVALID_TEXT_REPRESENTATION),
    ("'nonsense'", errors.INVALID_TEXT_REPRESENTATION)])
def test_a_wrong_literal_is_refused(bad, state):
    c = Database().connect()
    c.execute("CREATE TABLE t (id INT, emb VECTOR(3))")
    with pytest.raises(errors.SqlError) as e:
        c.execute(f"INSERT INTO t VALUES (1, {bad})")
    assert e.value.sqlstate == state
    assert _rows(c, "SELECT count(*) FROM t") == [(0,)]


def test_casts_and_the_distance_functions_read_the_array():
    c = Database().connect()
    c.execute("CREATE TABLE t (id INT, emb VECTOR(3))")
    c.execute("INSERT INTO t VALUES (1, '[1,0,0]'), (2, '[0,2,0]'), "
              "(3, NULL)")
    assert _rows(c, "SELECT emb::text, vec_dims(emb) FROM t WHERE id = 2") \
        == [("[0.0,2.0,0.0]", 3)]
    assert _rows(c, "SELECT '[1,2,3]'::vector(3)") == [("[1.0,2.0,3.0]",)]
    got = _rows(c, "SELECT id, vec_l2(emb, '[1,0,0]'), emb <#> '[1,1,1]', "
                   "emb <=> '[0,1,0]' FROM t ORDER BY id")
    assert got[0] == (1, 0.0, -1.0, 1.0)
    assert got[1][:3] == (2, 5.0, -2.0) and abs(got[1][3]) < 1e-7
    assert got[2] == (3, None, None, None)
    assert _rows(c, "SELECT id FROM t ORDER BY emb <-> '[0,1,0]' LIMIT 1") \
        == [(2,)]
    with pytest.raises(errors.SqlError):
        c.execute("SELECT vec_l2(emb, '[1,0]') FROM t")


@pytest.mark.parametrize("options", [
    "lists = 4", "lists = 4, metric = 'cos'",
    "type = 'flat', metric = 'cos'", "type = 'flat', metric = 'ip'"])
def test_a_json_text_column_answers_as_the_typed_one(options):
    """One parse into the same (rows, dim) array, then the same path:
    the same ids and the same distance bits from both columns."""
    x = np.random.default_rng(9).standard_normal((300, DIM)) \
        .astype(np.float32)
    c = Database().connect()
    c.execute(f"CREATE TABLE typed (id INT, emb VECTOR({DIM}))")
    c.execute("CREATE TABLE texty (id INT, emb VARCHAR)")
    vals = ", ".join(f"({i}, '{vector_text(x[i])}')" for i in range(300))
    for t in ("typed", "texty"):
        c.execute(f"INSERT INTO {t} VALUES {vals}, (300, NULL)")
        c.execute(f"CREATE INDEX ON {t} USING ivf (emb) WITH ({options})")
    c.execute("SET serene_nprobe = 4")
    fn = "vec_ip" if "'ip'" in options else \
        "vec_cos" if "'cos'" in options else "vec_l2"
    for qi in (3, 77):
        lit = vector_text(x[qi] * np.float32(1.01))
        sql = (f"SELECT id, {fn}(emb, '{lit}') d FROM {{}} "
               "ORDER BY d LIMIT 7")
        a = _rows(c, sql.format("typed"))
        assert a == _rows(c, sql.format("texty")) and len(a) == 7
        plan = _rows(c, "EXPLAIN " + sql.format("typed"))
        assert any("IvfScan" in r[0] for r in plan)
