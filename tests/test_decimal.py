"""DECIMAL(p, s), p <= 18, as a scaled int64: literals typed exactly
beside a DECIMAL, the scale rules of + - * /, an exact SUM with its
overflow error, comparisons across scales, casts, COPY from parquet
decimal128 and from text, and the wire's text and binary forms."""

import asyncio
import struct
import threading

import numpy as np
import pytest

from serenedb_tpu import errors
from serenedb_tpu.columnar import dtypes as dt
from serenedb_tpu.engine import Database


@pytest.fixture()
def conn():
    c = Database().connect()
    c.execute("CREATE TABLE d (x DECIMAL(15,2), q DECIMAL(15,2), "
              "f DECIMAL(15,4), k INT)")
    c.execute("INSERT INTO d VALUES (1.05, 2, 0.1234, 1), "
              "(100.10, 0.07, 1.5, 2), (-3.50, 0.06, -0.0001, 1), "
              "(NULL, 1, 0, 2)")
    return c


def _types(res):
    return [str(c.type) for c in res.batch.columns]


def test_type_names_round_trip():
    t = dt.type_from_name("DECIMAL(15,2)")
    assert (t.prec, t.scale, t.np_dtype) == (15, 2, np.dtype(np.int64))
    assert dt.type_from_name(str(t)) == t
    assert dt.type_from_name("numeric(10)") == dt.decimal_of(10, 0)
    assert dt.type_from_name("DECIMAL") == dt.decimal_of(18, 3)
    with pytest.raises(ValueError):
        dt.type_from_name("DECIMAL(19,2)")


def test_values_are_scaled_integers(conn):
    res = conn.execute("SELECT x, q, f FROM d ORDER BY k, x NULLS LAST")
    assert _types(res) == ["DECIMAL(15,2)", "DECIMAL(15,2)",
                           "DECIMAL(15,4)"]
    assert res.rows() == [(-350, 6, -1), (105, 200, 1234),
                          (10010, 7, 15000), (None, 100, 0)]


@pytest.mark.parametrize("sql, typ, rows", [
    ("SELECT x + q FROM d WHERE k = 1 ORDER BY 1", "DECIMAL(18,2)",
     [(-344,), (305,)]),
    ("SELECT x - f FROM d WHERE k = 1 ORDER BY 1", "DECIMAL(18,4)",
     [(-34999,), (9266,)]),
    ("SELECT x * q FROM d WHERE k = 1 ORDER BY 1", "DECIMAL(18,4)",
     [(-2100,), (21000,)]),
    ("SELECT x * (1 - q) FROM d WHERE k = 1 ORDER BY 1", "DECIMAL(18,4)",
     [(-32900,), (-10500,)]),
    ("SELECT x + 1 FROM d WHERE k = 1 ORDER BY 1", "DECIMAL(18,2)",
     [(-250,), (205,)]),
])
def test_scale_rules(conn, sql, typ, rows):
    res = conn.execute(sql)
    assert _types(res) == [typ]
    assert res.rows() == rows


def test_division_and_avg_are_double(conn):
    res = conn.execute("SELECT x / q FROM d WHERE k = 1 ORDER BY 1")
    assert _types(res) == ["DOUBLE"]
    assert res.rows()[1][0] == pytest.approx(0.525)
    res = conn.execute("SELECT avg(x), avg(q) FROM d")
    assert _types(res) == ["DOUBLE", "DOUBLE"]
    assert res.rows()[0][0] == pytest.approx(97.65 / 3)


def test_literals_typed_exactly(conn):
    # 0.05 is 5 at scale 2, never a float: exact equality and BETWEEN
    assert conn.execute("SELECT count(*) FROM d WHERE q = 0.07").scalar() \
        == 1
    assert conn.execute(
        "SELECT k FROM d WHERE q BETWEEN 0.06 - 0.01 AND 0.06 + 0.01 "
        "ORDER BY k").rows() == [(1,), (2,)]
    assert conn.execute("SELECT count(*) FROM d WHERE f = 0.1234"
                        ).scalar() == 1
    # alone the folded constant stays the binary float it always was
    assert conn.execute("SELECT 0.06 - 0.01").scalar() == 0.06 - 0.01
    assert conn.execute("SELECT 0.1 + 0.2 = 0.3").scalar() is False


def test_sum_min_max_exact(conn):
    res = conn.execute("SELECT sum(x), min(x), max(x), sum(x * q), "
                       "count(x) FROM d")
    assert _types(res) == ["DECIMAL(18,2)", "DECIMAL(15,2)",
                           "DECIMAL(15,2)", "DECIMAL(18,4)", "BIGINT"]
    assert res.rows() == [(9765, -350, 10010, 70070 + 21000 - 2100, 3)]
    res = conn.execute("SELECT k, sum(x) FROM d GROUP BY k ORDER BY k")
    assert res.rows() == [(1, -245), (2, 10010)]


def test_overflow_raises_22003():
    c = Database().connect()
    c.execute("CREATE TABLE big (v DECIMAL(18,0))")
    c.execute("INSERT INTO big SELECT 900000000000000000 FROM "
              "generate_series(1, 11)")
    with pytest.raises(errors.SqlError) as e:
        c.execute("SELECT sum(v) FROM big")
    assert e.value.sqlstate == "22003"
    with pytest.raises(errors.SqlError) as e:
        c.execute("SELECT v * v FROM big")
    assert e.value.sqlstate == "22003"
    with pytest.raises(errors.SqlError) as e:
        c.execute("SELECT CAST(123.45 AS DECIMAL(4,2))")
    assert e.value.sqlstate == "22003"


def test_comparisons_across_scales(conn):
    assert conn.execute("SELECT count(*) FROM d WHERE x > f").scalar() == 2
    assert conn.execute("SELECT count(*) FROM d WHERE x < 2").scalar() == 2
    assert conn.execute("SELECT count(*) FROM d WHERE f <= q").scalar() == 3


@pytest.mark.parametrize("sql, want", [
    ("SELECT CAST('12.345' AS DECIMAL(10,2))", 1235),
    ("SELECT CAST('-12.345' AS DECIMAL(10,2))", -1235),
    ("SELECT CAST(-2.5 AS DECIMAL(5,0))", -3),
    ("SELECT CAST(CAST(2.5 AS DECIMAL(5,1)) AS INT)", 3),
    ("SELECT CAST(CAST(1.25 AS DECIMAL(5,2)) AS DOUBLE)", 1.25),
    ("SELECT CAST(CAST(-1.5 AS DECIMAL(5,2)) AS TEXT)", "-1.50"),
    ("SELECT CAST(7 AS DECIMAL(6,3))", 7000),
])
def test_casts(sql, want):
    assert Database().connect().execute(sql).scalar() == want


def test_copy_parquet_decimal128_and_text(tmp_path):
    from decimal import Decimal

    import pyarrow as pa
    import pyarrow.parquet as pq
    vals = [Decimal("1.05"), Decimal("-99999999.99"), None,
            Decimal("0.00")]
    path = str(tmp_path / "dec.parquet")
    pq.write_table(pa.table({"x": pa.array(vals, pa.decimal128(15, 2))}),
                   path)
    c = Database().connect()
    c.execute("CREATE TABLE p (x DECIMAL(15,2))")
    c.execute(f"COPY p FROM '{path}' (FORMAT parquet)")
    assert c.execute("SELECT x FROM p").rows() == [
        (105,), (-9999999999,), (None,), (0,)]
    csv = tmp_path / "dec.csv"
    csv.write_text("1.05\n-7\n0.125\n")
    c.execute("CREATE TABLE t (x DECIMAL(15,2))")
    c.execute(f"COPY t FROM '{csv}' (FORMAT csv)")
    assert c.execute("SELECT x FROM t").rows() == [(105,), (-700,), (13,)]


def test_numeric_binary_send():
    from serenedb_tpu.columnar.pgcopy import encode_value
    t = dt.decimal_of(15, 2)
    # 12345.67 -> digits [1, 2345, 6700], weight 1, scale 2
    assert encode_value(1234567, t) == struct.pack(
        "!hhHH3H", 3, 1, 0, 2, 1, 2345, 6700)
    assert encode_value(-5, t) == struct.pack("!hhHH1H", 1, -1, 0x4000, 2,
                                              500)
    assert encode_value(0, t) == struct.pack("!hhHH", 0, 0, 0, 2)


@pytest.fixture(scope="module")
def server():
    from serenedb_tpu.server.pgwire import PgServer
    db = Database()
    c = db.connect()
    c.execute("CREATE TABLE m (x DECIMAL(15,2))")
    c.execute("INSERT INTO m VALUES (1.5), (-0.07), (12345678.9)")
    srv = PgServer(db, port=0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def go():
            await srv.start()
            started.set()
            await asyncio.Event().wait()
        try:
            loop.run_until_complete(go())
        except RuntimeError:
            pass
    threading.Thread(target=run, daemon=True).start()
    started.wait(10)
    yield srv
    loop.call_soon_threadsafe(loop.stop)


def test_pgwire_text_and_oid(server):
    from test_pgwire import RawPg
    c = RawPg(server.port)
    c.send(b"Q", b"SELECT x FROM m ORDER BY x\x00")
    oids, rows = [], []
    while True:
        kind, payload = c.read_msg()
        if kind == b"T":
            end = payload.index(b"\x00", 2)
            (oid,) = struct.unpack("!I", payload[end + 7:end + 11])
            oids.append(oid)
        elif kind == b"D":
            (ln,) = struct.unpack("!i", payload[2:6])
            rows.append(payload[6:6 + ln].decode())
        elif kind == b"Z":
            break
    assert oids == [1700]
    assert rows == ["-0.07", "1.50", "12345678.90"]
    _, rows, _, errs = c.query("SELECT sum(x) FROM m")
    assert not errs and rows == [("12345680.33",)]
    c.close()
