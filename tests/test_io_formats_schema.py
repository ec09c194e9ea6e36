"""Schema inference reads metadata, never data, where the format carries
its schema (PR 27): ``infer_schema`` takes a Parquet file's schema from
the footer and must return what decoding the file would have returned;
the ``resolve.read_source`` span says which way the schema was learned
and how many bytes that cost; nothing is kept between statements."""

import datetime
import decimal
import os

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as pq
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.columnar.arrow_interop import arrow_type_to_spec
from sail_tpu.exec.local import clear_caches
from sail_tpu.io import formats
from sail_tpu.io.object_store import resolve_filesystem
from sail_tpu.spec import data_type as dt

_DAY = datetime.date(1998, 12, 1)
_TS = datetime.datetime(1998, 12, 1, 13, 30, 15, 250)

COLUMNS = {
    "int32": pa.array([1, None, 3], pa.int32()),
    "int64": pa.array([1, None, 3], pa.int64()),
    "double": pa.array([1.5, None, -0.25], pa.float64()),
    "decimal128_15_2": pa.array([decimal.Decimal("901.25"), None,
                                 decimal.Decimal("-0.01")],
                                pa.decimal128(15, 2)),
    "date32": pa.array([_DAY, None, _DAY], pa.date32()),
    "timestamp_us": pa.array([_TS, None, _TS], pa.timestamp("us")),
    "timestamp_us_utc": pa.array([_TS, None, _TS],
                                 pa.timestamp("us", "UTC")),
    "string": pa.array(["a", None, "c"], pa.string()),
    "large_string": pa.array(["a", None, "c"], pa.large_string()),
    "bool": pa.array([True, None, False], pa.bool_()),
    "list": pa.array([[1, 2], None, []], pa.list_(pa.int64())),
    "struct": pa.array([{"x": 1, "y": "a"}, None, {"x": 3, "y": None}],
                       pa.struct([("x", pa.int32()), ("y", pa.string())])),
    "dictionary_string": pa.array(["a", None, "a"]).dictionary_encode(),
}
TABLES = {name: pa.table({name: col}) for name, col in COLUMNS.items()}
TABLES["every_column"] = pa.table(COLUMNS)
TABLES["zero_rows"] = pa.table(COLUMNS).slice(0, 0)


def _decoded(schema: pa.Schema) -> dt.StructType:
    """What ``infer_schema`` returned while it decoded the file."""
    return dt.StructType(tuple(
        dt.StructField(f.name, arrow_type_to_spec(f.type), True)
        for f in schema))


def _refuse(*a, **kw):
    raise AssertionError("schema inference decoded a Parquet file")


@pytest.fixture()
def no_decode(monkeypatch):
    """The Parquet decoder raises: a schema can only come from a footer."""
    monkeypatch.setattr(pq, "read_table", _refuse)


@pytest.mark.parametrize("store_schema", [True, False],
                         ids=["arrow_schema_stored", "parquet_schema_only"])
@pytest.mark.parametrize("case", sorted(TABLES))
def test_footer_schema_equals_the_decoded_one(tmp_path, monkeypatch,
                                              case, store_schema):
    path = str(tmp_path / "t.parquet")
    pq.write_table(TABLES[case], path, store_schema=store_schema)
    want = _decoded(pq.read_table(path).schema)
    assert len(want.fields) == TABLES[case].num_columns
    monkeypatch.setattr(pq, "read_table", _refuse)
    assert formats.infer_schema("parquet", (path,), {}) == want


def test_first_file_of_a_directory_decides(tmp_path, no_decode):
    d = tmp_path / "t"
    d.mkdir()
    pq.write_table(pa.table({"a": pa.array([1], pa.int32())}),
                   str(d / "part-0.parquet"))
    pq.write_table(pa.table({"a": pa.array([1], pa.int64()),
                             "b": pa.array(["x"])}),
                   str(d / "part-1.parquet"))
    (d / "_SUCCESS").write_bytes(b"")
    got = formats.infer_schema("parquet", (str(d),), {})
    assert got == dt.StructType((
        dt.StructField("a", dt.IntegerType(), True),))


@pytest.mark.parametrize("uri", ["file://{path}", "mock://bucket/t.parquet"],
                         ids=["file_uri", "remote_filesystem"])
def test_footer_is_read_through_the_paths_filesystem(tmp_path, no_decode,
                                                     uri):
    path = str(tmp_path / "t.parquet")
    uri = uri.format(path=path)
    fsys, rel = resolve_filesystem(uri, {})
    if fsys is not None:
        fsys.create_dir(os.path.dirname(rel))
    with pq.ParquetWriter(rel, TABLES["every_column"].schema,
                          filesystem=fsys) as w:
        w.write_table(TABLES["every_column"])
    got = formats.infer_schema("parquet", (uri,), {})
    assert got == _decoded(TABLES["every_column"].schema)


def test_a_missing_path_is_still_file_not_found(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        formats.infer_schema("parquet", (str(empty),), {})


# -- through a session: the span, and freshness ---------------------------

@pytest.fixture()
def spark():
    clear_caches()
    yield SparkSession({"spark.sail.execution.mesh": "off",
                        "spark.sail.cache.result.enabled": "false"})
    clear_caches()


def _read_source_spans():
    return [s for s in profiler.last_profile().spans
            if s.name == "resolve.read_source"]


def _orders(path, extra=None):
    cols = {"o_orderkey": pa.array(range(5000), pa.int64()),
            "o_comment": pa.array([f"comment {i}" for i in range(5000)])}
    cols.update(extra or {})
    pq.write_table(pa.table(cols), path)


def test_resolve_reads_the_footer_only(spark, tmp_path, monkeypatch):
    path = str(tmp_path / "orders.parquet")
    _orders(path)
    spark.read.parquet(path).createOrReplaceTempView("orders")
    df = spark.sql("SELECT count(*) AS n FROM orders")
    with monkeypatch.context() as m:
        m.setattr(pq, "read_table", _refuse)
        assert df.columns == ["n"]          # resolves without a decode
    assert df.toArrow().column("n").to_pylist() == [5000]
    (span,) = _read_source_spans()
    assert span.attributes["format"] == "parquet"
    assert span.attributes["files"] == 1
    assert span.attributes["schema_source"] == "footer"
    assert 0 < span.attributes["bytes_read"] < os.path.getsize(path)
    assert span.attributes["bytes_read"] == \
        pq.ParquetFile(path).metadata.serialized_size


def test_csv_types_still_come_from_the_data(spark, tmp_path):
    path = str(tmp_path / "t.csv")
    pacsv.write_csv(pa.table({"a": [1, 2, 3], "b": ["x", "y", "z"]}), path)
    got = spark.read.csv(path, header=True).toArrow()
    assert got.column("a").to_pylist() == [1, 2, 3]
    (span,) = _read_source_spans()
    assert span.attributes["schema_source"] == "data"
    assert span.attributes["bytes_read"] == os.path.getsize(path)


def test_a_declared_schema_reads_nothing(spark, tmp_path, no_decode):
    path = str(tmp_path / "orders.parquet")
    _orders(path)
    df = spark.read.schema("o_orderkey BIGINT, o_comment STRING") \
        .parquet(path)
    assert df.columns == ["o_orderkey", "o_comment"]
    with profiler.profile_query("declared") as p:
        spark._resolve(df._plan)
    (span,) = [s for s in p.spans if s.name == "resolve.read_source"]
    assert span.attributes["schema_source"] == "declared"
    assert "bytes_read" not in span.attributes


def test_a_replaced_file_is_seen_by_the_next_statement(spark, tmp_path):
    path = str(tmp_path / "orders.parquet")
    _orders(path)
    spark.read.parquet(path).createOrReplaceTempView("orders")
    assert spark.sql("SELECT * FROM orders").columns == \
        ["o_orderkey", "o_comment"]
    assert spark.sql("SELECT count(*) AS n FROM orders").toArrow() \
        .column("n").to_pylist() == [5000]
    _orders(path, {"o_priority": pa.array([i % 5 for i in range(5000)],
                                          pa.int32())})
    assert spark.sql("SELECT * FROM orders").columns == \
        ["o_orderkey", "o_comment", "o_priority"]
    got = spark.sql("SELECT sum(o_priority) AS s FROM orders").toArrow()
    assert got.column("s").to_pylist() == [10000]


def test_create_table_at_a_location_reads_the_footer(spark, tmp_path,
                                                     no_decode):
    d = tmp_path / "orders"
    d.mkdir()
    _orders(str(d / "part-0.parquet"))
    spark.sql(f"CREATE TABLE orders_ext USING parquet LOCATION '{d}'")
    assert spark.sql("SELECT * FROM orders_ext").columns == \
        ["o_orderkey", "o_comment"]
