"""The executor's eager host work under spans of its own: ``join.expand``
around the eager join expansion (a child of ``op.JoinExec``, opened once
the join's output attributes are noted on the operator) and
``arrow.convert`` around Arrow-to-host conversion (a child of
``op.ScanExec``, beside ``upload`` and not around it). Both lie on the
xplane's host plane as ``sail:`` annotations inside their operator's."""

import json
import os
import sys
from decimal import Decimal

import jax
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.exec.local import _OP_CACHE, clear_caches

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402
import tracered  # noqa: E402

#: what the expansion's span says of itself
EXPAND_ATTRS = {"probe_capacity", "out_capacity", "columns", "join_type",
                "residual"}
#: what stays on op.JoinExec
JOIN_ATTRS = {"out_rows", "out_capacity", "expanded", "probe_capacity",
              "build_capacity"}
CONVERT_ATTRS = {"rows", "columns", "strings", "decimals", "dicts_interned"}

OPTIONS = {"spark.sail.execution.mesh": "off",
           "spark.sail.cache.result.enabled": "false",
           "spark.sail.execution.backend.force": "xla"}


def _doc(name):
    with open(os.path.join(ROOT, "benchmark", "queries",
                           name + ".json")) as f:
        return json.load(f)


def _sql(name):
    with open(os.path.join(ROOT, "benchmark", "queries",
                           _doc(name)["sql_file"])) as f:
        return f.read()


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """Q3's and Q5's tables at SF0.01 as Parquet views of one session."""
    wanted = {}
    for q in ("tpch-q3", "tpch-q5"):
        for table, cols in _doc(q)["reads"].items():
            have = wanted.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    tmp = tmp_path_factory.mktemp("tpch_sf001")
    paths, _frames, _rows, _bytes = datagen.write_tables(
        wanted, 20261015, 0.01, str(tmp), workers=2)
    clear_caches()
    spark = SparkSession(dict(OPTIONS))
    for name, path in paths.items():
        spark.read.parquet(path).createOrReplaceTempView(name)
    yield spark
    clear_caches()


def _children(spans, parent, name):
    return [s for s in spans if s.parent_id == parent.span_id
            and s.name == name]


@pytest.mark.parametrize("query", ["tpch-q3", "tpch-q5"])
def test_each_expanding_join_holds_one_join_expand(tpch, query):
    tpch.sql(_sql(query)).toArrow()
    spans = list(profiler.last_profile().spans)
    joins = [s for s in spans if s.name == "op.JoinExec"]
    assert joins
    for j in joins:
        assert JOIN_ATTRS <= set(j.attributes), j.attributes
        expands = _children(spans, j, "join.expand")
        if not j.attributes["expanded"]:
            # join_unique: no expansion, no span
            assert expands == []
            continue
        (e,) = expands
        assert set(e.attributes) == EXPAND_ATTRS
        assert e.thread_id == j.thread_id
        assert j.start_ns <= e.start_ns <= e.end_ns <= j.end_ns
        assert e.attributes["out_capacity"] == j.attributes["out_capacity"]
        assert e.attributes["probe_capacity"] == \
            j.attributes["probe_capacity"]
        assert e.attributes["join_type"] == "inner"
        assert e.attributes["residual"] is False
        assert e.attributes["columns"] > 0
    assert sum(1 for s in spans if s.name == "join.expand") == \
        sum(1 for j in joins if j.attributes["expanded"])


def test_a_join_on_unique_build_keys_opens_no_join_expand(tpch):
    tpch.sql("SELECT o_orderkey, c_nationkey FROM orders "
             "JOIN customer ON o_custkey = c_custkey").toArrow()
    spans = list(profiler.last_profile().spans)
    (j,) = [s for s in spans if s.name == "op.JoinExec"]
    assert j.attributes["expanded"] is False
    assert JOIN_ATTRS <= set(j.attributes)
    assert not any(s.name == "join.expand" for s in spans)


def test_join_expand_lies_inside_its_join_on_the_host_plane(tpch, tmp_path):
    sql = _sql("tpch-q3")
    tpch.sql(sql).toArrow()  # warm: the traced statement compiles nothing
    with jax.profiler.trace(str(tmp_path)):
        tpch.sql(sql).toArrow()
    planes = tracered.load_xplane(tracered.find_xplane(str(tmp_path)))
    joins = tracered.host_spans(planes, "sail:op.JoinExec")
    expands = tracered.host_spans(planes, "sail:join.expand")
    assert expands and joins
    for _n, s, e in expands:
        assert any(js <= s and e <= je for _jn, js, je in joins), (s, e)


def _lineitem(n=4000):
    cents = pa.decimal128(15, 2)
    return pa.table({
        "l_extendedprice": pa.array([Decimal(100 + i) for i in range(n)],
                                    cents),
        "l_discount": pa.array([Decimal(i % 11) / 100 for i in range(n)],
                               cents),
        "l_shipmode": pa.array(["AIR", "RAIL", "SHIP", "MAIL"] * (n // 4)),
    })


def test_a_chunked_scan_converts_each_chunk_once_beside_its_upload(
        tmp_path):
    clear_caches()
    spark = SparkSession({**OPTIONS, "spark.sail.scan.chunkRows": "1000"})
    path = str(tmp_path / "lineitem")
    pq.write_to_dataset(_lineitem(), path)
    spark.read.parquet(path).createOrReplaceTempView("lineitem")
    sql = ("SELECT l_shipmode, sum(l_extendedprice * l_discount) "
           "AS r FROM lineitem GROUP BY l_shipmode")
    got = spark.sql(sql).toArrow()
    assert got.num_rows == 4
    spans = list(profiler.last_profile().spans)
    by_id = {s.span_id: s for s in spans}
    converts = [s for s in spans if s.name == "arrow.convert"]
    for c in converts:
        assert set(c.attributes) == CONVERT_ATTRS
        assert by_id[c.parent_id].name == "op.ScanExec"
    chunks = [c for c in converts if c.attributes["rows"] == 1000]
    assert len(chunks) == 4
    for c in chunks:
        assert c.attributes["columns"] == 3
        assert c.attributes["strings"] == 1
        assert c.attributes["decimals"] == 2
    # one conversion a scan, and its upload beside it, after it
    for scan in {c.parent_id for c in converts}:
        (c,) = _children(spans, by_id[scan], "arrow.convert")
        (u,) = _children(spans, by_id[scan], "upload")
        assert c.end_ns <= u.start_ns
    ids = {c.span_id for c in converts}
    assert not any(s.parent_id in ids for s in spans if s.name == "upload")
    # every chunk after the first, and the merge, finds the column's
    # dictionary in the intern table
    assert sum(c.attributes["dicts_interned"] for c in converts) == \
        sum(c.attributes["strings"] for c in converts) - 1
    # so a statement again hits the programs of the first and holds no
    # more of its chunks
    entries = len(_OP_CACHE.entries)
    allocated = pa.total_allocated_bytes()
    for _ in range(2):
        assert spark.sql(sql).toArrow().equals(got)
        assert len(_OP_CACHE.entries) == entries
        converts = [s for s in profiler.last_profile().spans
                    if s.name == "arrow.convert"]
        assert all(c.attributes["dicts_interned"] == c.attributes["strings"]
                   for c in converts)
    assert abs(pa.total_allocated_bytes() - allocated) <= 0.03 * allocated
    clear_caches()
