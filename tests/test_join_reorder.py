"""Join reordering (greedy operator ordering) unit tests.

Reference role: sail-physical-optimizer/src/join_reorder/ (cost-based
reorder) + collect_left.rs (small-side build selection). Correctness of
reordered plans is separately locked by the full TPC-H oracle suite.
"""

import datetime
import decimal
import json
import os
import sys
import types

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.io import cache as io_cache
from sail_tpu.plan import join_reorder as jr
from sail_tpu.plan import nodes as pn
from sail_tpu.plan.join_reorder import reorder_joins
from sail_tpu.plan.optimizer import optimize
from sail_tpu.sql import parse_one

from tpch_oracle import ORACLES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402


def _scan_order(p, out=None):
    """Left-to-right base-table row counts of a plan tree (temp-view scans
    carry no table name, so size identifies the relation)."""
    if out is None:
        out = []
    if isinstance(p, pn.ScanExec):
        out.append(p.source.num_rows if p.source is not None else -1)
    for c in p.children:
        if c is not None:
            _scan_order(c, out)
    return out


@pytest.fixture()
def star(request):
    """A star schema: big fact table, small filtered dimensions."""
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    rng = np.random.default_rng(3)
    n = 20000
    fact = pd.DataFrame({
        "f_d1": rng.integers(0, 100, n),
        "f_d2": rng.integers(0, 50, n),
        "f_val": rng.random(n),
    })
    d1 = pd.DataFrame({"d1_id": np.arange(100),
                       "d1_name": [f"n{i}" for i in range(100)]})
    d2 = pd.DataFrame({"d2_id": np.arange(50),
                       "d2_flag": (np.arange(50) % 5 == 0)})
    for name, df in [("fact", fact), ("d1", d1), ("d2", d2)]:
        spark.createDataFrame(df).createOrReplaceTempView(name)
    return spark, fact, d1, d2


SQL = """
SELECT d1.d1_name, SUM(fact.f_val)
FROM fact
JOIN d1 ON fact.f_d1 = d1.d1_id
JOIN d2 ON fact.f_d2 = d2.d2_id
WHERE d2.d2_flag
GROUP BY d1.d1_name
"""


def test_reorder_moves_fact_table_late(star):
    spark, fact, d1, d2 = star
    plan = optimize(spark._resolve(parse_one(SQL)))
    order = _scan_order(plan)
    assert set(order) == {20000, 100, 50}
    # the 20k-row fact table must not be the leading (left-most) relation
    assert order[0] != 20000


def test_reorder_preserves_results(star):
    spark, fact, d1, d2 = star
    got = spark.sql(SQL).toPandas().sort_values("d1_name").reset_index(drop=True)
    sub = fact[fact.f_d2.isin(d2[d2.d2_flag].d2_id)]
    exp = (sub.merge(d1, left_on="f_d1", right_on="d1_id")
           .groupby("d1_name")["f_val"].sum().reset_index()
           .sort_values("d1_name").reset_index(drop=True))
    assert len(got) == len(exp)
    np.testing.assert_allclose(got.iloc[:, 1].values, exp.f_val.values)


def test_reorder_keeps_output_schema(star):
    spark, *_ = star
    resolved = spark._resolve(parse_one(
        "SELECT * FROM fact JOIN d1 ON f_d1 = d1_id "
        "JOIN d2 ON f_d2 = d2_id"))
    before = [f.name for f in resolved.schema]
    after = [f.name for f in optimize(resolved).schema]
    assert before == after


def test_outer_joins_not_reordered(star):
    spark, *_ = star
    resolved = spark._resolve(parse_one(
        "SELECT * FROM fact LEFT JOIN d1 ON f_d1 = d1_id "
        "LEFT JOIN d2 ON f_d2 = d2_id"))
    plan = reorder_joins(resolved)
    assert _scan_order(plan) == _scan_order(resolved)


def test_cross_product_fallback_executes(star):
    spark, fact, d1, d2 = star
    got = spark.sql(
        "SELECT COUNT(*) FROM d1, d2 WHERE d1_id < 3 AND d2_id < 2"
    ).toPandas()
    assert got.iloc[0, 0] == 6


# ---------------------------------------------------------------------------
# a join key's distinct count, bounded from the Parquet footers (PR 34)
# ---------------------------------------------------------------------------

ROWS = 1000
DAY0 = datetime.date(1994, 1, 1)


def _columns(lo=0):
    i = np.arange(ROWS)
    return {
        "k_int": pa.array(lo + i % 50, pa.int64()),
        "k_wide": pa.array(lo + i * 1000, pa.int64()),
        "k_date": pa.array([DAY0 + datetime.timedelta(days=int(d))
                            for d in i % 30], pa.date32()),
        "k_bool": pa.array(i % 2 == 0),
        "k_str": pa.array([f"s{v}" for v in i % 7]),
        "k_dec": pa.array([decimal.Decimal(int(v)) for v in i % 9],
                          pa.decimal128(15, 2)),
        "k_dbl": pa.array((i % 11).astype(np.float64)),
        "k_ts": pa.array(i % 13, pa.timestamp("us")),
        "k_null": pa.array([None] * ROWS, pa.int64()),
    }


def _view(spark, tmp_path, name, tables, **write_options):
    """``tables`` as one directory of Parquet files, one file each,
    registered as a temp view; returns the directory."""
    d = tmp_path / name
    d.mkdir()
    for n, t in enumerate(tables):
        pq.write_table(t, str(d / f"part-{n:03d}.parquet"), **write_options)
    spark.read.parquet(str(d)).createOrReplaceTempView(name)
    return str(d)


@pytest.fixture()
def footers(tmp_path):
    io_cache.METADATA_CACHE.clear()
    io_cache.LISTING_CACHE.clear()
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    _view(spark, tmp_path, "one", [pa.table(_columns())])
    _view(spark, tmp_path, "two", [pa.table(_columns()),
                                   pa.table(_columns(lo=100))])
    _view(spark, tmp_path, "bare", [pa.table(_columns())],
          write_statistics=False)
    _view(spark, tmp_path, "half", [pa.table(_columns())])
    pq.write_table(pa.table(_columns()),
                   str(tmp_path / "half" / "part-001.parquet"),
                   write_statistics=False)
    spark.createDataFrame(pa.table(_columns())).createOrReplaceTempView("mem")
    return spark


def _ndv(spark, sql_from, key):
    """``key_ndv`` of the expression ``key`` over the resolved leaf
    ``SELECT * FROM <sql_from>``: the same call ``_collect`` makes."""
    leaf = spark._resolve(parse_one(
        f"SELECT {key} AS the_key FROM {sql_from}"))
    assert isinstance(leaf, pn.ProjectExec)
    return jr.key_ndv(leaf.input, leaf.exprs[0][1],
                      jr._base_rows(leaf.input))


@pytest.mark.parametrize("view,key,ndv,source", [
    ("one", "k_int", 50, "footer"),          # max - min + 1 of an integer
    ("one", "k_date", 30, "footer"),         # of a date, in days
    ("one", "k_bool", 2, "footer"),
    ("one", "k_wide", ROWS, "footer"),       # a range wider than the rows
    ("two", "k_int", 150, "footer"),         # 0..49 and 100..149: the hull
    ("two", "k_date", 30, "footer"),
    ("two", "k_wide", 2 * ROWS, "footer"),
    ("one", "k_str", ROWS, "rows"),          # types no range bounds
    ("one", "k_dec", ROWS, "rows"),
    ("one", "k_dbl", ROWS, "rows"),
    ("one", "k_ts", ROWS, "rows"),
    ("one", "k_null", ROWS, "rows"),         # nothing but NULLs: no value
    ("one", "k_int + 1", ROWS, "rows"),      # a key that is an expression
    ("one", "CAST(k_int AS INT)", ROWS, "rows"),
    ("bare", "k_int", ROWS, "rows"),         # a file without statistics
    ("half", "k_int", 2 * ROWS, "rows"),     # one file of two without
    ("mem", "k_int", ROWS, "rows"),          # an in-memory leaf
    ("(SELECT k_int AS kk, k_str FROM one WHERE k_dbl > 2) AS s", "kk",
     50, "footer"),                          # passed through unchanged
    ("(SELECT k_int * 2 AS kk FROM one) AS s", "kk", ROWS, "rows"),
])
def test_key_ndv_is_bounded_from_the_footers_or_falls_back(
        footers, view, key, ndv, source):
    got = _ndv(footers, view, key)
    assert (got.ndv, got.source) == (float(ndv), source)


def _fake_footer(chunks):
    """A ``pq.FileMetaData`` stand-in: one column ``k``, one row group
    per ``(min, max, distinct_count)``."""
    def group(lo, hi, distinct):
        st = types.SimpleNamespace(
            has_min_max=lo is not None, min=lo, max=hi,
            has_distinct_count=distinct is not None,
            distinct_count=distinct, null_count=0)
        col = types.SimpleNamespace(path_in_schema="k", statistics=st,
                                    num_values=100)
        return types.SimpleNamespace(num_columns=1, column=lambda j: col)
    groups = [group(*c) for c in chunks]
    return types.SimpleNamespace(num_row_groups=len(groups),
                                 row_group=lambda g: groups[g])


@pytest.mark.parametrize("chunks,expected", [
    # the writer's distinct counts, summed: an upper bound of the file's
    ([(0, 999, 7), (0, 999, 5)], io_cache.ColumnStats(12, 0, 999)),
    # one chunk without a count: the range alone
    ([(0, 999, 7), (5, 20, None)], io_cache.ColumnStats(None, 0, 999)),
    # counts on a type no range bounds
    ([("a", "z", 4), ("b", "c", 2)], io_cache.ColumnStats(6, None, None)),
    ([("a", "z", None)], None),
    ([(None, None, None)], None),
])
def test_column_stats_walks_the_row_groups(chunks, expected):
    assert io_cache.column_stats(_fake_footer(chunks), "k") == expected
    assert io_cache.column_stats(_fake_footer(chunks), "other") is None


def test_a_writers_distinct_count_beats_a_wider_range(footers, monkeypatch):
    monkeypatch.setattr(
        io_cache.METADATA_CACHE, "column_stats",
        lambda path, column: io_cache.ColumnStats(25, 0, 10**6))
    assert _ndv(footers, "two", "k_wide") == (50.0, "footer")   # 25 a file
    monkeypatch.setattr(
        io_cache.METADATA_CACHE, "column_stats",
        lambda path, column: io_cache.ColumnStats(10**6, 0, 24))
    assert _ndv(footers, "two", "k_wide") == (25.0, "footer")


THREE_WAY = ("SELECT COUNT(*) FROM one a JOIN two b ON a.k_int = b.k_int "
             "JOIN one c ON b.k_date = c.k_date")


def test_the_bound_reads_footers_only_and_a_warm_statement_opens_no_file(
        footers, monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the planner decoded a column")
    monkeypatch.setattr(pq, "read_table", refuse)
    monkeypatch.setattr(pq.ParquetFile, "read", refuse)
    monkeypatch.setattr(pq.ParquetFile, "read_row_group", refuse)
    monkeypatch.setattr(pq.ParquetFile, "iter_batches", refuse)
    cache = io_cache.METADATA_CACHE
    cache.clear()
    before = cache.misses
    resolved = footers._resolve(parse_one(THREE_WAY))
    with profiler.profile_query("test") as prof:
        with prof.phase("optimize"):
            optimize(resolved)
    # three files (one, two x 2): each footer read once, by the row
    # count; the bounds found them in the cache
    assert cache.misses - before == 3
    span, = [s for s in prof.spans if s.name == "optimize.join_reorder"]
    assert span.attributes["keys_bounded"] == 4
    assert span.attributes["keys_by_rows"] == 0
    # warm: no file is opened at all
    monkeypatch.setattr(pq, "ParquetFile", refuse)
    misses = cache.misses
    optimize(resolved)
    assert cache.misses == misses


def test_one_expansion_of_a_scans_paths_per_reordered_tree(
        footers, monkeypatch):
    from sail_tpu.io import formats
    resolved = footers._resolve(parse_one(THREE_WAY))
    calls = []
    real = formats.expand_paths
    monkeypatch.setattr(formats, "expand_paths",
                        lambda paths: calls.append(tuple(paths))
                        or real(paths))
    jr.reorder_joins(resolved)
    assert len(calls) == len(set(calls)) == 2       # views one and two


def test_a_stale_footer_is_read_again(footers, tmp_path):
    assert _ndv(footers, "one", "k_int").ndv == 50
    path = str(tmp_path / "one" / "part-000.parquet")
    pq.write_table(pa.table(_columns(lo=0)).slice(0, 10), path)
    io_cache.LISTING_CACHE.clear()
    assert _ndv(footers, "one", "k_int") == (10.0, "footer")


# -- TPC-H Q5 and Q3 over Parquet ---------------------------------------------

def _query(name):
    with open(os.path.join(ROOT, "benchmark", "queries", name + ".json")) as f:
        doc = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "queries",
                           doc["sql_file"])) as f:
        return doc, f.read()


@pytest.fixture(scope="module")
def tpch_parquet(tmp_path_factory):
    """Q5's six tables (Q3's three among them) at SF0.2 as Parquet, by
    the benchmark's generator: above SF0.1 a nation holds more customers
    (6,000 x SF) than a supplier has lineitems (600), so joining
    ``customer`` on the nation key alone is the expanding choice, as it
    is at SF1."""
    wanted = {}
    for q in ("tpch-q5", "tpch-q3"):
        for table, cols in _query(q)[0]["reads"].items():
            have = wanted.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    tmp = tmp_path_factory.mktemp("tpch_sf02")
    paths, frames, _rows, _bytes = datagen.write_tables(
        wanted, 20281004, 0.2, str(tmp), workers=2)
    return paths, frames


@pytest.fixture()
def tpch_spark(tpch_parquet):
    io_cache.METADATA_CACHE.clear()
    io_cache.LISTING_CACHE.clear()
    jr.clear_observed_rows()
    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.cache.result.enabled": "false",
                          "spark.sail.execution.backend.force": "xla"})
    for name, path in tpch_parquet[0].items():
        spark.read.parquet(path).createOrReplaceTempView(name)
    return spark


def _joins(spark, sql):
    """The optimized plan's joins, top down, as EXPLAIN prints them, the
    temporary directory and the runtime-filter ids left out."""
    text = spark.sql("EXPLAIN " + sql).toPandas().iloc[0, 0]
    out = []
    for line in text.splitlines():
        line = line.strip()
        if "JoinExec" in line:
            out.append(line.split("] ", 1)[1].split(" runtime_filter")[0])
        elif "ScanExec" in line:
            out.append("scan " + os.path.basename(
                line.split("table=('")[1].split("'")[0]))
    return out


def test_q5_joins_customer_last_on_both_keys_and_equals_the_oracle(
        tpch_spark, tpch_parquet):
    _doc, sql = _query("tpch-q5")
    assert _joins(tpch_spark, sql) == [
        "JoinExec type=inner on=[('#12:o_custkey', '#0:c_custkey'), "
        "('#6:s_nationkey', '#1:c_nationkey')]",
        "JoinExec type=inner on=[('#7:l_orderkey', '#0:o_orderkey')]",
        "JoinExec type=inner on=[('#5:s_suppkey', '#1:l_suppkey')]",
        "JoinExec type=inner on=[('#2:n_nationkey', '#1:s_nationkey')]",
        "JoinExec type=inner on=[('#0:r_regionkey', '#2:n_regionkey')]",
        "scan region", "scan nation", "scan supplier", "scan lineitem",
        "scan orders", "scan customer"]
    got = tpch_spark.sql(sql).toPandas()
    prof = profiler.last_profile()
    assert {r["backend"] for r in prof.backend_routes} == {"xla"}

    reorder, = [s for s in prof.spans if s.name == "optimize.join_reorder"]
    assert reorder.attributes["order"] == \
        "region,nation,supplier,lineitem,orders,customer"
    assert reorder.attributes["leaves"] == 6
    assert reorder.attributes["edges"] == 6
    assert reorder.attributes["keys_bounded"] == 12
    assert reorder.attributes["keys_by_rows"] == 0
    optimize_span, = [s for s in prof.spans if s.name == "optimize"]
    assert reorder.parent_id == optimize_span.span_id

    # executed bottom up: region x nation, supplier, lineitem, orders,
    # customer. No join puts out more rows than the lineitem join, and
    # the model's largest estimate is that join's
    joins = [s.attributes for s in prof.spans if s.name == "op.JoinExec"]
    assert len(joins) == 5
    out_rows = [j["out_rows"] for j in joins]
    assert max(out_rows) == out_rows[2]
    assert out_rows[2] <= reorder.attributes["est_rows_max"] * 1.5
    assert all(j["out_capacity"] >= j["out_rows"] for j in joins)
    # the attributes ride the join_phase fetch: one sync a join phase
    sites = [s.attributes["site"] for s in prof.spans if s.name == "sync"]
    assert sites.count("join_phase") == 5
    assert set(sites) <= {"join_phase", "rtf_build", "agg.n_groups",
                          "to_arrow"}

    exp = ORACLES[5](tpch_parquet[1]).reset_index(drop=True)
    assert list(got.n_name) == list(exp.n_name)
    np.testing.assert_allclose(got.revenue.astype(float), exp.revenue,
                               rtol=1e-10)


def test_q5_without_statistics_keeps_the_row_count_proxys_order(
        tpch_spark, tpch_parquet, tmp_path):
    """The fallback is the parent's model: the same tables from a
    writer that leaves statistics out are joined in the parent's order,
    ``customer`` on the nation key alone before ``orders``."""
    for name, path in tpch_parquet[0].items():
        d = tmp_path / name
        d.mkdir()
        for f in sorted(os.listdir(path)):
            pq.write_table(pq.read_table(os.path.join(path, f)),
                           str(d / f), write_statistics=False)
        tpch_spark.read.parquet(str(d)).createOrReplaceTempView(name)
    scans = [l for l in _joins(tpch_spark, _query("tpch-q5")[1])
             if l.startswith("scan")]
    assert scans == ["scan region", "scan nation", "scan supplier",
                     "scan customer", "scan orders", "scan lineitem"]


Q3_PLAN = [
    "JoinExec type=inner on=[('#2:o_orderkey', '#0:l_orderkey')]",
    "JoinExec type=inner on=[('#0:c_custkey', '#1:o_custkey')]",
    "scan customer", "scan orders", "scan lineitem"]


class _ScaledFooters:
    """``METADATA_CACHE`` as it would answer at ``times`` the scale: the
    SF0.2 files' row counts and the ranges of their integer columns
    multiplied (``nation`` and ``region`` do not grow; a date's range
    stays). The reorder reads nothing else of a file, so this is the
    plan at SF1 (x5) and SF10 (x50) with no data made."""

    def __init__(self, times):
        self.real, self.times = io_cache.METADATA_CACHE, times

    def _times(self, path):
        table = os.path.basename(os.path.dirname(path))
        return 1 if table in ("nation", "region") else self.times

    def num_rows(self, path):
        return self.real.num_rows(path) * self._times(path)

    def column_stats(self, path, column):
        st = self.real.column_stats(path, column)
        if st is None or not isinstance(st.lo, int):
            return st
        k = self._times(path)
        return st._replace(lo=st.lo * k, hi=(st.hi + 1) * k - 1)


@pytest.mark.parametrize("times", [1, 5, 50], ids=["sf0.2", "sf1", "sf10"])
def test_q3s_join_order_and_build_sides_are_the_parents(
        tpch_spark, monkeypatch, times):
    monkeypatch.setattr(io_cache, "METADATA_CACHE", _ScaledFooters(times))
    _doc, sql = _query("tpch-q3")
    assert _joins(tpch_spark, sql) == Q3_PLAN
    # and with the parent's proxy (every key by its leaf's rows)
    monkeypatch.setattr(
        jr, "_footer_bound", lambda node, key: None)
    assert _joins(tpch_spark, sql) == Q3_PLAN


@pytest.mark.parametrize("times", [5, 50], ids=["sf1", "sf10"])
def test_q5s_order_at_the_benchmarks_scales(tpch_spark, monkeypatch, times):
    monkeypatch.setattr(io_cache, "METADATA_CACHE", _ScaledFooters(times))
    scans = [l for l in _joins(tpch_spark, _query("tpch-q5")[1])
             if l.startswith("scan")]
    assert scans == ["scan region", "scan nation", "scan supplier",
                     "scan lineitem", "scan orders", "scan customer"]


def test_q3_runs_as_before_one_sync_a_join_phase(tpch_spark, tpch_parquet):
    _doc, sql = _query("tpch-q3")
    got = tpch_spark.sql(sql).toPandas()
    prof = profiler.last_profile()
    sites = [s.attributes["site"] for s in prof.spans if s.name == "sync"]
    assert sites.count("join_phase") == 2
    assert set(sites) <= {"join_phase", "rtf_build", "agg.n_groups",
                          "to_arrow"}
    joins = [s.attributes for s in prof.spans if s.name == "op.JoinExec"]
    assert len(joins) == 2 and all("out_rows" in j for j in joins)
    exp = ORACLES[3](tpch_parquet[1]).reset_index(drop=True)
    assert list(got.l_orderkey) == list(exp.l_orderkey)
    np.testing.assert_allclose(got.revenue.astype(float), exp.revenue,
                               rtol=1e-10)
