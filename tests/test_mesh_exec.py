"""SPMD mesh executor: whole job graphs as one shard_map program whose
exchanges are XLA collectives (all_to_all / all_gather) — the production
path replacing the reference's ShuffleWriteExec + Flight data plane
(crates/sail-execution/src/plan/shuffle_write.rs)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from sail_tpu import SparkSession
from sail_tpu.parallel.mesh_exec import MeshExecutor
from sail_tpu.parallel.mesh import make_mesh


@pytest.fixture()
def spark():
    s = SparkSession.builder.getOrCreate()
    yield s
    s.stop()


def _mesh_run(spark, sql, capture_hlo=False):
    """Resolve SQL and execute through the MeshExecutor explicitly,
    returning (table, executor)."""
    df = spark.sql(sql)
    node = spark._resolve(df._plan)
    conf = dict(spark.conf.items())
    if capture_hlo:
        conf["spark.sail.mesh.captureHlo"] = "true"
    ex = MeshExecutor(mesh=make_mesh(8), config=conf)
    table = ex.execute(node)
    return table, ex


def _local_run(spark, sql):
    from sail_tpu.exec.local import LocalExecutor
    df = spark.sql(sql)
    node = spark._resolve(df._plan)
    return LocalExecutor(dict(spark.conf.items())).execute(node)


def _sorted_df(table: pa.Table) -> pd.DataFrame:
    df = table.to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def test_mesh_two_phase_aggregate(spark):
    rng = np.random.default_rng(0)
    n = 4000
    t = pa.table({
        "k": rng.integers(0, 37, n),
        "v": rng.normal(size=n),
        "w": rng.integers(0, 100, n),
    })
    spark.createDataFrame(t).createOrReplaceTempView("t")
    sql = "SELECT k, SUM(v) AS s, COUNT(*) AS c, MAX(w) AS m FROM t GROUP BY k"
    out, ex = _mesh_run(spark, sql)
    assert out is not None, "mesh executor should support two-phase agg"
    assert ex.last_exchanges >= 1
    exp = _local_run(spark, sql)
    got, want = _sorted_df(out), _sorted_df(exp)
    pd.testing.assert_frame_equal(got, want, check_dtype=False,
                                  rtol=1e-9)


def test_mesh_aggregate_string_keys(spark):
    rng = np.random.default_rng(1)
    n = 3000
    keys = rng.choice(np.array(["alpha", "beta", "gamma", "delta"]), n)
    t = pa.table({"g": keys, "x": rng.integers(0, 1000, n)})
    spark.createDataFrame(t).createOrReplaceTempView("s")
    sql = "SELECT g, SUM(x) AS sx, MIN(g) AS mg FROM s GROUP BY g"
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False)


def test_mesh_shuffle_join(spark):
    rng = np.random.default_rng(2)
    n, m = 5000, 300
    fact = pa.table({
        "fk": rng.integers(0, m, n),
        "amount": rng.normal(size=n),
    })
    dim = pa.table({
        "id": np.arange(m),
        "name": np.array([f"dim{i}" for i in range(m)]),
        "weight": rng.integers(1, 10, m),
    })
    spark.createDataFrame(fact).createOrReplaceTempView("fact")
    spark.createDataFrame(dim).createOrReplaceTempView("dim")
    sql = ("SELECT d.name, SUM(f.amount * d.weight) AS total, COUNT(*) AS c "
           "FROM fact f JOIN dim d ON f.fk = d.id "
           "GROUP BY d.name")
    out, ex = _mesh_run(spark, sql, capture_hlo=True)
    assert out is not None, "mesh executor should support shuffle join + agg"
    # the program must actually contain collective exchanges
    assert ex.last_exchanges >= 2
    assert ex.last_hlo is not None and "all_to_all" in ex.last_hlo
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)


def test_mesh_join_filters_and_projections(spark):
    rng = np.random.default_rng(3)
    n, m = 4000, 500
    orders = pa.table({
        "o_id": np.arange(m, dtype=np.int64),
        "o_cust": rng.integers(0, 50, m),
        "o_total": np.round(rng.uniform(10, 1000, m), 2),
    })
    items = pa.table({
        "i_order": rng.integers(0, m, n),
        "i_qty": rng.integers(1, 20, n),
        "i_price": np.round(rng.uniform(1, 100, n), 2),
    })
    spark.createDataFrame(orders).createOrReplaceTempView("orders")
    spark.createDataFrame(items).createOrReplaceTempView("items")
    sql = ("SELECT o.o_cust, SUM(i.i_qty * i.i_price) AS rev "
           "FROM items i JOIN orders o ON i.i_order = o.o_id "
           "WHERE o.o_total > 200 AND i.i_qty > 2 "
           "GROUP BY o.o_cust")
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)


def test_mesh_duplicate_build_keys_expand(spark):
    # duplicate keys on the build side invalidate the unique-probe SPMD
    # join; the retry protocol must recompile with the many-to-many
    # expanding join and produce every matched pair
    left = pa.table({"k": np.array([1, 2, 3, 4] * 50),
                     "x": np.arange(200)})
    right = pa.table({"k": np.array([1, 1, 2, 3]),  # dup build key 1
                      "y": np.array([10, 11, 20, 30])})
    spark.createDataFrame(left).createOrReplaceTempView("l")
    spark.createDataFrame(right).createOrReplaceTempView("r")
    sql = ("SELECT l.k, SUM(r.y) AS s FROM l JOIN r ON l.k = r.k "
           "GROUP BY l.k")
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)


def test_mesh_global_aggregate(spark):
    """Keyless two-phase aggregation: partials route to partition 0 over
    an empty-key shuffle; exactly one output row survives the merge."""
    t = pa.table({"v": np.arange(1000, dtype=float),
                  "w": np.arange(1000) % 7})
    spark.createDataFrame(t).createOrReplaceTempView("g")
    sql = "SELECT SUM(v) AS s, COUNT(*) AS c, MAX(w) AS m FROM g"
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    df = out.to_pandas()
    assert len(df) == 1
    assert df.iloc[0, 0] == 999 * 500.0
    assert df.iloc[0, 1] == 1000
    assert df.iloc[0, 2] == 6


def test_mesh_left_join_residual(spark):
    """Residual predicate on a LEFT join: failing matches null the build
    side but keep the probe row; duplicate build keys expand."""
    left = pa.table({"k": np.arange(100) % 10, "x": np.arange(100)})
    right = pa.table({"k": np.array([1, 1, 2, 3]),
                      "y": np.array([10, 11, 20, 30])})
    spark.createDataFrame(left).createOrReplaceTempView("lr_l")
    spark.createDataFrame(right).createOrReplaceTempView("lr_r")
    sql = ("SELECT l.k, COUNT(*) AS n, COUNT(r.y) AS m "
           "FROM lr_l l LEFT JOIN lr_r r ON l.k = r.k AND r.y > 10 "
           "GROUP BY l.k")
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)


def test_mesh_via_session_conf(spark):
    """End-to-end: SQL through the session with mesh forced executes the
    collective path and matches."""
    rng = np.random.default_rng(4)
    n = 2000
    t = pa.table({"k": rng.integers(0, 11, n), "v": rng.normal(size=n)})
    spark.createDataFrame(t).createOrReplaceTempView("m")
    spark.conf.set("spark.sail.execution.mesh", "force")
    try:
        got = spark.sql(
            "SELECT k, SUM(v) AS s FROM m GROUP BY k ORDER BY k").toArrow()
    finally:
        spark.conf.reset("spark.sail.execution.mesh")
    exp = _local_run(
        spark, "SELECT k, SUM(v) AS s FROM m GROUP BY k ORDER BY k")
    pd.testing.assert_frame_equal(got.to_pandas(), exp.to_pandas(),
                                  check_dtype=False, rtol=1e-9)
    assert getattr(spark, "_last_mesh_executor", None) is not None
    assert spark._last_mesh_executor.last_exchanges >= 1


def test_mesh_overflow_retry(spark):
    """More groups than the first-attempt table ⇒ overflow retry path."""
    rng = np.random.default_rng(5)
    n = 6000
    t = pa.table({"k": np.arange(n) % 5000,  # ~5000 distinct groups
                  "v": rng.normal(size=n)})
    spark.createDataFrame(t).createOrReplaceTempView("big")
    sql = "SELECT k, SUM(v) AS s FROM big GROUP BY k"
    df = spark.sql(sql)
    node = spark._resolve(df._plan)
    conf = dict(spark.conf.items())
    conf["spark.sail.mesh.maxGroups"] = "64"  # force first-attempt overflow
    ex = MeshExecutor(mesh=make_mesh(8), config=conf)
    out = ex.execute(node)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)


def test_mesh_shuffle_join_string_keys(spark):
    """Equal strings carry DIFFERENT dictionary codes on the two sides;
    the shuffle must route by value (bind-time value-hash LUT), or the
    join silently drops matches."""
    rng = np.random.default_rng(6)
    n, m = 3000, 40
    names = np.array([f"key{i:03d}" for i in range(m)])
    # left table sees keys in shuffled order => different code assignment
    left_keys = rng.permutation(names)
    fact = pa.table({"k": rng.choice(left_keys, n),
                     "v": rng.normal(size=n)})
    dim = pa.table({"k2": names, "w": rng.integers(1, 5, m)})
    spark.createDataFrame(fact).createOrReplaceTempView("sfact")
    spark.createDataFrame(dim).createOrReplaceTempView("sdim")
    sql = ("SELECT d.k2 AS k2, SUM(f.v * d.w) AS s, COUNT(*) AS c "
           "FROM sfact f JOIN sdim d ON f.k = d.k2 GROUP BY d.k2")
    out, ex = _mesh_run(spark, sql)
    assert out is not None
    exp = _local_run(spark, sql)
    pd.testing.assert_frame_equal(_sorted_df(out), _sorted_df(exp),
                                  check_dtype=False, rtol=1e-9)
    # every fact row matches: none may be dropped by mis-routing
    assert out.to_pandas()["c"].sum() == 3000


def test_all_tpch_queries_use_mesh_path(spark, monkeypatch):
    """Coverage lock: every TPC-H query routes (at least a subtree)
    through the SPMD mesh executor on the 8-device test mesh — the
    round-4 review flagged mesh op coverage as a fallback cliff.
    The session records _last_mesh_executor only when the mesh program
    actually produced the result (session.py _try_mesh_execute). The
    router's row floor is held at 0: these SF0.005 tables sit under
    it, and this test is about op coverage, not about the cost gate.
    ``auto`` mode keeps the session's MeshUnsupported-only fallback in
    play — any other exception from the executor fails the test."""
    from sail_tpu.benchmarks.tpch_data import register_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    from sail_tpu.exec import router

    monkeypatch.setattr(router, "mesh_min_rows", lambda: 0)

    # Local-oracle comparison runs only for the historically
    # fallback-prone classes (dup-key expansion, global agg, scalar
    # subquery, non-inner residual, empty result) — comparing all 22
    # doubles an already-long test; full local-path correctness is
    # test_tpch.py's job.
    oracle_qs = {3, 6, 11, 13, 20, 21}
    spark.conf.set("spark.sail.execution.mesh", "auto")
    try:
        register_tpch(spark, sf=0.005)
        fell_back = []
        for q in sorted(QUERIES):
            spark._last_mesh_executor = None
            got = spark.sql(QUERIES[q]).toArrow()
            if getattr(spark, "_last_mesh_executor", None) is None:
                fell_back.append(q)
                continue
            if q not in oracle_qs:
                continue
            exp = _local_run(spark, QUERIES[q])
            g, e = got.to_pandas(), exp.to_pandas()
            g.columns = list(e.columns)
            pd.testing.assert_frame_equal(
                g.sort_values(list(g.columns), kind="stable")
                 .reset_index(drop=True),
                e.sort_values(list(e.columns), kind="stable")
                 .reset_index(drop=True),
                check_dtype=False, rtol=1e-6, atol=1e-9)
        assert not fell_back, f"queries off the mesh path: {fell_back}"
    finally:
        spark.conf.reset("spark.sail.execution.mesh")


@pytest.mark.parametrize("rows,copies,fits", [
    (1 << 20, 4, True),
    (1 << 30, 1, True),
    (1 << 31, 1, False),      # q5 at SF1, third attempt
    (1 << 29, 4, False),      # an exchange's P send buckets in one array
])
def test_scaled_capacity_overflow_is_the_declared_signal(rows, copies, fits):
    """A retry multiplier that scales a capacity past int32 indexing is
    "capacity overflow" (MeshUnsupported: the session falls back), not
    an OverflowError from inside the trace."""
    from sail_tpu.parallel.mesh_exec import (MeshUnsupported,
                                             _scaled_capacity)
    if fits:
        assert _scaled_capacity(rows, copies) >= rows
    else:
        with pytest.raises(MeshUnsupported, match="capacity overflow"):
            _scaled_capacity(rows, copies)
