"""The configuration ``tpch-sf1-snowflake`` and its cell ``tpch-sf1-join5``
(PR 34): Q5 over the six tables of its join graph. The configuration is
``tpch-sf1-resident``'s deployment without the two tables Q5 does not
touch; the cell runs end to end on the CPU at SF0.01; the two per-layer
metrics read the planner's ``optimize.join_reorder`` span and the
``out_capacity`` attribute of ``op.JoinExec``, and read 0 on a program
that has neither (the parent's side of the cell)."""

import importlib
import os
import sys
import types

import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402
import run as bench_run  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIG = load_json(os.path.join(BENCH, "configs", "tpch-sf1-snowflake.json"))
SF1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))
SIX = ["region", "nation", "supplier", "customer", "orders", "lineitem"]
NEW_METRICS = ("join_reorder_ms", "join_out_capacity_max")


@pytest.mark.parametrize("key", ["guarantees", "session_options",
                                 "process_environment", "limits",
                                 "logical_widths_bytes", "trace",
                                 "scale_factor"])
def test_everything_but_the_tables_is_the_sf1_deployments(key):
    assert CONFIG[key] == SF1[key]


def test_the_six_tables_of_q5s_join_graph_at_the_specs_widths():
    q5 = load_json(os.path.join(BENCH, "queries", "tpch-q5.json"))
    assert CONFIG["tables"] == SIX and set(q5["reads"]) == set(SIX)
    assert CONFIG["reduced"] == ["tables"]
    assert set(SF1["tables"]) - set(SIX) == {"part", "partsupp"}
    for table in SIX:
        assert CONFIG["rows"][table] == SF1["rows"][table]
        assert CONFIG["schema"][table] == SF1["schema"][table]
    assert set(CONFIG["rows"]) == set(CONFIG["schema"]) == set(SIX)
    assert needed_bytes(q5, CONFIG) == 224_560_560
    assert set(SF1["assumed"]) < set(CONFIG["assumed"])
    assert SF1["deployment"] in CONFIG["deployment"]
    assert "min/max statistics" in CONFIG["deployment"]


def test_the_cells_files_resolve():
    cell = bench_run.Cell("tpch-sf1-join5")
    assert cell.entry["config"] == "tpch-sf1-snowflake"
    assert cell.entry["traffic"] == "join-q5-1stream" and cell.chips == 1
    assert cell.traffic["streams"] == 1 and cell.traffic["loop"] == "closed"
    assert list(cell.queries) == ["tpch-q5"]
    assert set(cell.wanted_tables()) == set(SIX)
    assert [m["name"] for m in cell.end_to_end()] == [
        "query_ms_p50", "queries_per_hour", "setup_s"]
    layer = {m["name"]: m for m in cell.per_layer()}
    assert {"scan_hbm_roofline", "device_ms_per_query",
            "host_syncs_per_query", "planner_self_ms"} <= set(layer)
    assert layer["join_reorder_ms"]["layer"] == "Session / planner"
    assert layer["join_out_capacity_max"]["layer"] == "Local executor"
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == ["tpch-sf1-join5"]
        assert layer[name]["moves"] == "query_ms_p50"
    # no other cell's line gains a metric
    for other in ("tpch-sf1-join", "tpch-sf10-join", "tpch-sf1-scanagg"):
        names = {m["name"] for m in bench_run.Cell(other).per_layer()}
        assert not names & set(NEW_METRICS)


def test_the_generators_footers_bound_q5s_join_keys(tmp_path):
    """What ``deployment`` says of the files: the statistics the
    generator's writer leaves in every footer bound each of Q5's twelve
    join keys, the nation keys at 25."""
    from sail_tpu.io.cache import METADATA_CACHE
    q5 = load_json(os.path.join(BENCH, "queries", "tpch-q5.json"))
    paths, _frames, rows, _bytes = datagen.write_tables(
        q5["reads"], 2**31 + 34, 0.01, str(tmp_path), workers=2)
    ranges = {}
    for table, key in [("customer", "c_custkey"), ("customer", "c_nationkey"),
                       ("orders", "o_custkey"), ("orders", "o_orderkey"),
                       ("lineitem", "l_orderkey"), ("lineitem", "l_suppkey"),
                       ("supplier", "s_suppkey"), ("supplier", "s_nationkey"),
                       ("nation", "n_nationkey"), ("nation", "n_regionkey"),
                       ("region", "r_regionkey")]:
        stats = [METADATA_CACHE.column_stats(os.path.join(paths[table], f),
                                             key)
                 for f in sorted(os.listdir(paths[table]))]
        assert all(st is not None and st.distinct is None for st in stats)
        ranges[key] = (max(st.hi for st in stats)
                       - min(st.lo for st in stats) + 1)
    assert ranges["c_nationkey"] == ranges["s_nationkey"] == 25
    assert ranges["n_nationkey"] == 25 and ranges["r_regionkey"] == 5
    assert ranges["c_custkey"] == rows["customer"]
    assert ranges["s_suppkey"] == ranges["l_suppkey"] == rows["supplier"]


# -- the cell, end to end on the CPU -------------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark whose ``tpch-sf1-snowflake`` runs at
    SF0.01 with the CPU tests' session options: the cell, its traffic
    file, its metrics and their readers are the checkout's own."""
    dest = tmp_path_factory.mktemp("bench_snowflake")
    bench_copy.make_copy(dest)
    path = os.path.join(str(dest), "benchmark", "configs",
                        "tpch-sf1-snowflake.json")
    config = load_json(path)
    config["scale_factor"] = 0.01
    config["rows"] = {t: rows if t in ("region", "nation")
                      else int(rows * 0.01)
                      for t, rows in config["rows"].items()}
    config["session_options"] = dict(bench_copy.TEST_SESSION_OPTIONS)
    config["trace"] = {"after_seconds": 0.2, "seconds": 1.0}
    bench_copy.write_json(path, config)
    return dest, bench_copy.load_run_module(dest)


def drive(copy, capsys, trace, seed):
    dest, run = copy
    capsys.readouterr()
    rc = run.main(["--workload", "tpch-sf1-join5", "--seed", str(seed),
                   "--seconds", "1.5", "--trace", str(trace)],
                  require_platform="cpu", root=str(dest))
    captured = capsys.readouterr()
    assert rc == 0
    return result_line(captured.out)


def test_the_cell_runs_and_reports_its_end_to_end_metrics(copy, capsys):
    result = drive(copy, capsys, trace=0, seed=2**31 + 340)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"query_ms_p50", "queries_per_hour",
                                      "setup_s"}
    checks = result["checks"]
    assert checks["worst_rel_err"][0] <= checks["worst_rel_err"][1] == 1e-10
    for name in ("exact_mismatches", "row_count_mismatches",
                 "failed_statements", "not_xla_routes",
                 "result_cache_hits"):
        assert checks[name] == [0, 0], name


def test_both_new_metrics_are_in_the_traced_line(copy, capsys, monkeypatch):
    tracered = importlib.import_module("tracered")   # the copy's own
    monkeypatch.setattr(tracered, "device_planes",
                        lambda planes: ["/host:CPU"])
    result = drive(copy, capsys, trace=1, seed=2**31 + 341)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["join_reorder_ms"]["unit"] == "ms"
    assert 0 < metrics["join_reorder_ms"]["value"] < \
        metrics["plan_ms"]["value"]
    assert metrics["join_out_capacity_max"]["unit"] == "rows"
    # 600 lineitem rows of 20 ASIA suppliers' 12,000: a bucket of that
    # order, not the 60,000-row table
    assert 0 < metrics["join_out_capacity_max"]["value"] <= 16384
    for name in ("plan_ms", "planner_self_ms", "host_syncs_per_query",
                 "executor_self_ms", "device_ms_per_query"):
        assert name in metrics, name


# -- the two readers on profiles with and without what they read ---------------

def _reader(name):
    return bench_run.load_reader(BENCH, f"readers/{name}.py:read")


def _run_of(*profiles):
    return types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles])


@pytest.fixture(scope="module")
def join_profile():
    """A three-way join's profile from this program: one reordered
    tree, two ``op.JoinExec``."""
    import numpy as np
    import pandas as pd
    from sail_tpu import SparkSession, profiler
    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.cache.result.enabled": "false"})
    rng = np.random.default_rng(34)
    spark.createDataFrame(pd.DataFrame({
        "a": rng.integers(0, 50, 4000), "b": rng.integers(0, 20, 4000)})
    ).createOrReplaceTempView("f")
    spark.createDataFrame(pd.DataFrame({"a": np.arange(50)})
                          ).createOrReplaceTempView("da")
    spark.createDataFrame(pd.DataFrame({"b": np.arange(20)})
                          ).createOrReplaceTempView("db")
    spark.sql("SELECT COUNT(*) FROM f JOIN da ON f.a = da.a "
              "JOIN db ON f.b = db.b").toPandas()
    return profiler.last_profile()


def test_the_readers_read_the_span_and_the_attribute(join_profile):
    spans = join_profile.spans
    reorder, = [s for s in spans if s.name == "optimize.join_reorder"]
    assert reorder.attributes["leaves"] == 3
    assert reorder.attributes["keys_by_rows"] == 4     # in-memory leaves
    assert _reader("join_reorder_ms")(_run_of(join_profile)) == \
        pytest.approx(reorder.ms)
    capacities = [s.attributes["out_capacity"] for s in spans
                  if s.name == "op.JoinExec"]
    assert len(capacities) == 2
    assert _reader("join_out_capacity_max")(_run_of(join_profile)) == \
        max(capacities) >= 4000


def _as_the_parent_recorded_it(profile):
    """The same span tree from a program without this PR: no
    ``optimize.join_reorder`` span, no ``out_rows`` / ``out_capacity``."""
    import copy as copy_module
    old = copy_module.copy(profile)
    old.spans = []
    for s in profile.spans:
        if s.name == "optimize.join_reorder":
            continue
        s = copy_module.copy(s)
        s.attributes = {k: v for k, v in s.attributes.items()
                        if k not in ("out_rows", "out_capacity")}
        old.spans.append(s)
    return old


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_0_on_a_program_without_the_span_or_attribute(
        join_profile, name):
    old = _as_the_parent_recorded_it(join_profile)
    assert old.span_count("op.JoinExec") == 2
    assert old.span_count("optimize.join_reorder") == 0
    value = _reader(name)(_run_of(old))
    assert value == 0 and value is not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_nothing_where_no_profile_keeps_a_span_tree(name):
    before_pr26 = types.SimpleNamespace(phases={"optimize": 1.0})
    assert _reader(name)(_run_of(before_pr26, None)) is None


def test_a_statement_without_a_join_reads_0():
    from sail_tpu import SparkSession, profiler
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    spark.sql("SELECT 1 AS x").toPandas()
    profile = profiler.last_profile()
    for name in NEW_METRICS:
        assert _reader(name)(_run_of(profile)) == 0
