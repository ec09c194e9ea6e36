"""The configuration ``tpch-sf10-resident`` and its cell
``tpch-sf10-join`` (PR 28): the needed-bytes figure computed from the
configuration's rows and the statement's columns, the cell's files, and the
two per-layer metrics that read the executor's ``spill`` spans."""

import os
import sys
import types

import numpy as np
import pandas as pd
import pytest

from bench_copy import ROOT, load_json

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402
import run as bench_run  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIG = load_json(os.path.join(BENCH, "configs", "tpch-sf10-resident.json"))
SF1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))


def test_needed_bytes_are_rows_times_logical_widths():
    q3 = load_json(os.path.join(BENCH, "queries", "tpch-q3.json"))
    assert "needed_bytes" not in CONFIG
    assert needed_bytes(q3, CONFIG) == 2_058_000_000 == \
        60_000_000 * 28 + 15_000_000 * 24 + 1_500_000 * 12
    assert needed_bytes(q3, CONFIG) == 10 * needed_bytes(q3, SF1)


def test_rows_are_the_generators_at_scale_factor_10():
    assert CONFIG["scale_factor"] == 10
    actual = datagen.table_rows(10)
    assert set(CONFIG["rows"]) == set(CONFIG["tables"]) == set(actual)
    for table, rows in CONFIG["rows"].items():
        assert abs(actual[table] - rows) <= 1e-5 * rows, table
        ten = SF1["rows"][table] * (1 if table in ("region", "nation")
                                    else 10)
        assert rows == ten, table


@pytest.mark.parametrize("key", ["schema", "guarantees", "session_options",
                                 "process_environment", "limits",
                                 "logical_widths_bytes"])
def test_everything_but_the_scale_is_the_sf1_deployments(key):
    assert CONFIG[key] == SF1[key]


def test_nothing_is_reduced_and_the_trace_holds_whole_statements():
    assert CONFIG["reduced"] == []
    assert CONFIG["trace"] == {"after_seconds": 1.0, "seconds": 30.0}
    assert CONFIG["limits"]["worst_rel_err"] == 1e-10
    assert CONFIG["limits"]["not_xla_routes"] == 0
    assert set(SF1["assumed"]) < set(CONFIG["assumed"])


def test_the_cells_files_resolve():
    cell = bench_run.Cell("tpch-sf10-join")
    assert cell.entry["config"] == "tpch-sf10-resident"
    assert cell.entry["traffic"] == "join-q3-1stream" and cell.chips == 1
    assert list(cell.queries) == ["tpch-q3"]
    assert set(cell.wanted_tables()) == {"customer", "orders", "lineitem"}
    assert [m["name"] for m in cell.end_to_end()] == [
        "query_ms_p50", "queries_per_hour", "setup_s"]
    layer = {m["name"]: m for m in cell.per_layer()}
    assert "scan_hbm_roofline" in layer
    for name in ("spills_per_query", "spill_mb_per_query"):
        assert layer[name]["layer"] == "Local executor"
        assert "workloads" not in layer[name]


# -- the two readers ---------------------------------------------------------

def _reader(name):
    return bench_run.load_reader(BENCH, f"readers/{name}.py:read")


def _profile_of_a_join(monkeypatch, spill_rows):
    from sail_tpu import SparkSession, profiler
    if spill_rows is not None:
        monkeypatch.setenv("SAIL_EXECUTION__JOIN_SPILL_ROWS", str(spill_rows))
    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.cache.result.enabled": "false"})
    rng = np.random.default_rng(28)
    spark.createDataFrame(pd.DataFrame({
        "k": rng.integers(0, 200, 3000), "v": rng.random(3000)})
    ).createOrReplaceTempView("l")
    spark.createDataFrame(pd.DataFrame({
        "k": np.arange(150), "w": rng.random(150)})
    ).createOrReplaceTempView("r")
    spark.sql("SELECT COUNT(*) FROM l JOIN r ON l.k = r.k").toPandas()
    return profiler.last_profile()


def _run_of(*profiles):
    return types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles])


def test_readers_return_0_on_a_span_tree_without_spill(monkeypatch):
    profile = _profile_of_a_join(monkeypatch, None)
    assert profile.span_count("spill") == 0
    assert _reader("spills_per_query")(_run_of(profile)) == 0
    assert _reader("spill_mb_per_query")(_run_of(profile)) == 0


def test_readers_count_one_spill_and_its_bytes(monkeypatch):
    profile = _profile_of_a_join(monkeypatch, 1000)
    spill, = [s for s in profile.spans if s.name == "spill"]
    assert spill.attributes["kind"] == "join"
    assert _reader("spills_per_query")(_run_of(profile)) == 1
    assert _reader("spill_mb_per_query")(_run_of(profile)) == \
        pytest.approx(spill.attributes["bytes"] / 1e6)
    assert profile.spill_bytes == spill.attributes["bytes"] > 0


def test_readers_return_nothing_where_no_profile_keeps_a_span_tree():
    old = types.SimpleNamespace(spill_bytes=0)       # a profile before PR 26
    for name in ("spills_per_query", "spill_mb_per_query"):
        assert _reader(name)(_run_of(old, None)) is None
