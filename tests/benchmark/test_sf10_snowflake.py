"""The configuration ``tpch-sf10-snowflake`` and its cell
``tpch-sf10-join5`` (PR 36): ``tpch-sf1-snowflake``'s deployment at
``tpch-sf10-resident``'s scale, trace and spill rule. The file differs
from the SF1 one in what the scale changes and nothing else; the bytes
its ``deployment`` states are rows (or capacities) times widths; the cell
runs end to end on the CPU at SF0.02; the four per-layer metrics read the
attributes this PR puts on ``op.JoinExec`` and ``op.ScanExec`` and read 0
on a program that has none of them (the parent's side of the cell)."""

import copy as copy_module
import importlib
import os
import sys
import types

import numpy as np
import pandas as pd
import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line
from test_sf1_snowflake import SIX, _reader, _run_of

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402
import run as bench_run  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIG = load_json(os.path.join(BENCH, "configs", "tpch-sf10-snowflake.json"))
SNOW1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-snowflake.json"))
SF10 = load_json(os.path.join(BENCH, "configs", "tpch-sf10-resident.json"))
SF1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))
Q5 = load_json(os.path.join(BENCH, "queries", "tpch-q5.json"))
NEW_METRICS = ("join_build_capacity_max", "join_spill_margin_pct",
               "resident_scan_gb", "rtf_rows_pruned_per_query")
#: what the scale changes; every other key is tpch-sf1-snowflake's
BY_SCALE = {"name", "source", "deployment", "scale_factor", "rows",
            "trace", "assumed"}


@pytest.mark.parametrize("key", sorted(set(SNOW1) - BY_SCALE))
def test_everything_but_the_scale_is_the_sf1_snowflakes(key):
    assert CONFIG[key] == SNOW1[key]


def test_only_what_the_scale_changes_differs():
    assert set(CONFIG) == set(SNOW1)
    assert {k for k in CONFIG if CONFIG[k] != SNOW1[k]} == BY_SCALE
    assert CONFIG["trace"] == SF10["trace"] == {"after_seconds": 1.0,
                                                "seconds": 30.0}
    assert CONFIG["scale_factor"] == SF10["scale_factor"] == 10
    # no session option or environment variable beyond the SF1 file's
    assert CONFIG["session_options"] == {
        "spark.sail.cache.result.enabled": "false"}
    assert list(CONFIG["process_environment"]) == [
        "SAIL_TELEMETRY__PROFILE_RING_CAPACITY"]


@pytest.mark.parametrize("snowflake, whole", [(SNOW1, SF1), (CONFIG, SF10)],
                         ids=["tpch-sf1-snowflake", "tpch-sf10-snowflake"])
def test_the_six_tables_of_q5s_join_graph_at_the_specs_rows_and_widths(
        snowflake, whole):
    assert snowflake["tables"] == SIX and set(Q5["reads"]) == set(SIX)
    assert snowflake["reduced"] == ["tables"]
    assert set(whole["tables"]) - set(SIX) == {"part", "partsupp"}
    assert set(snowflake["rows"]) == set(snowflake["schema"]) == set(SIX)
    actual = datagen.table_rows(snowflake["scale_factor"])
    for table in SIX:
        assert snowflake["rows"][table] == whole["rows"][table]
        assert snowflake["schema"][table] == whole["schema"][table]
        assert abs(actual[table] - snowflake["rows"][table]) \
            <= 1e-5 * snowflake["rows"][table], table
    assert set(whole["assumed"][:5]) < set(snowflake["assumed"])
    assert "min/max statistics" in snowflake["deployment"]


def test_the_bytes_the_deployment_states_are_rows_times_widths():
    logical = needed_bytes(Q5, CONFIG)
    assert logical == 2_245_600_560 == (
        60_000_000 * 32 + 15_000_000 * 20 + 1_500_000 * 16
        + 100_000 * 16 + 25 * 20 + 5 * 12)
    text = CONFIG["deployment"]
    assert f"{logical / 1e9:.2f} GB logical" in text
    # lineitem on the device: four 8-byte columns and the selection byte
    # for every row of the capacity bucket its 59,999,997 rows take
    capacity = 67_108_864
    assert f"{capacity:,}-row capacity" in text
    assert f"{capacity * (4 * 8 + 1) / 1e9:.2f} GB" in text
    assert "one chip's share of scale factor 40 on a four-chip host" in text
    assert "out_of_core" in text and "budget" in text
    assert any("in_list_max" in line or "inListMax" in line
               for line in CONFIG["assumed"])
    assert any("run_seconds 40 holds" in line for line in CONFIG["assumed"])
    entry, = [c for c in bench_run.Cell("tpch-sf10-join5").benchmark[
        "configs"] if c["name"] == "tpch-sf10-snowflake"]
    assert entry["reduced"] == ["tables"] and "SF40" in entry["source"]


def test_the_cells_files_resolve():
    cell = bench_run.Cell("tpch-sf10-join5")
    assert cell.entry["config"] == "tpch-sf10-snowflake"
    assert cell.entry["traffic"] == "join-q5-1stream" and cell.chips == 1
    assert cell.traffic["streams"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["warm_cycles"] == 1
    assert list(cell.queries) == ["tpch-q5"]
    assert cell.queries["tpch-q5"]["reference"] == "tpch_oracle:q5"
    assert set(cell.wanted_tables()) == set(SIX)
    assert [m["name"] for m in cell.end_to_end()] == [
        "query_ms_p50", "queries_per_hour", "setup_s"]
    layer = {m["name"]: m for m in cell.per_layer()}
    assert {"scan_hbm_roofline", "device_ms_per_query", "spills_per_query",
            "host_syncs_per_query", "peak_hbm_gb"} <= set(layer)
    # the two Q5-only metrics' lists are the accepted benchmark's: an
    # entry that is there is not edited (PERF.md, Open questions)
    assert not {"join_reorder_ms", "join_out_capacity_max"} & set(layer)
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == ["tpch-sf10-join5"]
        assert layer[name]["layer"] == "Local executor"
        assert layer[name]["source"] == "program_counter"
    assert [layer[n]["moves"] for n in NEW_METRICS] == [
        "query_ms_p50", "query_ms_p50", "queries_per_hour", "query_ms_p50"]
    # no other cell's line gains a metric
    for other in ("tpch-sf1-join5", "tpch-sf10-join", "tpch-sf1-scanagg"):
        names = {m["name"] for m in bench_run.Cell(other).per_layer()}
        assert not names & set(NEW_METRICS)


# -- the cell, end to end on the CPU -------------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark whose ``tpch-sf10-snowflake`` runs at
    SF0.02 with the CPU tests' session options; the cell, its traffic
    file, its metrics and their readers are the checkout's own."""
    dest = tmp_path_factory.mktemp("bench_snowflake10")
    bench_copy.make_copy(dest)
    path = os.path.join(str(dest), "benchmark", "configs",
                        "tpch-sf10-snowflake.json")
    config = load_json(path)
    config["scale_factor"] = 0.02
    config["rows"] = {t: rows if t in ("region", "nation")
                      else int(rows * 0.002)
                      for t, rows in config["rows"].items()}
    config["session_options"] = dict(bench_copy.TEST_SESSION_OPTIONS)
    config["trace"] = {"after_seconds": 0.2, "seconds": 1.0}
    bench_copy.write_json(path, config)
    return dest, bench_copy.load_run_module(dest)


def test_the_cell_runs_traced_with_the_four_new_metrics_in_its_line(
        copy, capsys, monkeypatch):
    from sail_tpu.exec import local as lm
    dest, run = copy
    tracered = importlib.import_module("tracered")   # the copy's own
    monkeypatch.setattr(tracered, "device_planes",
                        lambda planes: ["/host:CPU"])
    # the CPU reports no memory; a v5e's, so that out_of_core has a budget
    monkeypatch.setattr(lm, "_device_memory_stats", lambda: {
        "bytes_limit": 16_900_000_000, "bytes_in_use": 400_000_000})
    capsys.readouterr()
    rc = run.main(["--workload", "tpch-sf10-join5", "--seed",
                   str(2**31 + 360), "--seconds", "1.5", "--trace", "1"],
                  require_platform="cpu", root=str(dest))
    result = result_line(capsys.readouterr().out)
    assert rc == 0 and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    checks = result["checks"]
    assert checks["worst_rel_err"][0] <= checks["worst_rel_err"][1] == 1e-10
    for name in ("exact_mismatches", "row_count_mismatches",
                 "failed_statements", "not_xla_routes",
                 "result_cache_hits"):
        assert checks[name] == [0, 0], name
    metrics = result["metrics"]
    assert [metrics[n]["unit"] for n in NEW_METRICS] == [
        "rows", "%", "GB", "rows"]
    # 119,997 lineitem rows, unpruned (40 ASIA suppliers are a list here;
    # it is the orders join's build that is widest at this scale, or
    # lineitem's bucket): a capacity of that order, not a constant
    rows = datagen.table_rows(0.02)
    assert rows["orders"] * 0.1 < metrics["join_build_capacity_max"]["value"] \
        <= 131_072
    assert 0 < metrics["join_spill_margin_pct"]["value"] < 1.0
    # at least lineitem's pruned rows at 33 bytes each, under a MB more
    assert 0 < metrics["resident_scan_gb"]["value"] < 0.01
    assert metrics["rtf_rows_pruned_per_query"]["value"] > 0
    assert metrics["spills_per_query"]["value"] == 0
    for name in ("plan_ms", "host_syncs_per_query", "executor_self_ms",
                 "device_ms_per_query", "planner_self_ms"):
        assert name in metrics, name
    assert "join_out_capacity_max" not in metrics


# -- the four readers on profiles with and without what they read --------------

@pytest.fixture(scope="module")
def pruned_join_profile():
    """A two-way join's profile from this program, under a device that
    reports its memory: half the fact's keys have no dimension row, so
    the runtime filter prunes the fact's scan."""
    from sail_tpu import SparkSession, profiler
    from sail_tpu.exec import local as lm
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lm, "_device_memory_stats", lambda: {
            "bytes_limit": 1_000_000_000, "bytes_in_use": 200_000_000})
        lm.clear_caches()
        spark = SparkSession({"spark.sail.execution.mesh": "off",
                              "spark.sail.cache.result.enabled": "false"})
        rng = np.random.default_rng(36)
        spark.createDataFrame(pd.DataFrame({
            "a": rng.integers(0, 100, 4000), "v": rng.random(4000)})
        ).createOrReplaceTempView("f36")
        spark.createDataFrame(pd.DataFrame({"a": np.arange(50)})
                              ).createOrReplaceTempView("d36")
        spark.sql("SELECT SUM(v) FROM f36 JOIN d36 ON f36.a = d36.a"
                  ).toPandas()
        return profiler.last_profile()


def test_the_readers_read_the_new_attributes(pruned_join_profile):
    spans = pruned_join_profile.spans
    join, = [s.attributes for s in spans if s.name == "op.JoinExec"]
    scans = [s.attributes for s in spans if s.name == "op.ScanExec"]
    assert len(scans) == 2
    run = _run_of(pruned_join_profile)
    assert join["rtf_listed"] is True and join["rtf_ndv"] == 50
    assert join["rtf_pushed"] == 1 and join["rtf_dropped_by_footer"] == 0
    assert _reader("join_build_capacity_max")(run) == \
        join["build_capacity"] >= 50
    assert join["probe_capacity"] < 4000       # the pruned fact's bucket
    assert join["budget_bytes"] == int(0.75 * join["free_bytes"]) \
        == 600_000_000
    assert _reader("join_spill_margin_pct")(run) == pytest.approx(
        100.0 * join["working_set_bytes"] / 600_000_000)
    assert {a["fragment"] for a in scans} == {"decoded"}
    assert sorted(a["runtime_conjuncts"] for a in scans) == [0, 3]
    assert all(a["capacity"] >= a["rows"] and a["bytes"] >= 9 * a["rows"]
               for a in scans)
    assert _reader("resident_scan_gb")(run) == pytest.approx(
        sum(a["bytes"] for a in scans) / 1e9)
    pruned = _reader("rtf_rows_pruned_per_query")(run)
    assert pruned == pruned_join_profile.rtf_rows_pruned
    assert 1500 < pruned == 4000 - min(a["rows"] for a in scans
                                       if a["runtime_conjuncts"])


def _as_the_parent_recorded_it(profile):
    """The same span tree from a program without this PR: no capacities
    or budget on ``op.JoinExec``, nothing on ``op.ScanExec``; and a
    statement whose filters pruned nothing."""
    old = copy_module.copy(profile)
    old.rtf_rows_pruned = 0
    old.spans = []
    for s in profile.spans:
        s = copy_module.copy(s)
        s.attributes = {
            k: v for k, v in s.attributes.items()
            if s.name not in ("op.JoinExec", "op.ScanExec")
            or k in ("working_set_bytes", "free_bytes", "spilled",
                     "out_rows", "out_capacity")}
        old.spans.append(s)
    return old


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_0_on_a_program_without_the_attributes(
        pruned_join_profile, name):
    old = _as_the_parent_recorded_it(pruned_join_profile)
    assert old.span_count("op.JoinExec") == 1
    assert old.span_count("op.ScanExec") == 2
    value = _reader(name)(_run_of(old))
    assert value == 0 and value is not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_nothing_where_no_profile_keeps_a_span_tree(name):
    before_pr26 = types.SimpleNamespace(rtf_rows_pruned=7)
    assert _reader(name)(_run_of(before_pr26, None)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_statement_without_a_join_or_a_scan_reads_0(name):
    from sail_tpu import SparkSession, profiler
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    spark.sql("SELECT 1 AS x").toPandas()
    assert _reader(name)(_run_of(profiler.last_profile())) == 0
