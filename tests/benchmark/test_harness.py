"""``benchmark/run.py`` driven end to end on the CPU at SF0.01, with the
look for a chip skipped (``require_platform="cpu"``):

- a throw-away cell, configuration and per-layer metric added as NEW
  files to a temp copy are found and run, and no file that was there is
  edited;
- the answers of Q1, Q6, Q3 and Q5 equal the reference through the
  harness's own path;
- with the timed path broken underneath, ``correct`` comes out false,
  once for each fault a cell of this benchmark can have;
- without a TPU the run ends before any data is made.
"""

import hashlib
import importlib
import json
import os
import sys

import pyarrow as pa
import pyarrow.compute as pc
import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line

sys.path.insert(0, ROOT)


def file_hashes(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy with a throw-away scan cell (Q1+Q6) added."""
    dest = tmp_path_factory.mktemp("bench_copy")
    cell = bench_copy.make_copy(dest)
    return dest, cell, bench_copy.load_run_module(dest)


def drive(copy, capsys, trace=0, seed=2**31 + 77, seconds=0.5):
    dest, cell, run = copy
    capsys.readouterr()
    rc = run.main(["--workload", cell, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_platform="cpu", root=str(dest))
    captured = capsys.readouterr()
    assert rc == 0
    return result_line(captured.out), captured


def test_added_files_edit_nothing_that_was_there(copy):
    dest, _cell, _run = copy
    before = file_hashes(os.path.join(ROOT, "benchmark"))
    after = file_hashes(os.path.join(str(dest), "benchmark"))
    assert {k: after[k] for k in before} == before
    added = sorted(set(after) - set(before))
    assert added == ["configs/throwaway-config.json",
                     "metrics/throwaway_rows.json",
                     "readers/throwaway_rows.py",
                     "traffic/throwaway-traffic.json"]
    old = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    new = load_json(os.path.join(str(dest), "BENCHMARK.json"))
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) == len(old[key]) + 1
    assert {k: new[k] for k in new if k not in
            ("configs", "workloads", "per_layer")} == \
        {k: old[k] for k in old if k not in
         ("configs", "workloads", "per_layer")}


def test_the_added_cell_runs_and_reports_end_to_end_metrics(copy, capsys):
    result, captured = drive(copy, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 2
    # the window holds whole cycles of the two-statement mix
    assert result["attempted"] % 2 == 0
    assert set(result["metrics"]) == {"query_ms_p50", "queries_per_hour",
                                      "setup_s"}
    for metric in result["metrics"].values():
        assert metric["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    checks = result["checks"]
    assert checks["worst_rel_err"][0] <= checks["worst_rel_err"][1]
    assert checks["exact_mismatches"] == [0, 0]
    assert checks["not_xla_routes"] == [0, 0]
    # each number beside its limit, last on standard error too
    err = captured.err.strip().splitlines()
    assert err[-1] == "correct: True"
    assert any(line.startswith("check worst_rel_err: ") for line in err)
    steps = [json.loads(line)["step"] for line in captured.out.splitlines()
             if line.startswith('{"step"')]
    assert steps == ["device", "data", "first_calls", "warm_cycle", "window",
                     "compare"]


def test_the_added_metric_is_read_in_the_traced_run(copy, capsys,
                                                   monkeypatch):
    _dest, _cell, run = copy
    tracered = importlib.import_module("tracered")   # the copy's own
    # the CPU's trace has no device plane: let its host plane stand in,
    # so that the whole traced path runs (its op line is empty)
    monkeypatch.setattr(tracered, "device_planes",
                        lambda planes: ["/host:CPU"])
    result, _captured = drive(copy, capsys, trace=1, seconds=1.5)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["throwaway_rows"]["value"] >= 5
    assert metrics["throwaway_rows"]["unit"] == "rows"
    for name in ("wire_ms", "plan_ms", "execute_ms", "upload_mb_per_query",
                 "compiles_in_window", "first_calls_s", "device_idle_pct"):
        assert name in metrics, name
    assert metrics["plan_ms"]["value"] > 0
    assert metrics["wire_ms"]["value"] > 0
    # nothing ran on a device: a share of a roofline is left out, not 0
    assert "scan_hbm_roofline" not in metrics
    assert "query_ms_p50" not in metrics
    assert result["device"]["window_s"] == pytest.approx(0.5, abs=0.2)
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["breakdown"]["idle_gaps"]


def test_a_statement_that_outlasts_the_trace_is_still_read(
        copy, capsys, monkeypatch):
    """PR 32's case through the whole harness: the traced window lies
    inside one slow statement, none ends in it, and the device time
    per statement is in the line all the same."""
    import time
    from sail_tpu.spark_connect.client import SparkConnectClient
    tracered = importlib.import_module("tracered")   # the copy's own
    real = SparkConnectClient.sql
    calls = {"n": 0}

    def slow_sql(self, query):
        calls["n"] += 1
        if calls["n"] > 6:              # past the first calls and warm cycles
            time.sleep(1.2)
        return real(self, query)

    def half_busy(lines):
        (_n, t0, t1), = tracered.host_spans({"/host:CPU": lines},
                                            tracered.WINDOW_SPAN)
        return [["%fake = f32[] add(x)", t0, (t1 - t0) / 2]]

    monkeypatch.setattr(SparkConnectClient, "sql", slow_sql)
    monkeypatch.setattr(tracered, "device_planes",
                        lambda planes: ["/host:CPU"])
    monkeypatch.setattr(tracered, "op_events", half_busy)
    result, _captured = drive(copy, capsys, trace=1, seconds=0.5)
    assert result["correct"] is True and result["attempted"] == 2
    device, metrics = result["device"], result["metrics"]
    assert device["window_s"] == pytest.approx(0.5, abs=0.1)
    assert device["busy_s"] == pytest.approx(device["window_s"] / 2)
    # busy share x the statement's own time: over a second, not 250 ms
    assert 1200 / 2 <= metrics["device_ms_per_query"]["value"] <= 3000 / 2
    # a CPU has no row in peaks.json, so no roofline here: that reader's
    # reading of this case is test_window_shares.py's
    assert "scan_hbm_roofline" not in metrics


def test_same_seed_same_answers_and_order(copy):
    _dest, cell, run = copy
    c = run.Cell(cell, str(copy[0]))
    assert c.stream_orders(5) == c.stream_orders(5)
    assert sorted(c.stream_orders(5)[0]) == sorted(c.traffic["cycle"])
    assert {tuple(c.stream_orders(s)[0]) for s in range(20)} == \
        {("tpch-q1", "tpch-q6"), ("tpch-q6", "tpch-q1")}


def test_q1_q6_q3_q5_equal_the_reference_through_the_harness(
        tmp_path, capsys):
    cell = bench_copy.make_copy(
        tmp_path, cycle=("tpch-q1", "tpch-q6", "tpch-q3", "tpch-q5"),
        cell="throwaway-all-four")
    run = bench_copy.load_run_module(tmp_path)
    result, _captured = drive((tmp_path, cell, run), capsys, seed=11,
                              seconds=0.2)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"]["row_count_mismatches"] == [0, 0]
    assert result["checks"]["exact_mismatches"] == [0, 0]
    assert result["checks"]["worst_rel_err"][0] < 1e-12


# -- the timed path broken underneath -----------------------------------------

def _alter(table, how):
    if how == "integer":
        col = table.column("count_order") if "count_order" in \
            table.column_names else None
        if col is None:
            return table
        i = table.column_names.index("count_order")
        return table.set_column(i, "count_order", pc.add(col, 1))
    if how == "decimal":
        i = table.num_columns - 1 if "revenue" in table.column_names else 2
        name = table.column_names[i]
        scaled = pc.multiply(table.column(i).cast(pa.float64()), 1.000001)
        return table.set_column(i, name, scaled)
    if how == "row":
        return table.slice(0, max(table.num_rows - 1, 0))
    raise AssertionError(how)


@pytest.mark.parametrize("how,check", [
    ("integer", "exact_mismatches"),
    ("decimal", "worst_rel_err"),
    ("row", "row_count_mismatches"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        copy, capsys, monkeypatch, how, check):
    from sail_tpu.spark_connect.client import SparkConnectClient
    real = SparkConnectClient.sql
    calls = {"n": 0}

    def sql(self, query):
        table = real(self, query)
        calls["n"] += 1
        # only some answers, and none of the warm-up's
        return _alter(table, how) if calls["n"] > 8 and calls["n"] % 3 == 0 \
            else table

    monkeypatch.setattr(SparkConnectClient, "sql", sql)
    result, captured = drive(copy, capsys, seconds=1.0)
    assert result["attempted"] > 4
    assert result["correct"] is False
    value, limit = result["checks"][check]
    assert value > limit
    assert captured.err.strip().splitlines()[-1] == "correct: False"


def test_half_of_the_rows_left_out_is_not_correct(copy, capsys, monkeypatch):
    import pyarrow.parquet as pq
    datagen = sys.modules["datagen"]
    real = datagen.write_tables

    def write_half(wanted, seed, sf, out_dir, **kw):
        paths, frames, rows, nbytes = real(wanted, seed, sf, out_dir, **kw)
        for name in os.listdir(paths["lineitem"]):
            path = os.path.join(paths["lineitem"], name)
            table = pq.read_table(path)
            pq.write_table(table.slice(0, table.num_rows // 2), path)
        return paths, frames, rows, nbytes

    monkeypatch.setattr(datagen, "write_tables", write_half)
    result, _captured = drive(copy, capsys)
    assert result["correct"] is False
    assert result["checks"]["exact_mismatches"][0] > 0
    assert result["checks"]["worst_rel_err"][0] > 0.1


def test_a_statement_that_fails_is_counted_and_not_correct(
        copy, capsys, monkeypatch):
    from sail_tpu.spark_connect.client import SparkConnectClient
    real = SparkConnectClient.sql
    calls = {"n": 0}

    def sql(self, query):
        calls["n"] += 1
        if calls["n"] == 10:
            raise RuntimeError("the server went away")
        return real(self, query)

    monkeypatch.setattr(SparkConnectClient, "sql", sql)
    result, _captured = drive(copy, capsys, seconds=1.0)
    assert calls["n"] > 10
    assert result["failed"] == 1 and result["correct"] is False
    assert result["checks"]["failed_statements"] == [1, 0]
    # the failed statement counts as the worst latency, not as none
    assert result["metrics"]["query_ms_p50"]["value"] > 0


@pytest.mark.parametrize("option,value,check", [
    ("spark.sail.cache.result.enabled", "true", "result_cache_hits"),
    ("spark.sail.execution.backend.force", "", "not_xla_routes"),
])
def test_an_answer_that_did_no_device_work_is_not_correct(
        tmp_path, capsys, option, value, check):
    """Answered from the result cache, or by the host's native kernel
    (which the CPU has and the chip has not)."""
    cell = bench_copy.make_copy(tmp_path)
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        "throwaway-config.json")
    config = load_json(path)
    if value:
        config["session_options"][option] = value
    else:
        del config["session_options"][option]
    bench_copy.write_json(path, config)
    run = bench_copy.load_run_module(tmp_path)
    result, _captured = drive((tmp_path, cell, run), capsys)
    assert result["correct"] is False
    assert result["checks"][check][0] > 0
    assert result["checks"]["exact_mismatches"] == [0, 0]


@pytest.mark.parametrize("backends,correct", [
    (None, False),
    (["xla", "mesh"], True),
])
def test_a_plan_routed_to_the_mesh_counts_by_the_configurations_backends(
        tmp_path, capsys, backends, correct):
    """The whole plan through ``MeshExecutor`` on the test's eight
    virtual devices: ``session._try_mesh_execute`` records a route
    ``mesh``, which only a configuration that states it lets pass."""
    cell = bench_copy.make_copy(tmp_path)
    path = os.path.join(str(tmp_path), "benchmark", "configs",
                        "throwaway-config.json")
    config = load_json(path)
    config["session_options"] = {
        "spark.sail.cache.result.enabled": "false",
        "spark.sail.execution.mesh": "force"}
    if backends:
        config["backends"] = backends
    bench_copy.write_json(path, config)
    run = bench_copy.load_run_module(tmp_path)
    kept = {}

    class KeepRun(run.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            kept["run"] = self

    run.Run = KeepRun
    result, _captured = drive((tmp_path, cell, run), capsys)
    routed = [r["backend"] for st in kept["run"].done
              for r in st.profile.backend_routes]
    assert routed.count("mesh") == len(kept["run"].done) > 0
    assert set(routed) == {"mesh", "xla"}
    assert result["correct"] is correct
    assert result["checks"]["not_xla_routes"] == \
        [0 if correct else routed.count("mesh"), 0]
    assert result["checks"]["exact_mismatches"] == [0, 0]
    assert result["checks"]["worst_rel_err"][0] < 1e-12


def test_a_statement_that_fails_in_set_up_ends_the_run(
        copy, capsys, monkeypatch):
    from sail_tpu.spark_connect.client import SparkConnectClient

    def sql(self, query):
        raise RuntimeError("no such table")

    monkeypatch.setattr(SparkConnectClient, "sql", sql)
    dest, cell, run = copy
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"], require_platform="cpu", root=str(dest))
    assert "first call failed" in str(exc.value.code)
    assert '"correct"' not in capsys.readouterr().out


# -- no chip --------------------------------------------------------------------

def test_without_a_tpu_the_run_ends_before_any_data(copy, capsys):
    dest, cell, run = copy
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"], root=str(dest))
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"correct"' not in out and '"step": "data"' not in out


def test_an_unknown_workload_is_refused(copy, capsys):
    dest, _cell, run = copy
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "no-such-cell", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], require_platform="cpu",
                 root=str(dest))
    assert exc.value.code not in (0, None)


def test_a_device_kind_without_peaks_is_refused(copy, monkeypatch):
    """On a TPU of a kind ``peaks.json`` has no row for, the run ends:
    a roofline against a guessed peak is worse than none."""
    _dest, cell, run = copy
    import jax

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    with pytest.raises(SystemExit) as exc:
        run.device_step(run.Cell(cell, str(copy[0])), "tpu")
    assert "no peaks" in str(exc.value.code)


def test_more_chips_asked_than_there_are_is_refused(copy, monkeypatch):
    _dest, cell, run = copy
    c = run.Cell(cell, str(copy[0]))
    c.chips = 64
    with pytest.raises(SystemExit) as exc:
        run.device_step(c, "cpu")
    assert "needs 64 chip" in str(exc.value.code)


def test_percentile_is_nearest_rank(copy):
    _dest, _cell, run = copy
    values = list(range(1, 101))
    assert run.percentile(values, 95) == 95
    assert run.percentile(values, 50) == 50
    assert run.percentile([5.0], 95) == 5.0
    assert run.percentile([1.0, 2.0], 95) == 2.0
