"""The configuration ``tpch-sf1-largevolume`` and its cell ``tpch-sf1-agg18``:
TPC-H Q18 over customer, orders and lineitem. The configuration is
``tpch-sf1-resident``'s deployment with the three tables Q18 reads; the
reference makes ``c_name`` from the key as the generator writes it; the
cell runs end to end on the CPU at a small scale; the two per-layer
metrics read ``rtf_list_keys`` on ``op.JoinExec`` and ``path`` on the
aggregate spans, and read 0 on a program that has neither."""

import importlib
import os
import sys
import types

import numpy as np
import pyarrow as pa
import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import compare  # noqa: E402
import datagen  # noqa: E402
import run as bench_run  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIG = load_json(os.path.join(BENCH, "configs",
                                "tpch-sf1-largevolume.json"))
SF1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))
Q18 = load_json(os.path.join(BENCH, "queries", "tpch-q18.json"))
THREE = ["customer", "orders", "lineitem"]
NEW_METRICS = ("rtf_list_keys_per_query", "agg_sorted_ms")
CELL = "tpch-sf1-agg18"


@pytest.mark.parametrize("key", ["guarantees", "session_options",
                                 "limits", "logical_widths_bytes", "trace",
                                 "scale_factor"])
def test_everything_but_the_tables_is_the_sf1_deployments(key):
    assert CONFIG[key] == SF1[key]


def test_the_server_gives_every_small_batch_one_capacity():
    """The one setting the deployment adds: a capacity floor over the
    n qualifying orders and their 7n lines, so every seed's data runs
    the same programs."""
    env = CONFIG["process_environment"]
    assert env == {**SF1["process_environment"],
                   "SAIL_EXECUTION__BATCH_CAPACITY_MIN": "1024"}
    assert any("BATCH_CAPACITY_MIN" in a for a in CONFIG["assumed"])


def test_the_three_tables_of_q18_at_the_specs_widths():
    assert CONFIG["tables"] == THREE and set(Q18["reads"]) == set(THREE)
    assert CONFIG["reduced"] == ["tables"]
    for table in THREE:
        assert CONFIG["rows"][table] == SF1["rows"][table]
        assert CONFIG["schema"][table] == SF1["schema"][table]
    assert set(CONFIG["rows"]) == set(CONFIG["schema"]) == set(THREE)
    # customer 150,000 x 8 + orders 1.5M x (8 + 8 + 4 + 8) + lineitem
    # 6M x (8 + 8): the columns the reference reads
    assert needed_bytes(Q18, CONFIG) == 139_200_000
    assert set(SF1["assumed"]) < set(CONFIG["assumed"])
    assert SF1["deployment"] in CONFIG["deployment"]


def test_the_query_is_the_specs_text_with_quantity_300():
    with open(os.path.join(BENCH, "queries", Q18["sql_file"])) as f:
        sql = " ".join(f.read().split())
    assert "sum(l_quantity) > 300" in sql
    assert sql.endswith("order by o_totalprice desc, o_orderdate limit 100")
    assert Q18["ordered"] is True
    assert Q18["reference"] == "tpch_spec_names:q18"


def test_the_cells_files_resolve():
    cell = bench_run.Cell(CELL)
    assert cell.entry["config"] == "tpch-sf1-largevolume"
    assert cell.entry["traffic"] == "agg-q18-1stream" and cell.chips == 1
    assert cell.traffic["streams"] == 1 and cell.traffic["loop"] == "closed"
    assert cell.traffic["warm_cycles"] == 1
    assert list(cell.queries) == ["tpch-q18"]
    assert cell.wanted_tables() == Q18["reads"]
    assert [m["name"] for m in cell.end_to_end()] == [
        "query_ms_p50", "queries_per_hour", "setup_s"]
    layer = {m["name"]: m for m in cell.per_layer()}
    assert {"scan_hbm_roofline", "device_ms_per_query",
            "host_syncs_per_query", "peak_hbm_gb"} <= set(layer)
    assert layer["rtf_list_keys_per_query"]["layer"] == "Local executor"
    assert layer["agg_sorted_ms"]["layer"] == "Kernels"
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "query_ms_p50"
    # no other cell's line gains a metric
    for other in ("tpch-sf1-join", "tpch-sf1-join5", "tpch-sf1-scanagg"):
        names = {m["name"] for m in bench_run.Cell(other).per_layer()}
        assert not names & set(NEW_METRICS)


def test_the_reference_names_customers_as_the_generator_writes_them(
        tmp_path):
    """The reference makes ``c_name`` from ``c_custkey``; the Parquet
    the server reads holds the generator's own names."""
    import pyarrow.parquet as pq
    paths, frames, _rows, _bytes = datagen.write_tables(
        {"customer": ["c_custkey"]}, 2**31 + 420, 0.002, str(tmp_path),
        workers=1)
    written = pa.concat_tables(
        [pq.read_table(os.path.join(paths["customer"], f),
                       columns=["c_custkey", "c_name"])
         for f in sorted(os.listdir(paths["customer"]))])
    q18 = compare.reference_function("tpch_spec_names:q18")
    named = q18.__globals__["_with_customer_names"](frames)["customer"]
    assert list(named.c_custkey) == written.column("c_custkey").to_pylist()
    assert list(named.c_name) == written.column("c_name").to_pylist()
    assert named.c_name.iloc[0] == "Customer#000000001"


@pytest.fixture(scope="module")
def sf005(tmp_path_factory):
    """Q18's tables at SF0.05 (75,000 orders): a few orders over 300."""
    paths, frames, _rows, _bytes = datagen.write_tables(
        Q18["reads"], 2**31 + 421, 0.05,
        str(tmp_path_factory.mktemp("q18_sf005")), workers=2)
    return paths, frames


def test_the_float32_control_fails_the_configurations_limits(sf005):
    """The reference in float32 put in the program's place: the order
    totals lose their cents, so ``worst_rel_err`` is over the limit;
    the quantity sums are whole numbers and stay exact."""
    _paths, frames = sf005
    exp = compare.reference_answer(Q18, frames)
    assert 1 <= len(exp) <= 100
    numbers = compare.control_reading({"tpch-q18": Q18}, frames)
    correct, checks = compare.verdict(
        {**numbers, "failed_statements": 0, "not_xla_routes": 0,
         "result_cache_hits": 0}, CONFIG["limits"])
    assert correct is False
    assert checks["worst_rel_err"][0] > CONFIG["limits"]["worst_rel_err"]
    assert numbers["row_count_mismatches"] == 0
    low = compare.reference_answer(Q18, compare.lower_precision_frames(
        frames))
    assert np.array_equal(low.c5.to_numpy(), exp.c5.to_numpy())


def test_no_two_qualifying_orders_tie_on_the_sort_keys(sf005):
    """``ordered: true`` holds the answer's row order to the
    reference's: it is decided only where no two rows tie on
    (o_totalprice, o_orderdate)."""
    _paths, frames = sf005
    exp = compare.reference_answer(Q18, frames)
    assert not exp.duplicated(["c4", "c3"]).any()


# -- the cell, end to end on the CPU -------------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark whose ``tpch-sf1-largevolume`` runs at
    SF0.05 with the CPU tests' session options: the cell, its traffic
    file, its metrics and their readers are the checkout's own."""
    dest = tmp_path_factory.mktemp("bench_largevolume")
    bench_copy.make_copy(dest)
    sys.modules.pop("tpch_spec_names", None)
    path = os.path.join(str(dest), "benchmark", "configs",
                        "tpch-sf1-largevolume.json")
    config = load_json(path)
    config["scale_factor"] = 0.05
    config["rows"] = {t: int(rows * 0.05)
                      for t, rows in config["rows"].items()}
    config["session_options"] = dict(bench_copy.TEST_SESSION_OPTIONS)
    config["trace"] = {"after_seconds": 0.2, "seconds": 1.0}
    # run.py sets the environment in this process for good, and the
    # floor is read once per process: the tests after these must keep
    # the default
    config["process_environment"] = dict(SF1["process_environment"])
    bench_copy.write_json(path, config)
    return dest, bench_copy.load_run_module(dest)


def drive(copy, capsys, trace, seed):
    dest, run = copy
    capsys.readouterr()
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "1.0", "--trace", str(trace)],
                  require_platform="cpu", root=str(dest))
    captured = capsys.readouterr()
    assert rc == 0
    return result_line(captured.out)


def test_the_cell_runs_traced_with_both_new_metrics(copy, capsys,
                                                     monkeypatch):
    tracered = importlib.import_module("tracered")   # the copy's own
    monkeypatch.setattr(tracered, "device_planes",
                        lambda planes: ["/host:CPU"])
    result = drive(copy, capsys, trace=1, seed=2**31 + 422)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    checks = result["checks"]
    assert checks["worst_rel_err"][0] <= checks["worst_rel_err"][1] == 1e-10
    for name in ("exact_mismatches", "row_count_mismatches",
                 "failed_statements", "not_xla_routes",
                 "result_cache_hits"):
        assert checks[name] == [0, 0], name
    metrics = result["metrics"]
    # the HAVING filter's orders reach the orders and lineitem scans
    assert metrics["rtf_list_keys_per_query"]["unit"] == "keys"
    assert metrics["rtf_list_keys_per_query"]["value"] >= 2
    assert metrics["agg_sorted_ms"]["unit"] == "ms"
    assert 0 < metrics["agg_sorted_ms"]["value"] < \
        metrics["execute_ms"]["value"]
    for name in ("plan_ms", "host_syncs_per_query", "executor_self_ms",
                 "device_ms_per_query", "join_out_capacity_max"):
        assert name not in metrics or metrics[name]["value"] >= 0
    assert "join_out_capacity_max" not in metrics   # not this cell's


# -- the two readers on span trees with and without what they read -------------

def _reader(name):
    return bench_run.load_reader(BENCH, f"readers/{name}.py:read")


def _profile(*spans):
    """A profile of (name, id, parent id, start ms, end ms, attributes)."""
    from sail_tpu import profiler
    from sail_tpu import tracing as tr
    p = profiler.QueryProfile(query_id="q")
    for name, sid, parent, start, end, attrs in spans:
        p.add_span(tr.Span(trace_id="t" * 32, span_id=sid, parent_id=parent,
                           name=name, start_ns=start * 10**6,
                           end_ns=end * 10**6, thread_id=1,
                           attributes=dict(attrs)))
    return p


def _run_of(*profiles):
    return types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles])


def _q18_like(with_attributes=True):
    """Q18's shape: an outer sorted aggregate over a semi join whose
    build is the HAVING filter over a sorted aggregate, and an inner
    join; times in ms."""
    def a(**kw):
        return kw if with_attributes else {}
    return _profile(
        ("query", "q", None, 0, 200, {}),
        ("execute", "e", "q", 5, 195, {}),
        ("op.AggregateExec", "outer", "e", 10, 190,
         a(path="sorted", input_capacity=512, groups=68)),
        ("op.JoinExec", "semi", "outer", 12, 170,
         a(rtf_list_keys=68, rtf_listed=True)),
        ("op.FilterExec", "having", "semi", 14, 80, {}),
        ("op.AggregateExec", "inner", "having", 15, 75,
         a(path="sorted", input_capacity=6291456, groups=1500000)),
        ("op.ScanExec", "li", "inner", 16, 20, {}),
        ("sync", "s1", "inner", 60, 74, {"site": "agg.n_groups"}),
        ("op.JoinExec", "j2", "semi", 80, 160,
         a(rtf_list_keys=68, rtf_listed=True)),
        ("op.JoinExec", "j1", "j2", 82, 100,
         a(rtf_list_keys=0, rtf_listed=False)),
        ("op.AggregateExec", "q1like", "e", 191, 194,
         a(path="direct", input_capacity=8, groups=12)))


def test_the_list_reader_sums_the_joins_list_keys():
    read = _reader("rtf_list_keys_per_query")
    p = _q18_like()
    assert read(_run_of(p)) == 136
    bare = _profile(("query", "q", None, 0, 10, {}))
    assert read(_run_of(p, p, bare)) == 136      # the median statement's


def test_the_sorted_aggregate_reader_leaves_out_the_operators_beneath():
    read = _reader("agg_sorted_ms")
    # outer: 180 ms less the semi join's 158; inner: 60 less the scan's 4
    # (its sync stays in); the direct aggregate does not count
    assert read(_run_of(_q18_like())) == pytest.approx(22 + 56)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_0_on_a_program_without_the_attributes(name):
    value = _reader(name)(_run_of(_q18_like(with_attributes=False)))
    assert value == 0 and value is not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_nothing_where_no_profile_keeps_a_span_tree(name):
    before_spans = types.SimpleNamespace(phases={"optimize": 1.0})
    assert _reader(name)(_run_of(before_spans, None)) is None
