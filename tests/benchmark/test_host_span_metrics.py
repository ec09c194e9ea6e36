"""The two per-layer metrics that read the executor's eager host work,
``join_expand_ms`` (``join.expand`` spans) and ``arrow_convert_ms``
(``arrow.convert`` spans): each reader on hand-built span trees, and both
in the result line of a traced run of the harness on the CPU."""

import contextlib
import importlib
import importlib.util
import io
import math
import os
import sys
import types

import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line, write_json

sys.path.insert(0, ROOT)

from sail_tpu import profiler  # noqa: E402
from sail_tpu import tracing as tr  # noqa: E402

#: metric -> the span it sums under execute
SPANS = {"join_expand_ms": "join.expand", "arrow_convert_ms": "arrow.convert"}


def _reader(metric):
    spec = load_json(os.path.join(ROOT, "benchmark", "metrics",
                                  metric + ".json"))
    rel, _, function = spec["reader"].partition(":")
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    module_spec = importlib.util.spec_from_file_location(
        "reader_" + metric, os.path.join(ROOT, "benchmark", rel))
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return getattr(module, function)


def _profile(*spans):
    """A profile of (name, id, parent id, start ms, end ms) spans."""
    p = profiler.QueryProfile(query_id="q")
    for name, sid, parent, start, end in spans:
        p.add_span(tr.Span(trace_id="t" * 32, span_id=sid, parent_id=parent,
                           name=name, start_ns=start * 10**6,
                           end_ns=end * 10**6, thread_id=1))
    return p


def _run(*profiles):
    return types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles])


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_reads_zero_on_a_tree_without_its_span(metric):
    read = _reader(metric)
    p = _profile(("query", "a", None, 0, 100),
                 ("execute", "b", "a", 10, 90),
                 ("op.JoinExec", "c", "b", 20, 80),
                 ("dispatch", "d", "c", 30, 40),
                 ("upload", "e", "c", 40, 50))
    assert read(_run(p, p)) == 0
    # a program that keeps no span tree: nothing to read
    assert read(_run(types.SimpleNamespace())) is None


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_sums_its_spans_under_execute(metric):
    read = _reader(metric)
    name = SPANS[metric]
    p = _profile(("query", "a", None, 0, 100),
                 ("execute", "b", "a", 10, 90),
                 ("op.JoinExec", "c", "b", 10, 40),
                 (name, "d", "c", 20, 30),
                 ("op.ScanExec", "e", "b", 40, 80),
                 (name, "f", "e", 50, 55),
                 ("fetch", "g", "a", 90, 99),
                 (name, "h", "g", 91, 98))      # not under execute
    assert read(_run(p)) == 15
    bare = _profile(("query", "a", None, 0, 10), ("execute", "b", "a", 1, 9))
    assert read(_run(p, p, bare)) == 15         # the median statement's


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a throw-away cell of Q3 (expanding joins) and a
    streamed Q1 (chunked scan), both new metrics listing the cell."""
    dest = tmp_path_factory.mktemp("bench_host_spans")
    cell = bench_copy.make_copy(dest, cycle=("tpch-q3", "tpch-q1"))
    config_path = os.path.join(dest, "benchmark", "configs",
                               "throwaway-config.json")
    config = load_json(config_path)
    config["session_options"]["spark.sail.scan.chunkRows"] = "20000"
    write_json(config_path, config)
    bench_path = os.path.join(dest, "BENCHMARK.json")
    bench = load_json(bench_path)
    for m in bench["per_layer"]:
        if m["name"] in SPANS:
            m["workloads"].append(cell)
    write_json(bench_path, bench)

    run = bench_copy.load_run_module(dest)
    tracered = importlib.import_module("tracered")
    kept = {}
    real_devices, real_run = tracered.device_planes, run.Run

    class KeepRun(real_run):
        def __init__(self, **kw):
            super().__init__(**kw)
            kept["run"] = self

    # the CPU's trace has no device plane: its host plane stands in
    tracered.device_planes = lambda planes: ["/host:CPU"]
    run.Run = KeepRun
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", cell, "--seed", str(2**31 + 38),
                           "--seconds", "2", "--trace", "1"],
                          require_platform="cpu", root=str(dest))
    finally:
        tracered.device_planes = real_devices
        run.Run = real_run
    assert rc == 0
    return result_line(out.getvalue()), kept["run"]


def test_both_metrics_are_in_the_traced_line_and_finite(traced):
    result, _run = traced
    assert result["correct"] is True
    bench = {m["name"]: m for m in
             load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for metric in SPANS:
        assert metric in result["metrics"], metric
        value = result["metrics"][metric]["value"]
        assert math.isfinite(value) and value >= 0, metric
        assert result["metrics"][metric]["unit"] == bench[metric]["unit"]


def test_each_span_reads_where_its_statement_runs(traced):
    _result, run = traced
    by_query = {}
    for st in run.done:
        for metric, name in SPANS.items():
            by_query.setdefault((st.query, metric), []).append(
                st.profile.span_ms(name, under="execute"))
    # Q3 expands its joins; the streamed Q1 converts every chunk
    assert min(by_query[("tpch-q3", "join_expand_ms")]) > 0
    assert min(by_query[("tpch-q1", "arrow_convert_ms")]) > 0
    assert max(by_query[("tpch-q1", "join_expand_ms")]) == 0
