"""The ten per-layer metrics that read the statement's span tree
(PR 26), driven through ``benchmark/run.py`` on the CPU the way
``test_harness.py`` drives its traced run: all present and finite in
the result line, consistent with the phase metrics they split, and the
``sail:`` annotations on the xplane's host plane on the same clock as
the harness's own ``bench:call:`` annotations."""

import contextlib
import importlib
import io
import math
import os
import sys

import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line

sys.path.insert(0, ROOT)

SPAN_METRICS = ("resolve_read_ms", "planner_self_ms", "host_syncs_per_query",
                "sync_wait_ms", "executor_self_ms", "scan_wait_ms",
                "upload_host_ms", "rpc_server_ms", "unattributed_ms",
                "host_rss_growth_mb")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a throw-away Q1+Q6 cell; the result line, the
    loaded xplane and the window's statements."""
    dest = tmp_path_factory.mktemp("bench_spans")
    cell = bench_copy.make_copy(dest)
    run = bench_copy.load_run_module(dest)
    # the copy's own module: load_run_module put its directory first
    tracered = importlib.import_module("tracered")
    kept = {}
    real_devices, real_load = tracered.device_planes, tracered.load_xplane
    real_run = run.Run

    def load(path):
        kept["planes"] = real_load(path)
        return kept["planes"]

    class KeepRun(real_run):
        def __init__(self, **kw):
            super().__init__(**kw)
            kept["run"] = self

    # the CPU's trace has no device plane: its host plane stands in
    tracered.device_planes = lambda planes: ["/host:CPU"]
    tracered.load_xplane = load
    run.Run = KeepRun
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", cell, "--seed", str(2**31 + 5),
                           "--seconds", "1.5", "--trace", "1"],
                          require_platform="cpu", root=str(dest))
    finally:
        tracered.device_planes = real_devices
        tracered.load_xplane = real_load
        run.Run = real_run
    assert rc == 0
    return result_line(out.getvalue()), kept["planes"], kept["run"], tracered


def test_all_ten_metrics_are_in_the_traced_line_and_finite(traced):
    result, _planes, _run, _tracered = traced
    assert result["correct"] is True
    metrics = result["metrics"]
    bench = {m["name"]: m for m in
             load_json(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for name in SPAN_METRICS:
        assert name in metrics, name
        assert math.isfinite(metrics[name]["value"]), name
        assert metrics[name]["unit"] == bench[name]["unit"]
        assert "workloads" not in bench[name]
    # the resident scan waits on no prefetch queue: 0, and not left out
    assert metrics["scan_wait_ms"]["value"] == 0
    assert metrics["resolve_read_ms"]["value"] > 0
    assert metrics["unattributed_ms"]["value"] >= 0
    assert metrics["rpc_server_ms"]["value"] > 0


def test_span_metrics_split_the_phase_metrics(traced):
    result, _planes, _run, _tracered = traced
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert value["resolve_read_ms"] <= value["plan_ms"]
    inside = (value["sync_wait_ms"] + value["scan_wait_ms"]
              + value["upload_host_ms"] + value["executor_self_ms"])
    assert inside <= value["execute_ms"] + 1.0
    assert value["executor_self_ms"] > 0


def test_every_statement_of_the_window_left_one_rooted_tree(traced):
    _result, _planes, run, _tracered = traced
    assert run.done
    for st in run.done:
        spans = st.profile.spans
        ids = {s.span_id for s in spans}
        roots = [s for s in spans if s.parent_id not in ids]
        assert [s.name for s in roots] == ["spark_connect:execute_plan"]
        assert [s.name for s in spans if s.parent_id == roots[0].span_id
                and s.name == "query"] == ["query"]
        assert len({s.trace_id for s in spans}) == 1
        assert st.profile.spans_dropped == 0


def test_sail_annotations_share_the_clock_of_the_calls(traced):
    _result, planes, run, tracered = traced
    sail = tracered.host_spans(planes, "sail:")
    calls = tracered.host_spans(planes, tracered.CALL_SPAN)
    assert calls
    names = {n for n, _s, _e in sail}
    for name in ("sail:query", "sail:resolve", "sail:execute",
                 "sail:resolve.read_source", "sail:dispatch"):
        assert name in names, name
    # a query began inside its client call (the calls cut by the trace's
    # two ends have no annotation of their own: leave their queries out)
    queries = [(s, e) for n, s, e in sail if n == "sail:query"]
    first, last = calls[0][1], max(ce for _n, _cs, ce in calls)
    whole = [(s, e) for s, e in queries if first <= s < last]
    assert whole
    for s, e in whole:
        assert any(cs <= s <= ce for _n, cs, ce in calls), (s, e)
    # and the profile's own clock (time.time_ns) agrees with the
    # trace's through the window annotation, as run.py maps them
    window = tracered.host_spans(planes, tracered.WINDOW_SPAN)[0]
    wall0 = run.trace["wall"][0]
    inside = [st for st in run.done if st.wall0 >= wall0
              and st.wall1 <= run.trace["wall"][1]]
    assert inside
    for st in inside:
        root = [s for s in st.profile.spans if s.name == "query"][0]
        on_trace = window[1] + (root.start_ns / 1e9 - wall0) * 1e9
        assert any(abs(on_trace - s) < 5e6 for s, _e in queries)
