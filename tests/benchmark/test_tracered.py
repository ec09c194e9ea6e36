"""The reduction from a profiler trace to busy time, idle share and
labelled gaps: on hand-made intervals, and on a small trace recorded
on a v5e (``data/trace_v5e_scanagg.json``: the first 120 events of
every device line of a ``tpch-sf1-scanagg`` run with two streams, and
the benchmark's own host annotations)."""

import json
import os
import sys

import pytest

from bench_copy import ROOT

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import tracered  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_v5e_scanagg.json")) as f:
        return json.load(f)


def planes(ops, calls=(), window=(0.0, 100.0), modules=()):
    host = [["bench:window", window[0], window[1] - window[0]]]
    host += [[f"bench:call:{n}", s, e - s] for n, s, e in calls]
    return {"/device:TPU:0": {"XLA Ops": [list(o) for o in ops],
                              "XLA Modules": [list(m) for m in modules]},
            "/host:CPU": {"python3": host, "other": [["noise", 0.0, 5.0]]}}


@pytest.mark.parametrize("intervals,expected", [
    ([], []),
    ([("a", 0, 10)], [[0, 10]]),
    ([("a", 0, 10), ("b", 5, 20)], [[0, 20]]),
    ([("a", 0, 10), ("b", 10, 20)], [[0, 20]]),
    ([("b", 30, 40), ("a", 0, 10)], [[0, 10], [30, 40]]),
    ([("a", 0, 50), ("b", 10, 20)], [[0, 50]]),
])
def test_union(intervals, expected):
    assert tracered.union(intervals) == expected


@pytest.mark.parametrize("merged,expected", [
    ([], [(0, 100)]),
    ([[0, 100]], []),
    ([[10, 20]], [(0, 10), (20, 100)]),
    ([[0, 20], [50, 100]], [(20, 50)]),
])
def test_gaps(merged, expected):
    assert tracered.gaps(merged, 0, 100) == expected


def test_busy_union_clips_to_the_window_and_counts_overlap_once():
    ops = [("%a = f32[] add(x)", -10.0, 20.0),    # half outside
           ("%b = f32[] mul(x)", 5.0, 10.0),      # overlaps a
           ("%c = f32[] sort(x)", 50.0, 25.0),
           ("%d = f32[] add(x)", 95.0, 50.0)]     # runs past the end
    r = tracered.reduce_trace(planes(ops))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((15 + 25 + 5) * 1e-9)
    by_name = dict(r["device_ops"])
    assert by_name["%c sort"] == pytest.approx(25e-9)
    assert by_name["%a add"] == pytest.approx(10e-9)
    assert by_name["%d add"] == pytest.approx(5e-9)
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.55)


def test_gaps_are_labelled_by_the_calls_in_flight_and_their_phase():
    ops = [("%a = f32[] add(x)", 20.0, 10.0), ("%b = f32[] add(x)", 60.0, 10.0)]
    calls = [("s0:q1", 10.0, 45.0), ("s0:q6", 50.0, 90.0),
             ("s1:q1", 55.0, 95.0)]

    def phase_at(name, ns):
        return "execute" if ns > 40 else "resolve"

    r = tracered.reduce_trace(planes(ops, calls), phase_at)
    gaps = dict(r["idle_gaps"])
    # [0,20): mid 10 -> s0:q1 resolve; [30,60): mid 45 -> s0:q1 execute;
    # [70,100): mid 85 -> both later calls
    assert gaps["s0:q1/resolve"] == pytest.approx(20e-9)
    assert gaps["s0:q1/execute"] == pytest.approx(30e-9)
    assert gaps["s0:q6/execute+s1:q1/execute"] == pytest.approx(30e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    no_phase = dict(tracered.reduce_trace(planes(ops, calls))["idle_gaps"])
    assert "s0:q1" in no_phase and "s0:q6+s1:q1" in no_phase


def test_a_gap_with_no_call_says_so():
    r = tracered.reduce_trace(planes([("%a = f32[] add(x)", 0.0, 10.0)]))
    assert r["idle_gaps"] == [["no call in flight", pytest.approx(90e-9)]]


def test_ops_are_named_by_module_and_short_instruction():
    ops = [('%custom-call.1 = u32[5242880]{0:T(1024)S(1)} custom-call('
            's64[5242880]{0:T(1024)} %cols), custom_call_target="X64SplitLow"',
            10.0, 5.0),
           ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 40.0,
            5.0)]
    modules = [("jit_fn(1)", 0.0, 20.0), ("jit_fn(2)", 30.0, 30.0)]
    r = tracered.reduce_trace(planes(ops, modules=modules))
    assert [n for n, _s in r["device_ops"]] == [
        "jit_fn(1)/%custom-call.1 custom-call:X64SplitLow",
        "jit_fn(2)/%fusion.3 fusion"]


def test_at_most_ten_entries_each():
    ops = [(f"%op{i} = f32[] add(x)", i * 4.0, 1.0) for i in range(25)]
    calls = [(f"s0:q{i}", i * 4.0, i * 4.0 + 4.0) for i in range(25)]
    r = tracered.reduce_trace(planes(ops, calls))
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10


def test_a_plane_without_an_ops_line_falls_back_to_modules():
    p = planes([])
    del p["/device:TPU:0"]["XLA Ops"]
    p["/device:TPU:0"]["XLA Modules"] = [["jit_fn(1)", 10.0, 30.0]]
    r = tracered.reduce_trace(p)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["device_ops"] == [["jit_fn(1)", pytest.approx(30e-9)]]


def test_busy_time_is_averaged_over_the_device_planes():
    p = planes([("%a = f32[] add(x)", 0.0, 40.0)])
    p["/device:TPU:1"] = {"XLA Ops": [["%a = f32[] add(x)", 0.0, 20.0]]}
    assert tracered.reduce_trace(p)["busy_s"] == pytest.approx(30e-9)


@pytest.mark.parametrize("broken", ["window", "device"])
def test_a_trace_without_window_or_device_is_an_error(broken):
    p = planes([("%a = f32[] add(x)", 0.0, 40.0)])
    if broken == "window":
        p["/host:CPU"]["python3"] = []
    else:
        del p["/device:TPU:0"]
    with pytest.raises(ValueError):
        tracered.reduce_trace(p)


def test_the_recorded_trace_has_the_planes_the_reduction_names(recorded):
    assert tracered.device_planes(recorded) == ["/device:TPU:0"]
    assert tracered.OPS_LINE in recorded["/device:TPU:0"]
    assert "XLA Modules" in recorded["/device:TPU:0"]
    assert len(tracered.host_spans(recorded, tracered.WINDOW_SPAN)) == 1
    assert len(tracered.host_spans(recorded, tracered.CALL_SPAN)) >= 8


def test_the_recorded_trace_reduces(recorded):
    r = tracered.reduce_trace(recorded)
    assert r["window_s"] == pytest.approx(4.0, abs=0.01)
    # 120 ops of a few hundred microseconds at most
    assert 0.005 < r["busy_s"] < 0.05
    # the count of statements in the window is the readers' own
    # (span_metrics.shares_in_window), on the wall clock
    assert "calls_in_window" not in r
    assert all(name.startswith("jit_fn(") for name, _s in r["device_ops"])
    assert len(r["device_ops"]) == 10
    ranked = [s for _n, s in r["device_ops"]]
    assert ranked == sorted(ranked, reverse=True)
    assert sum(s for _n, s in r["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])
    labels = [n for n, _s in r["idle_gaps"]]
    assert any(label.startswith("s0:tpch-q") for label in labels)


def test_shrink_keeps_the_annotations_and_the_head_of_device_lines(recorded):
    small = tracered.shrink(recorded, keep_events=5)
    assert all(len(ev) <= 5 for ev in small["/device:TPU:0"].values())
    assert tracered.host_spans(small, tracered.WINDOW_SPAN) == \
        tracered.host_spans(recorded, tracered.WINDOW_SPAN)
