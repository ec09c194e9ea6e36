"""The two per-statement device readings (``device_ms_per_query``,
``scan_hbm_roofline``) count the statements that RAN in the traced
window by their share of it, and the backends a cell's stages may take
come from its configuration: on hand-made runs, no JAX.

The cases are the ones that broke: PR 32's cell, whose parent side
takes 86 s a statement and so ended none inside any traced window
(refused ``output_malformed``: "metrics lacks device_ms_per_query"),
and ``tpch-sf10-join``, where 28.98 busy seconds divided by the 10
statements that ended in 30 s read 2,898 ms of device time in a
2,797 ms statement (ledger, PR 31)."""

import os
import sys
import types

import pytest

from bench_copy import ROOT, load_json

sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import run as bench_run  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402
from span_metrics import share, shares_in_window  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
SF1 = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))
SF10 = load_json(os.path.join(BENCH, "configs", "tpch-sf10-resident.json"))
V5E = load_json(os.path.join(BENCH, "peaks.json"))["devices"]["TPU v5 lite"]
QUERIES = {q: load_json(os.path.join(BENCH, "queries", q + ".json"))
           for q in ("tpch-q1", "tpch-q6", "tpch-q3", "tpch-q5")}


def st(wall0, wall1, query="tpch-q3", stream=0, error=None):
    return types.SimpleNamespace(wall0=wall0, wall1=wall1, query=query,
                                 stream=stream, error=error)


def run_of(statements, wall, busy_s, config=SF1):
    return bench_run.Run(
        config=config, queries=QUERIES, statements=statements,
        done=[s for s in statements if s.error is None], peaks=V5E,
        trace={"wall": list(wall), "busy_s": busy_s,
               "window_s": wall[1] - wall[0]})


def reading(name, run):
    return bench_run.load_reader(BENCH, f"readers/{name}.py:read")(run)


@pytest.mark.parametrize("wall0,wall1,expected", [
    (90.0, 98.0, 0.0),          # before the window
    (96.0, 104.0, 0.5),         # across its start
    (101.0, 103.0, 1.0),        # inside
    (102.0, 110.0, 0.25),       # across its end
    (57.0, 143.0, 4.0 / 86.0),  # around the whole window: Q5 at 86 s
    (104.0, 112.0, 0.0),        # begins as the window ends
    (102.0, 102.0, 1.0),        # no time at all, inside
])
def test_share_of_a_statement_in_the_window(wall0, wall1, expected):
    assert share(st(wall0, wall1), 100.0, 104.0) == pytest.approx(expected)


def test_a_window_inside_one_statement_gives_both_metrics():
    """PR 32's parent side: Q5 at 86 s, traced 4 s from 1 s in."""
    run = run_of([st(0.0, 86.0, "tpch-q5")], (1.0, 5.0), busy_s=3.6)
    assert shares_in_window(run, run.statements) == \
        [(run.statements[0], pytest.approx(4.0 / 86.0))]
    # busy share x the statement's time
    assert reading("device_ms_per_query", run) == \
        pytest.approx(0.9 * 86_000.0)
    least_s = 224_560_560 * (4.0 / 86.0) / 819e9
    assert reading("scan_hbm_roofline", run) == \
        pytest.approx(100.0 * least_s / 3.6)


def test_device_time_cannot_pass_the_statement_of_one_closed_loop_stream():
    """``tpch-sf10-join``'s numbers: 2.7966 s statements back to back,
    28.98 busy seconds of a 30 s trace that ten of them ended in."""
    length = 2.7966
    statements = [st(i * length - 2.0, (i + 1) * length - 2.0)
                  for i in range(15)]
    run = run_of(statements, (1.0, 31.0), busy_s=28.98, config=SF10)
    ended = [s for s in statements if 1.0 <= s.wall1 <= 31.0]
    assert len(ended) == 10 and 28.98 * 1000 / len(ended) > length * 1000
    ran = sum(s for _st, s in shares_in_window(run, statements))
    assert ran == pytest.approx(30.0 / length)
    value = reading("device_ms_per_query", run)
    assert value == pytest.approx(28.98 / 30.0 * length * 1000.0)
    assert value < length * 1000.0
    needed = needed_bytes(QUERIES["tpch-q3"], SF10)
    assert needed == 2_058_000_000
    assert reading("scan_hbm_roofline", run) == pytest.approx(
        100.0 * needed * ran / 819e9 / 28.98)
    assert reading("scan_hbm_roofline", run) < 100.0


def test_two_streams_sum_their_shares():
    a = [st(0.0, 2.0, "tpch-q1", 0), st(2.0, 4.0, "tpch-q6", 0),
         st(4.0, 8.0, "tpch-q1", 0)]
    b = [st(0.0, 3.0, "tpch-q6", 1), st(3.0, 5.0, "tpch-q1", 1),
         st(5.0, 7.0, "tpch-q6", 1)]
    run = run_of(a + b, (1.0, 5.0), busy_s=2.0)
    shares = dict((id(s), v) for s, v in shares_in_window(run, a + b))
    assert [shares.get(id(s), 0.0) for s in a] == \
        [pytest.approx(0.5), pytest.approx(1.0), pytest.approx(0.25)]
    assert [shares.get(id(s), 0.0) for s in b] == \
        [pytest.approx(2.0 / 3.0), pytest.approx(1.0), 0.0]
    ran = 0.5 + 1.0 + 0.25 + 2.0 / 3.0 + 1.0
    assert reading("device_ms_per_query", run) == \
        pytest.approx(2000.0 / ran)
    q1, q6 = 264_000_000, 168_000_000
    needed = q1 * (0.5 + 0.25 + 1.0) + q6 * (1.0 + 2.0 / 3.0)
    assert reading("scan_hbm_roofline", run) == \
        pytest.approx(100.0 * needed / 819e9 / 2.0)


def test_a_failed_statement_used_the_device_and_scanned_nothing_to_the_end():
    good, bad = st(1.0, 3.0, "tpch-q6"), st(3.0, 5.0, "tpch-q6", error="x")
    run = run_of([good, bad], (1.0, 5.0), busy_s=1.0)
    assert reading("device_ms_per_query", run) == pytest.approx(500.0)
    assert reading("scan_hbm_roofline", run) == \
        pytest.approx(100.0 * 168_000_000 / 819e9 / 1.0)


@pytest.mark.parametrize("name,statements,busy_s,untraced", [
    ("device_ms_per_query", [st(10.0, 12.0)], 1.0, False),   # none overlaps
    ("scan_hbm_roofline", [st(10.0, 12.0)], 1.0, False),
    ("scan_hbm_roofline", [st(1.0, 3.0, error="x")], 1.0, False),
    ("scan_hbm_roofline", [st(1.0, 3.0)], 0.0, False),   # device never busy
    ("device_ms_per_query", [st(1.0, 3.0)], 1.0, True),      # --trace 0
    ("scan_hbm_roofline", [st(1.0, 3.0)], 1.0, True),
])
def test_nothing_to_read_is_nothing_returned(name, statements, busy_s,
                                             untraced):
    run = run_of(statements, (1.0, 5.0), busy_s)
    if untraced:
        run.trace = None
    assert reading(name, run) is None


def test_a_device_kind_without_peaks_has_no_roofline():
    run = run_of([st(1.0, 3.0)], (1.0, 5.0), 1.0)
    run.peaks = None
    assert reading("scan_hbm_roofline", run) is None
    assert reading("device_ms_per_query", run) == pytest.approx(1000.0)


# -- the backends a configuration allows --------------------------------------

def answered(*backends, profile=True):
    routes = [{"stage": i, "backend": b} for i, b in enumerate(backends)]
    return types.SimpleNamespace(
        profile=types.SimpleNamespace(backend_routes=routes)
        if profile else None)


@pytest.mark.parametrize("statements,backends,counted", [
    ([answered("xla", "xla"), answered("xla")], ["xla"], 0),
    ([answered("mesh"), answered("xla")], ["xla"], 1),
    ([answered("mesh"), answered("xla")], ["xla", "mesh"], 0),
    ([answered("mesh", "mesh")], ["xla", "mesh"], 0),
    ([answered("native"), answered("xla")], ["xla"], 1),
    ([answered("native"), answered("mesh")], ["xla", "mesh"], 1),
    # a profile without routes, a statement without a profile: unknown
    # where they ran, whatever the configuration allows
    ([answered(), answered("mesh")], ["xla", "mesh"], 1),
    ([answered(profile=False), answered("xla")], ["xla", "mesh"], 1),
    ([answered(None)], ["xla", "mesh"], 1),   # a route that names none
])
def test_routes_are_held_to_the_configurations_backends(statements, backends,
                                                        counted):
    assert bench_run.routes_off_the_backends(statements, backends) == counted


def test_the_three_configurations_keep_the_default():
    for name in ("tpch-sf1-resident", "tpch-sf10-lineitem-stream",
                 "tpch-sf10-resident"):
        doc = load_json(os.path.join(BENCH, "configs", name + ".json"))
        assert doc.get("backends", ["xla"]) == ["xla"]
        assert doc["limits"]["not_xla_routes"] == 0
