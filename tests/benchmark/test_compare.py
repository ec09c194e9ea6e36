"""The comparison that decides ``correct`` and the control it has to
fail: the reference computed in float32, put in the program's place,
at a size a test run can hold (the control's readings at the cells' own
sizes are in PERF.md)."""

import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from bench_copy import ROOT, load_json, statements_of

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import compare  # noqa: E402
import datagen  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIGS = ("tpch-sf1-resident", "tpch-sf10-lineitem-stream")


def queries(*names):
    return {n: load_json(os.path.join(BENCH, "queries", n + ".json"))
            for n in names}


def frames_for(qs, seed, sf, tmp):
    wanted = {}
    for q in qs.values():
        for table, cols in q["reads"].items():
            have = wanted.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    _p, frames, _r, _b = datagen.write_tables(wanted, seed, sf, str(tmp))
    return frames


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    qs = queries("tpch-q1", "tpch-q6", "tpch-q3", "tpch-q5")
    return qs, frames_for(qs, 21, 0.05, tmp_path_factory.mktemp("cmp"))


@pytest.mark.parametrize("config", CONFIGS)
def test_limits_cover_every_number_compared(config):
    limits = load_json(os.path.join(BENCH, "configs",
                                    config + ".json"))["limits"]
    assert set(limits) == {"failed_statements", "row_count_mismatches",
                           "exact_mismatches", "worst_rel_err",
                           "not_xla_routes", "result_cache_hits"}
    assert all(limits[k] == 0 for k in limits if k != "worst_rel_err")
    assert 0 < limits["worst_rel_err"] < 1e-8


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_the_float32_control_is_not_correct(small, config, seed, tmp_path):
    """The control at test size: every seed's worst relative error is
    at least three times the limit, so ``correct`` comes out false."""
    doc = load_json(os.path.join(BENCH, "configs", config + ".json"))
    qs = queries(*statements_of(doc))
    frames = frames_for(qs, seed, 0.05, tmp_path)
    numbers = compare.control_reading(qs, frames)
    correct, checks = compare.verdict(
        numbers, {k: doc["limits"][k] for k in numbers})
    assert correct is False
    assert numbers["worst_rel_err"] >= 3 * doc["limits"]["worst_rel_err"]
    assert checks["worst_rel_err"] == [numbers["worst_rel_err"],
                                       doc["limits"]["worst_rel_err"]]
    # it fails by precision, not by a wrong row
    assert numbers["row_count_mismatches"] == 0


def test_the_reference_held_to_itself_is_exact(small):
    qs, frames = small
    for name, q in qs.items():
        ref = compare.reference_answer(q, frames)
        assert compare.compare_frames(ref, ref.copy()) == {
            "row_count_mismatches": 0, "exact_mismatches": 0,
            "worst_rel_err": 0.0}


def test_control_frames_change_floats_only(small):
    _qs, frames = small
    low = compare.lower_precision_frames(frames)
    li, low_li = frames["lineitem"], low["lineitem"]
    assert low_li["l_extendedprice"].dtype == np.float32
    assert low_li["l_orderkey"].dtype == li["l_orderkey"].dtype
    assert low_li["l_shipdate"].dtype == li["l_shipdate"].dtype
    assert li["l_extendedprice"].dtype == np.float64   # a copy was changed


def frame(**cols):
    return compare.normalize(pd.DataFrame(cols))


@pytest.mark.parametrize("got,exp,expected", [
    (frame(a=[1, 2], b=[1.0, 2.0]), frame(a=[1, 2], b=[1.0, 2.0]),
     (0, 0, 0.0)),
    (frame(a=[1, 3], b=[1.0, 2.0]), frame(a=[1, 2], b=[1.0, 2.0]),
     (0, 1, 0.0)),
    (frame(a=[1], b=[1.0]), frame(a=[1, 2], b=[1.0, 2.0]), (1, 0, 0.0)),
    (frame(a=[1, 2]), frame(a=[1, 2], b=[1.0, 2.0]), (1, 0, 0.0)),
    (frame(a=[1, 2], b=[1.0, 2.2]), frame(a=[1, 2], b=[1.0, 2.0]),
     (0, 0, 0.1)),
    # below 1 the error is absolute
    (frame(a=[1], b=[0.06]), frame(a=[1], b=[0.05]), (0, 0, 0.01)),
    (frame(a=["x", "y"]), frame(a=["x", "z"]), (0, 1, 0.0)),
    (frame(a=[1.0, 2.0]), frame(a=[1, 2]), (0, 2, 0.0)),
    (frame(a=[float("nan")]), frame(a=[float("nan")]), (0, 0, 0.0)),
    (frame(a=[float("nan")]), frame(a=[1.0]), (0, 0, float("inf"))),
    (frame(d=pd.to_datetime(["1995-03-15"])),
     frame(d=pd.to_datetime(["1995-03-16"])), (0, 1, 0.0)),
])
def test_compare_frames(got, exp, expected):
    out = compare.compare_frames(got, exp)
    assert (out["row_count_mismatches"], out["exact_mismatches"]) == \
        expected[:2]
    assert out["worst_rel_err"] == pytest.approx(expected[2])


def test_unordered_answers_compare_as_row_sets():
    a = frame(k=[2, 1], v=[20.0, 10.0])
    b = frame(k=[1, 2], v=[10.0, 20.0])
    assert compare.compare_frames(a, b)["exact_mismatches"] == 2
    assert compare.compare_frames(a, b, ordered=False) == {
        "row_count_mismatches": 0, "exact_mismatches": 0,
        "worst_rel_err": 0.0}


def test_answer_frame_reads_decimals_dates_and_dictionaries():
    import datetime
    import decimal
    table = pa.table({
        "m": pa.array([decimal.Decimal("12.34")], pa.decimal128(15, 2)),
        "d": pa.array([datetime.date(1995, 3, 15)], pa.date32()),
        "s": pa.array(["x"]).dictionary_encode(),
        "n": pa.array([7], pa.int32())})
    df = compare.answer_frame(table)
    assert [df[c].dtype.kind for c in df.columns] == ["f", "M", "O", "i"]
    assert df["c0"][0] == pytest.approx(12.34)
    assert df["c3"].dtype == np.int64


def test_compare_answers_counts_every_answer_and_keeps_the_worst(small):
    qs, frames = small
    q6 = {"tpch-q6": qs["tpch-q6"]}
    ref = compare.reference_answer(qs["tpch-q6"], frames)
    good = pa.table({"revenue": pa.array(ref["c0"].to_numpy())})
    bad = pa.table({"revenue": pa.array(ref["c0"].to_numpy() * (1 + 1e-6))})
    empty = pa.table({"revenue": pa.array([], pa.float64())})
    out = compare.compare_answers(
        [("tpch-q6", good), ("tpch-q6", bad), ("tpch-q6", good),
         ("tpch-q6", empty), ("tpch-q6", empty)], q6, frames)
    assert out["row_count_mismatches"] == 2
    assert out["worst_rel_err"] == pytest.approx(1e-6, rel=1e-3)


def test_verdict_wants_a_limit_for_every_number():
    assert compare.verdict({"a": 0, "b": 1e-12}, {"a": 0, "b": 1e-10}) == \
        (True, {"a": [0, 0], "b": [1e-12, 1e-10]})
    assert compare.verdict({"a": 1}, {"a": 0})[0] is False
    assert compare.verdict({"a": float("nan")}, {"a": 1.0})[0] is False
    with pytest.raises(KeyError):
        compare.verdict({"a": 0}, {})


def test_the_reference_is_the_copy_of_the_repositorys_oracle():
    """The original (tests/tpch_oracle.py) stays until a later PR
    deletes it; until then the two must not drift."""
    with open(os.path.join(ROOT, "tests", "tpch_oracle.py")) as f:
        original = f.read()
    with open(os.path.join(BENCH, "reference", "tpch_oracle.py")) as f:
        assert f.read() == original
