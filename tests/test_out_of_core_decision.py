"""The out-of-core decision of a join and a sort (exec/local.py
``out_of_core``): by working-set bytes against the device's free memory
by default, by an explicit row count where one is set; and TPC-H Q3 and
a sort answered in device memory and through the spill paths, both held
to the benchmark's pandas reference."""

import json
import os
import sys

import numpy as np
import pytest

import sail_tpu.exec.local as lm
from sail_tpu import SparkSession, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
import compare  # noqa: E402
import datagen  # noqa: E402

MI = 1 << 20
SORT_SQL = ("select o_orderkey, o_totalprice, o_orderdate from orders "
            "order by o_totalprice desc, o_orderkey")
#: bytes a copied row of each Q3 input takes at most (value + validity)
LINEITEM_ROW = (8 + 1) * 3 + (4 + 1)        # orderkey, price, discount, date
ORDERS_ROW = (8 + 1) * 2 + (4 + 1) * 2      # orderkey, custkey, date, prio
CUSTOMER_ROW = (8 + 1) + (4 + 1)            # custkey, mktsegment code


def _query(name):
    doc = json.load(open(os.path.join(BENCH, "queries", name + ".json")))
    doc["sql"] = open(os.path.join(BENCH, "queries", doc["sql_file"])).read()
    return doc


QUERIES = {q: _query(q) for q in ("tpch-q1", "tpch-q6", "tpch-q3")}


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """TPC-H at SF0.01 from a seed, as Parquet and as the reference's
    frames: every column the three statements and the sort read."""
    wanted = {}
    for q in QUERIES.values():
        for table, cols in q["reads"].items():
            have = wanted.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    wanted["orders"].append("o_totalprice")
    tmp = tmp_path_factory.mktemp("tpch_sf001")
    paths, frames, _rows, _bytes = datagen.write_tables(wanted, 20281001,
                                                        0.01, str(tmp))
    return paths, frames


@pytest.fixture()
def spark(tpch):
    lm.clear_caches()
    session = SparkSession({"spark.sail.execution.mesh": "off",
                            "spark.sail.cache.result.enabled": "false",
                            "spark.sail.execution.backend.force": "xla"})
    for name, path in tpch[0].items():
        session.read.parquet(path).createOrReplaceTempView(name)
    return session


def _fake_memory(monkeypatch, limit, in_use):
    monkeypatch.setattr(lm, "_device_memory_stats", lambda: {
        "bytes_limit": int(limit), "bytes_in_use": int(in_use)})


def _run(spark, sql):
    table = spark.sql(sql).toArrow()
    return table, profiler.last_profile()


def _sync_sites(profile):
    return [s.attributes.get("site") for s in profile.spans
            if s.name == "sync"]


def _op_attrs(profile, op):
    return [s.attributes for s in profile.spans if s.name == op]


def _hold_q3_to_the_reference(table, frames):
    numbers = compare.compare_answers([("tpch-q3", table)], QUERIES, frames)
    assert numbers["row_count_mismatches"] == 0
    assert numbers["exact_mismatches"] == 0
    assert numbers["worst_rel_err"] <= 1e-10


def _hold_sort_to_the_reference(table, frames):
    exp = frames["orders"].sort_values(
        ["o_totalprice", "o_orderkey"], ascending=[False, True],
        kind="stable")
    got = compare.answer_frame(table)
    assert len(got) == len(exp)
    assert (got["c0"].to_numpy() == exp["o_orderkey"].to_numpy()).all()
    np.testing.assert_allclose(got["c1"].to_numpy(),
                               exp["o_totalprice"].to_numpy(),
                               rtol=1e-10, atol=0)


# -- the decision function, on Q3's shapes at scale factor 10 ----------------

JOIN, SORT = "execution.join_spill_rows", "execution.sort_spill_rows"
Q3_SF10 = [
    (JOIN, 64 * MI + 16 * MI,
     lm.join_working_set(64 * MI, 16 * MI,
                         LINEITEM_ROW + ORDERS_ROW + CUSTOMER_ROW)),
    (JOIN, 16 * MI + 3 * MI // 2,
     lm.join_working_set(16 * MI, 3 * MI // 2, ORDERS_ROW + CUSTOMER_ROW)),
    (SORT, 64 * MI, lm.sort_working_set(64 * MI, LINEITEM_ROW)),
]


@pytest.mark.parametrize("key,capacity,working_set", Q3_SF10)
def test_q3_at_sf10_fits_a_chip_with_2_gb_in_use(monkeypatch, key,
                                                  capacity, working_set):
    _fake_memory(monkeypatch, 16e9, 2e9)
    assert lm.out_of_core(key, capacity, working_set) is None


@pytest.mark.parametrize("key,capacity,working_set", [Q3_SF10[0],
                                                      Q3_SF10[2]])
def test_q3_at_sf10_does_not_fit_1_gb(monkeypatch, key, capacity,
                                      working_set):
    _fake_memory(monkeypatch, 1e9, 0)
    decision = lm.out_of_core(key, capacity, working_set)
    assert decision is not None and not decision.by_rows
    # a partition pair, or two runs, hold what fits three quarters of
    # the free gigabyte
    assert 0 < decision.rows < capacity
    assert working_set * decision.rows / capacity <= 0.75e9


def test_the_lineitem_join_is_reckoned_at_gigabytes_not_rows():
    key, _capacity, working_set = Q3_SF10[0]
    assert key == JOIN and 4e9 < working_set < 10.5e9


#: Q3's two joins as the executor runs them on the chip at SF10: probe
#: and build capacity, the bytes a copied output row takes, and what the
#: v5e's compiler counts for the phase (temporaries + results; PERF.md §5)
LINEITEM_JOIN = (3 * MI // 2, 32 * MI,
                 LINEITEM_ROW + ORDERS_ROW + CUSTOMER_ROW,
                 880_029_184 + 416_815_104)
ORDERS_JOIN = (327_680, 8 * MI, ORDERS_ROW + CUSTOMER_ROW,
               145_916_928 + 103_618_560)


@pytest.mark.parametrize("probe,build,out_row,_counted",
                         [LINEITEM_JOIN, ORDERS_JOIN])
def test_q3s_joins_as_they_run_on_the_chip_stay_in_hbm(monkeypatch, probe,
                                                       build, out_row,
                                                       _counted):
    # 1.32 GB of resident columns between statements (PERF.md §4)
    _fake_memory(monkeypatch, 16.9e9, 1.32e9)
    working_set = lm.join_working_set(probe, build, out_row)
    assert lm.out_of_core(JOIN, probe + build, working_set) is None


@pytest.mark.parametrize("probe,build,_out_row,counted",
                         [LINEITEM_JOIN, ORDERS_JOIN])
def test_the_join_phase_bound_bounds_the_compilers_count(probe, build,
                                                         _out_row, counted):
    """The merge probe (ops/join.py _merge_ranges) sorts build and probe
    keys together: each side's rows pay for a row of the merge, and the
    phase alone (no output row) stays above what the compiler counts."""
    assert lm.join_working_set(probe, build, 0) > counted


def test_no_memory_reported_means_nothing_spills(monkeypatch):
    monkeypatch.setattr(lm, "_device_memory_stats", lambda: None)
    assert lm.device_free_bytes() is None
    assert lm.out_of_core(JOIN, 1 << 40, 1 << 50) is None
    assert lm.out_of_core(SORT, 1 << 40, 1 << 50) is None


@pytest.mark.parametrize("key,env", [
    (JOIN, "SAIL_EXECUTION__JOIN_SPILL_ROWS"),
    (SORT, "SAIL_EXECUTION__SORT_SPILL_ROWS")])
def test_an_explicit_row_count_overrides_the_memory(monkeypatch, key, env):
    _fake_memory(monkeypatch, 16e9, 0)
    monkeypatch.setenv(env, "1000")
    assert lm.out_of_core(key, 1000, 1) is None       # capacity bounds rows
    assert lm.out_of_core(key, 1001, 1) == lm.OutOfCore(1000, True)
    monkeypatch.setenv(env, "0")                        # never
    _fake_memory(monkeypatch, 1e6, 0)
    assert lm.out_of_core(key, 1 << 30, 1 << 40) is None


def test_neither_key_has_a_default_row_count():
    from sail_tpu.config import get
    assert get(JOIN) is None and get(SORT) is None


# -- Q3 and a sort, in device memory and through the spill paths -------------

def test_q3_stays_on_the_device_when_it_fits(spark, tpch, monkeypatch):
    _fake_memory(monkeypatch, 16e9, 2e9)
    table, profile = _run(spark, QUERIES["tpch-q3"]["sql"])
    _hold_q3_to_the_reference(table, tpch[1])
    assert profile.span_count("spill") == 0 and profile.spill_bytes == 0
    assert "join.spill_decision" not in _sync_sites(profile)
    assert profile.span_count("sync", under="execute") == 5
    joins = _op_attrs(profile, "op.JoinExec")
    assert len(joins) == 2
    for attrs in joins:
        assert attrs["spilled"] is False
        assert attrs["free_bytes"] == 14_000_000_000
        assert 0 < attrs["working_set_bytes"] < attrs["free_bytes"]


@pytest.mark.parametrize("how", ["rows", "memory"])
def test_q3_through_the_spill_path_equals_the_reference(spark, tpch,
                                                        monkeypatch, how):
    if how == "rows":
        monkeypatch.setenv("SAIL_EXECUTION__JOIN_SPILL_ROWS", "1000")
    else:
        _fake_memory(monkeypatch, 100_000, 0)
    table, profile = _run(spark, QUERIES["tpch-q3"]["sql"])
    _hold_q3_to_the_reference(table, tpch[1])
    spills = [s for s in profile.spans if s.name == "spill"]
    assert spills and profile.spill_bytes > 0
    assert "join.spill_decision" in _sync_sites(profile)
    for s in spills:
        assert s.attributes["kind"] in ("join", "sort")
        assert s.attributes["rows"] > 0 and s.attributes["partitions"] >= 2
    assert sum(s.attributes["bytes"] for s in spills) == profile.spill_bytes
    assert any(a["spilled"] for a in _op_attrs(profile, "op.JoinExec"))
    by_parent = {s.span_id: s for s in profile.spans}
    assert all(by_parent[s.parent_id].name.startswith("op.")
               for s in spills)


@pytest.mark.parametrize("how", ["device", "rows", "memory"])
def test_a_sort_of_orders_equals_the_reference(spark, tpch, monkeypatch,
                                               how):
    if how == "rows":
        monkeypatch.setenv("SAIL_EXECUTION__SORT_SPILL_ROWS", "500")
    elif how == "memory":
        _fake_memory(monkeypatch, 100_000, 0)
    else:
        _fake_memory(monkeypatch, 16e9, 2e9)
    table, profile = _run(spark, SORT_SQL)
    _hold_sort_to_the_reference(table, tpch[1])
    sorts = _op_attrs(profile, "op.SortExec")
    assert len(sorts) == 1 and sorts[0]["working_set_bytes"] > 0
    if how == "device":
        assert profile.span_count("spill") == 0
        assert "sort.spill_decision" not in _sync_sites(profile)
        assert sorts[0]["spilled"] is False
    else:
        spill, = [s for s in profile.spans if s.name == "spill"]
        assert spill.attributes["kind"] == "sort"
        assert spill.attributes["rows"] == 15_000
        assert spill.attributes["bytes"] == profile.spill_bytes > 0
        assert sorts[0]["spilled"] is True


# -- the default: what the existing cells' statements decide today -----------

@pytest.mark.parametrize("query,syncs", [("tpch-q1", 1), ("tpch-q6", 1),
                                         ("tpch-q3", 5)])
def test_at_the_default_the_cells_statements_decide_as_before(spark, query,
                                                              syncs):
    assert os.environ.get("SAIL_EXECUTION__JOIN_SPILL_ROWS") is None
    assert os.environ.get("SAIL_EXECUTION__SORT_SPILL_ROWS") is None
    _table, profile = _run(spark, QUERIES[query]["sql"])
    sites = _sync_sites(profile)
    assert "join.spill_decision" not in sites
    assert "sort.spill_decision" not in sites
    assert profile.span_count("sync", under="execute") == syncs
    assert profile.span_count("spill") == 0 and profile.spill_bytes == 0
