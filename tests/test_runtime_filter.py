"""Runtime join filters: kernel correctness (usable-row and distinct-key
counts, key bounds), plan-annotation lineage, on/off result equivalence
across join types incl. NULL keys, scan-side pruning, one join-phase
program with and without a filter, EXPLAIN surfaces, cluster-mode filter
shipping, and adaptive skips."""

import json

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.exec.local import clear_caches
from sail_tpu.plan import nodes as pn
from sail_tpu.plan import rex as rx
from sail_tpu.sql import parse_one


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_caches()
    yield
    clear_caches()


def _session(**conf):
    base = {"spark.sail.execution.mesh": "off"}
    base.update(conf)
    return SparkSession(base)


def _resolve(spark, sql):
    return spark._resolve(parse_one(sql))


# ---------------------------------------------------------------------------
# kernel: key_stats / column_bounds
# ---------------------------------------------------------------------------

def _long(values, validity=None, kind="long"):
    return (kind, values, validity)


#: (key columns, selection, n_build, ndv, per-column (min, max) or None
#: where the build is empty and the bounds are sentinels with min > max)
_KEY_STATS_CASES = {
    "dead-rows": (
        [_long([5, 5, 9, 7, 7, 3])],
        [True, True, False, True, True, False],
        4, 2, [(5, 7)]),
    "null-key-parts": (
        [_long([1, 2, 2, 4], [True, False, True, True]),
         _long([10, 20, 20, 40], [True, True, True, False])],
        [True] * 4,
        2, 2, [(1, 2), (10, 20)]),
    "empty-build": (
        [_long([7, 8, 9])],
        [False] * 3,
        0, 0, None),
    "multi-column-hashed": (
        # two int64 columns exceed 64 packed bits → hash64 path
        [_long([-2**62, 2**61, -2**62, 2**61, 5]),
         _long([2**62 - 1, -2**60, 2**62 - 1, -2**60 + 1, 5])],
        [True] * 5,
        5, 4, [(-2**62, 2**61), (-2**60, 2**62 - 1)]),
    "spark-float-keys": (
        # -0.0 and 0.0 are ONE key; NaN is ONE key (Spark join equality)
        [_long([0.0, -0.0, np.nan, np.nan, 1.5], kind="double")],
        [True] * 5,
        5, 3, None),
    "all-distinct": (
        [_long(list(range(100, 164)))],
        [True] * 64,
        64, 64, [(100, 163)]),
}


@pytest.mark.parametrize("case", sorted(_KEY_STATS_CASES))
def test_key_stats_and_column_bounds(case):
    """What ``_rtf_prepare`` reads of a join's source side: usable rows,
    distinct JOIN-equal keys, and each key column's bounds over the
    usable rows (dead rows and rows with a NULL key part excluded)."""
    import jax.numpy as jnp

    from sail_tpu.columnar.batch import Column
    from sail_tpu.ops import runtime_filter as rtfk
    from sail_tpu.spec import data_type as dt
    cols_in, sel, n_build, ndv, bounds = _KEY_STATS_CASES[case]
    cols = []
    for kind, values, validity in cols_in:
        dtype = dt.LongType() if kind == "long" else dt.DoubleType()
        v = None if validity is None else jnp.asarray(np.asarray(validity))
        cols.append(Column(jnp.asarray(np.asarray(values)), v, dtype))
    sel = jnp.asarray(np.asarray(sel))
    res = rtfk.key_stats(cols, sel)
    assert res._fields == ("n_build", "ndv")  # no bit array, no key bounds
    assert (int(res.n_build), int(res.ndv)) == (n_build, ndv)
    usable = sel
    for c in cols:
        if c.validity is not None:
            usable = usable & c.validity
    got = [tuple(np.asarray(x).item() for x in
                 rtfk.column_bounds(c.data, usable)) for c in cols]
    if bounds is not None:
        assert got == bounds
    elif n_build == 0:
        assert all(lo > hi for lo, hi in got)  # the empty-build sentinel


@pytest.mark.parametrize("size", [2, 4, 64])
@pytest.mark.parametrize("case", sorted(_KEY_STATS_CASES))
def test_key_bucket_holds_the_usable_rows_keys(case, size):
    """What a key list is made from: each key column's values on the
    usable rows, in row order, at the front of a bucket of a fixed size
    whatever the source's capacity; zeros where there are more."""
    import jax.numpy as jnp

    from sail_tpu.columnar.batch import Column
    from sail_tpu.ops import runtime_filter as rtfk
    from sail_tpu.spec import data_type as dt
    cols_in, sel, n_build, _ndv, _bounds = _KEY_STATS_CASES[case]
    cols, usable = [], np.asarray(sel)
    for kind, values, validity in cols_in:
        dtype = dt.LongType() if kind == "long" else dt.DoubleType()
        v = None if validity is None else jnp.asarray(np.asarray(validity))
        if validity is not None:
            usable = usable & np.asarray(validity)
        cols.append(Column(jnp.asarray(np.asarray(values)), v, dtype))
    bucket = rtfk.key_bucket(cols, jnp.asarray(usable), size)
    assert len(bucket) == len(cols)
    for got, (_kind, values, _validity) in zip(bucket, cols_in):
        assert got.shape == (size,)
        if n_build <= size:
            np.testing.assert_array_equal(np.asarray(got)[:n_build],
                                          np.asarray(values)[usable])
        else:
            assert not np.asarray(got).any()


# ---------------------------------------------------------------------------
# plan annotation lineage
# ---------------------------------------------------------------------------

def _register_star(spark, n=4000, dim=40):
    rng = np.random.default_rng(5)
    fact = pd.DataFrame({"k": rng.integers(0, 1000, n),
                         "v": rng.random(n)})
    d = pd.DataFrame({"id": np.arange(dim),
                      "flag": np.arange(dim) % 2 == 0})
    spark.createDataFrame(fact).createOrReplaceTempView("fact")
    spark.createDataFrame(d).createOrReplaceTempView("dim")
    return fact, d


def _find(plan, cls):
    return [x for x in pn.walk_plan(plan) if isinstance(x, cls)]


class TestAnnotation:
    def test_inner_join_annotates_join_and_scan(self):
        spark = _session()
        _register_star(spark)
        plan = _resolve(
            spark, "SELECT * FROM fact JOIN dim ON fact.k = dim.id")
        joins = [j for j in _find(plan, pn.JoinExec) if j.runtime_filters]
        assert joins, "inner join should carry runtime_filters"
        tgt = joins[0].runtime_filters[0]
        scan = [s for s in _find(plan, pn.ScanExec)
                if any(t.fid == tgt.fid for t in s.runtime_filters)]
        assert scan and scan[0].schema[tgt.column].name == "k"

    def test_filter_and_project_chain_reaches_scan(self):
        spark = _session()
        _register_star(spark)
        plan = _resolve(spark, """
            SELECT * FROM (SELECT k AS kk, v FROM fact WHERE v > 0.5) f
            JOIN dim ON f.kk = dim.id""")
        joins = [j for j in _find(plan, pn.JoinExec) if j.runtime_filters]
        assert joins
        tgt = joins[0].runtime_filters[0]
        scans = [s for s in _find(plan, pn.ScanExec)
                 if any(t.fid == tgt.fid for t in s.runtime_filters)]
        assert scans, "filter should trace through project+filter"
        assert scans[0].schema[tgt.column].name == "k"

    def test_computed_key_blocks_annotation(self):
        spark = _session()
        _register_star(spark)
        plan = _resolve(spark, """
            SELECT * FROM (SELECT k + 1 AS kk FROM fact) f
            JOIN dim ON f.kk = dim.id""")
        for s in _find(plan, pn.ScanExec):
            assert not any(t.side == "probe" for t in s.runtime_filters), \
                "k+1 is not key-preserving; the probe scan must not be " \
                "annotated (build-side edges to dim are fine)"

    def test_aggregate_blocks_annotation(self):
        spark = _session()
        _register_star(spark)
        plan = _resolve(spark, """
            SELECT * FROM (SELECT k, count(*) c FROM fact GROUP BY k) f
            JOIN dim ON f.k = dim.id""")
        for s in _find(plan, pn.ScanExec):
            assert not any(t.side == "probe" for t in s.runtime_filters), \
                "filters must not push through an aggregate"

    def test_left_and_anti_joins_not_annotated(self):
        spark = _session()
        _register_star(spark)
        for sql in (
                "SELECT * FROM fact LEFT JOIN dim ON fact.k = dim.id",
                "SELECT * FROM fact LEFT ANTI JOIN dim "
                "ON fact.k = dim.id"):
            plan = _resolve(spark, sql)
            for j in _find(plan, pn.JoinExec):
                assert not j.runtime_filters, sql

    def test_explain_renders_annotations(self):
        spark = _session()
        _register_star(spark)
        text = spark.sql(
            "EXPLAIN SELECT * FROM fact JOIN dim ON fact.k = dim.id"
        ).toPandas().plan[0]
        assert "runtime_filter=[" in text
        assert "runtime_filters=[" in text  # the annotated scan


# ---------------------------------------------------------------------------
# on/off equivalence (incl. NULL keys)
# ---------------------------------------------------------------------------

_JOIN_SQLS = [
    ("inner", "SELECT f.k, f.v, d.w FROM f JOIN d ON f.k = d.k"),
    ("left", "SELECT f.k, f.v, d.w FROM f LEFT JOIN d ON f.k = d.k"),
    ("semi", "SELECT f.k, f.v FROM f LEFT SEMI JOIN d ON f.k = d.k"),
    ("anti", "SELECT f.k, f.v FROM f LEFT ANTI JOIN d ON f.k = d.k"),
]


def _null_key_frames():
    rng = np.random.default_rng(11)
    fk = [None if rng.random() < 0.1 else int(x)
          for x in rng.integers(0, 300, 2500)]
    f = pd.DataFrame({"k": pd.array(fk, dtype="Int64"),
                      "v": rng.random(2500)})
    dk = [None, None] + [int(x) for x in rng.integers(0, 60, 80)]
    d = pd.DataFrame({"k": pd.array(dk, dtype="Int64"),
                      "w": rng.random(82)})
    return f, d


@pytest.mark.parametrize("jt,sql", _JOIN_SQLS)
def test_on_off_equivalence(jt, sql):
    outs = {}
    for mode in ("true", "false"):
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        clear_caches()
        f, d = _null_key_frames()
        spark.createDataFrame(f).createOrReplaceTempView("f")
        spark.createDataFrame(d).createOrReplaceTempView("d")
        outs[mode] = spark.sql(sql).toArrow()
    assert outs["true"].equals(outs["false"]), jt


def test_date_key_join_on_off_equivalence():
    # DateType keys exercise the raw-days → date-literal conversion in
    # the pushed bounds/in-list conjuncts
    import datetime
    outs = {}
    for mode in ("true", "false"):
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        clear_caches()
        rng = np.random.default_rng(12)
        base = datetime.date(2024, 1, 1)
        f = pd.DataFrame({
            "d": [base + datetime.timedelta(days=int(x))
                  for x in rng.integers(0, 365, 2000)],
            "v": rng.random(2000)})
        dim = pd.DataFrame({
            "d": [base + datetime.timedelta(days=int(x))
                  for x in range(10, 40)],
            "w": rng.random(30)})
        spark.createDataFrame(f).createOrReplaceTempView("fd")
        spark.createDataFrame(dim).createOrReplaceTempView("dd")
        outs[mode] = spark.sql(
            "SELECT fd.d, fd.v, dd.w FROM fd JOIN dd ON fd.d = dd.d"
        ).toArrow()
        if mode == "true":
            assert profiler.last_profile().rtf_rows_pruned > 0
    assert outs["true"].equals(outs["false"])


def test_inner_join_results_bit_identical_with_pruning():
    outs = {}
    for mode in ("true", "false"):
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        clear_caches()
        _register_star(spark)
        outs[mode] = spark.sql(
            "SELECT fact.k, fact.v, dim.flag FROM fact "
            "JOIN dim ON fact.k = dim.id WHERE dim.flag").toArrow()
        if mode == "true":
            prof = profiler.last_profile()
            assert prof.rtf_built >= 1
            assert prof.rtf_rows_pruned > 0  # fact keys 0..999 vs dim 0..39
    assert outs["true"].equals(outs["false"])


# ---------------------------------------------------------------------------
# the key list: decided by the rows the source keeps, not its capacity
# ---------------------------------------------------------------------------

#: orders in both tables; over 131,072, the source capacity above which
#: no key list used to leave the device
_ORDERS = 140_000
_IN_HAVING = ("SELECT o.k, o.v FROM o WHERE o.k IN "
              "(SELECT k FROM li GROUP BY k HAVING count(*) > 1)")

#: case -> (orders the HAVING filter keeps, inListMax, a list goes out)
_LIST_CASES = {
    "a-handful-over-the-old-gate": (5, None, True),
    "a-handful-over-inListMax": (5, "4", False),
    "more-than-inListMax": (9_000, None, False),
}


def _having_tables(keep):
    """``li``: one row per order and three more for ``keep`` of them,
    so the HAVING filter keeps ``keep`` rows of a 140,000-group
    aggregate, at its capacity; ``o``: the orders."""
    rng = np.random.default_rng(42)
    kept = np.sort(rng.choice(_ORDERS, keep, replace=False))
    li = pd.DataFrame({"k": np.concatenate(
        [np.arange(_ORDERS), np.repeat(kept, 3)])})
    o = pd.DataFrame({"k": np.arange(_ORDERS), "v": rng.random(_ORDERS)})
    return li, o, kept


@pytest.mark.parametrize("case", sorted(_LIST_CASES))
def test_the_list_is_decided_by_the_rows_the_source_keeps(case):
    """An IN-subquery over a HAVING filter (TPC-H Q18's shape): the
    semi join's source keeps a few rows at its aggregate's capacity. A
    list goes out when every usable key fits the ``inListMax`` bucket,
    bounds alone otherwise; either way the filter costs the one
    ``rtf_build`` sync, which fetches no more than the bucket."""
    keep, in_list_max, listed = _LIST_CASES[case]
    li, o, kept = _having_tables(keep)
    conf = {"spark.sail.execution.backend.force": "xla",
            "spark.sail.cache.result.enabled": "false"}
    if in_list_max is not None:
        conf["spark.sail.join.runtimeFilter.inListMax"] = in_list_max
    syncs, answers = {}, {}
    for mode in ("false", "true"):
        clear_caches()
        spark = _session(**conf,
                         **{"spark.sail.join.runtimeFilter.enabled": mode})
        spark.createDataFrame(li).createOrReplaceTempView("li")
        spark.createDataFrame(o).createOrReplaceTempView("o")
        answers[mode] = spark.sql(_IN_HAVING).toPandas() \
            .sort_values("k").reset_index(drop=True)
        prof = profiler.last_profile()
        syncs[mode] = [s.attributes for s in prof.spans if s.name == "sync"]
    assert list(answers["true"].k) == list(kept)
    assert answers["true"].equals(answers["false"])
    # the filter adds its one sync, listed or not
    assert len(syncs["true"]) == len(syncs["false"]) + 1
    build, = [a for a in syncs["true"] if a["site"] == "rtf_build"]
    semi, = [s.attributes for s in prof.spans if s.name == "op.JoinExec"]
    assert semi["build_capacity"] > 131_072
    assert semi["rtf_source_rows"] == keep and semi["rtf_ndv"] == keep
    assert semi["rtf_pushed"] == 1 and semi["rtf_listed"] is listed
    bucket = min(int(in_list_max or 8192), semi["build_capacity"])
    assert build["bytes"] <= 8 * bucket + 64
    scan = [s.attributes for s in prof.spans if s.name == "op.ScanExec"
            and s.attributes["runtime_conjuncts"]]
    assert len(scan) == 1
    if listed:
        assert semi["rtf_list_keys"] == keep
        assert scan[0]["rows"] == keep
    else:
        assert semi["rtf_list_keys"] == 0
        assert scan[0]["rows"] == kept[-1] - kept[0] + 1  # the bounds


# ---------------------------------------------------------------------------
# the join phase takes no filter: one program, no mask, same rows
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (pjit, while, cond, scan bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def test_one_join_phase_program_with_and_without_filter(monkeypatch):
    import jax

    from sail_tpu.exec import local as xl
    captured = []
    real = xl.LocalExecutor._compile_join_keys

    def spy(self, p, left, right, seed):
        builder = real(self, p, left, right, seed)

        def recording_builder():
            fn, aux = builder()

            def recorded(*args):
                captured.append((fn, jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    args)))
                return fn(*args)
            return recorded, aux
        return recording_builder

    monkeypatch.setattr(xl.LocalExecutor, "_compile_join_keys", spy)
    sql = ("SELECT fact.k, fact.v, dim.flag FROM fact "
           "JOIN dim ON fact.k = dim.id")
    outs = {}
    for mode in ("true", "false"):  # ONE process, caches kept between
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        _register_star(spark)
        outs[mode] = spark.sql(sql).toPandas().sort_values(
            ["k", "v"]).reset_index(drop=True)
        if mode == "true":
            assert profiler.last_profile().rtf_pushed >= 1
    assert outs["true"].equals(outs["false"])
    phases = {key for key, _ident in xl._OP_CACHE.entries
              if key[0] == "join_phase"}
    assert len(phases) == 1, phases
    assert captured
    for fn, shapes in captured:
        # probe columns, probe selection, build columns, build selection
        assert len(shapes) == 4
        eqns = list(_walk_eqns(jax.make_jaxpr(fn)(*shapes).jaxpr))
        assert any(e.primitive.name == "sort" for e in eqns)
        for e in eqns:
            if e.primitive.name == "gather":
                operand = e.invars[0].aval
                assert operand.dtype != np.bool_, \
                    f"a gather from a bool{operand.shape} array: the " \
                    "bloom's bit probe is back in the join phase"


@pytest.mark.parametrize("in_list_max", ["8192", "0"])
@pytest.mark.parametrize("jt", ["inner", "semi"])
def test_repeated_build_keys_outside_the_probe(jt, in_list_max):
    """``has_duplicate_build_keys`` looks at the LIVE build rows. A build
    whose repeated keys all miss the probe's key set read as unique
    under the old in-join mask (``join_unique``); without it the join
    takes ``join_expand`` wherever the scan push did not remove the
    rows first (``inListMax=0``: bounds only, and the repeated keys lie
    inside them). Both routes are exact: same rows filter on and off."""
    from sail_tpu.exec import local as xl
    rng = np.random.default_rng(21)
    probe = pd.DataFrame({"id": np.arange(0, 2000, 2),
                          "v": rng.random(1000)})
    odd = np.arange(1, 2000, 2)
    build = pd.DataFrame({
        "k": np.concatenate([np.arange(0, 2000, 2), odd, odd, odd]),
        "w": rng.random(1000 + 3 * len(odd))})
    sql = ("SELECT small.id, small.v, big.w FROM small JOIN big "
           "ON small.id = big.k") if jt == "inner" else \
        ("SELECT small.id, small.v FROM small LEFT SEMI JOIN big "
         "ON small.id = big.k")
    outs, unique_route = {}, {}
    for mode in ("true", "false"):
        spark = _session(**{
            "spark.sail.join.runtimeFilter.enabled": mode,
            "spark.sail.join.runtimeFilter.inListMax": in_list_max})
        clear_caches()
        spark.createDataFrame(probe).createOrReplaceTempView("small")
        spark.createDataFrame(build).createOrReplaceTempView("big")
        outs[mode] = spark.sql(sql).toPandas()
        unique_route[mode] = any(
            key[0] == "join_unique" for key, _ident in xl._OP_CACHE.entries)
        if mode == "true":
            assert profiler.last_profile().rtf_built >= 1
    cols = list(outs["true"].columns)
    assert len(outs["true"]) == 1000
    for mode in outs:
        outs[mode] = outs[mode].sort_values(cols).reset_index(drop=True)
    assert outs["true"].equals(outs["false"])
    assert unique_route["false"] is False
    # the exact in-list removes the repeated rows at the scan, so the
    # build reads as unique; bounds alone keep them, and the join expands
    assert unique_route["true"] is (in_list_max != "0")


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE surfaces
# ---------------------------------------------------------------------------

def test_explain_analyze_shows_rows_pruned():
    spark = _session()
    _register_star(spark)
    text = spark.sql(
        "EXPLAIN ANALYZE SELECT SUM(fact.v) FROM fact "
        "JOIN dim ON fact.k = dim.id").toPandas().plan[0]
    assert "runtime filters:" in text
    assert "rows_pruned=" in text
    pruned = int(text.split("rows_pruned=")[1].split()[0])
    assert pruned > 0


def test_explain_analyze_json_includes_counters():
    spark = _session()
    _register_star(spark)
    out = spark.sql(
        "EXPLAIN ANALYZE FORMAT JSON SELECT SUM(fact.v) FROM fact "
        "JOIN dim ON fact.k = dim.id").toPandas().plan[0]
    doc = json.loads(out)
    rf = doc["runtime_filter"]
    assert rf["built"] >= 1
    assert rf["rows_pruned"] > 0
    assert rf["build_ms"] >= 0


# ---------------------------------------------------------------------------
# adaptive / configurable skips
# ---------------------------------------------------------------------------

def test_first_join_trace_does_not_leak_module_constants():
    """The first-ever import of ops.runtime_filter must not land while
    a program is being TRACED: a module-level jnp constant created
    inside a trace is a leaked tracer and poisons every later trace
    that uses it with UnexpectedTracerError. A first join with filters
    disabled never imports the module (the join phase does not use it);
    a later join WITH filters imports it in _rtf_prepare, on the host,
    before its rtf_build program is traced (the module holds no
    constant of its own now: _KEY_MAX is ops/join.py's)."""
    import sys

    # simulate a fresh process: the kernels module was never imported
    sys.modules.pop("sail_tpu.ops.runtime_filter", None)
    clear_caches()
    spark = _session(**{"spark.sail.join.runtimeFilter.enabled": "false"})
    _register_star(spark)
    off = spark.sql("SELECT SUM(fact.v) FROM fact JOIN dim "
                    "ON fact.k = dim.id").toArrow()
    # a later join WITH filters uses the module's constants in a new
    # trace — poisoned constants raise UnexpectedTracerError here
    spark2 = _session()
    clear_caches()
    _register_star(spark2)
    on = spark2.sql("SELECT SUM(fact.v) FROM fact JOIN dim "
                    "ON fact.k = dim.id").toArrow()
    assert profiler.last_profile().rtf_built >= 1
    assert on.equals(off)


def test_disabled_builds_nothing():
    spark = _session(**{"spark.sail.join.runtimeFilter.enabled": "false"})
    _register_star(spark)
    spark.sql("SELECT SUM(fact.v) FROM fact JOIN dim "
              "ON fact.k = dim.id").toArrow()
    prof = profiler.last_profile()
    assert prof.rtf_built == 0 and prof.rtf_pushed == 0


def test_min_build_rows_skips_small_builds():
    spark = _session(
        **{"spark.sail.join.runtimeFilter.minBuildRows": "1000000"})
    _register_star(spark)
    spark.sql("SELECT SUM(fact.v) FROM fact JOIN dim "
              "ON fact.k = dim.id").toArrow()
    assert profiler.last_profile().rtf_built == 0


def test_adaptive_skip_after_useless_filter():
    # every fact key exists in dim → the filter prunes nothing; the
    # second execution must skip the build (observed selectivity ≈ 0)
    spark = _session()
    rng = np.random.default_rng(6)
    fact = pd.DataFrame({"k": rng.integers(0, 40, 5000),
                         "v": rng.random(5000)})
    d = pd.DataFrame({"id": np.arange(40)})
    spark.createDataFrame(fact).createOrReplaceTempView("fact")
    spark.createDataFrame(d).createOrReplaceTempView("dim")
    sql = "SELECT SUM(fact.v) FROM fact JOIN dim ON fact.k = dim.id"
    spark.sql(sql).toArrow()
    first = profiler.last_profile()
    assert first.rtf_built >= 1  # tried once
    spark.sql(sql).toArrow()
    second = profiler.last_profile()
    assert second.rtf_built == 0  # learned it was useless

def test_reverse_filter_prunes_fact_build_side():
    # when the FACT table is the join's build (right) side, the filter
    # flows in REVERSE: the small probe side runs first and its key set
    # prunes the fact scan
    outs = {}
    for mode in ("true", "false"):
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        clear_caches()
        rng = np.random.default_rng(7)
        big = pd.DataFrame({"k": rng.integers(0, 500, 20000),
                            "w": rng.random(20000)})
        small = pd.DataFrame({"id": np.arange(50), "v": rng.random(50)})
        spark.createDataFrame(big).createOrReplaceTempView("big")
        spark.createDataFrame(small).createOrReplaceTempView("small")
        outs[mode] = spark.sql(
            "SELECT SUM(small.v * big.w) FROM small JOIN big "
            "ON small.id = big.k").toArrow()
        if mode == "true":
            prof = profiler.last_profile()
            assert prof.rtf_built >= 1
            # big keys 0..499 vs small ids 0..49 → ~90% of the build
            # side prunes before upload
            assert prof.rtf_rows_pruned > 10000
    assert outs["true"].equals(outs["false"])


def test_adaptive_verdict_is_per_query_not_per_shape():
    # a useless-filter verdict for `fact JOIN dim` (unfiltered dim: no
    # pruning) must not disable the filter for the SAME join shape with
    # a selective WHERE on dim
    spark = _session()
    rng = np.random.default_rng(14)
    fact = pd.DataFrame({"k": rng.integers(0, 40, 8000),
                         "v": rng.random(8000)})
    d = pd.DataFrame({"id": np.arange(40), "w": np.arange(40) * 1.0})
    spark.createDataFrame(fact).createOrReplaceTempView("fact")
    spark.createDataFrame(d).createOrReplaceTempView("dim")
    useless = "SELECT SUM(fact.v) FROM fact JOIN dim ON fact.k = dim.id"
    spark.sql(useless).toArrow()
    spark.sql(useless).toArrow()
    assert profiler.last_profile().rtf_built == 0  # learned: useless
    selective = ("SELECT SUM(fact.v) FROM fact JOIN dim "
                 "ON fact.k = dim.id WHERE dim.w < 3")
    spark.sql(selective).toArrow()
    prof = profiler.last_profile()
    assert prof.rtf_built >= 1, \
        "the unfiltered join's verdict leaked onto the filtered one"
    assert prof.rtf_rows_pruned > 0


def test_empty_build_date_join_does_not_overflow():
    # an empty build side leaves dtype-extreme sentinel bounds; for date
    # keys those used to overflow the date-literal conversion
    spark = _session()
    import datetime
    base = datetime.date(2024, 1, 1)
    f = pd.DataFrame({
        "d": [base + datetime.timedelta(days=i) for i in range(200)],
        "v": np.arange(200.0)})
    dim = pd.DataFrame({
        "d": [base + datetime.timedelta(days=i) for i in range(5)],
        "flag": [False] * 5})  # filter below removes every build row
    spark.createDataFrame(f).createOrReplaceTempView("fd")
    spark.createDataFrame(dim).createOrReplaceTempView("dd")
    got = spark.sql(
        "SELECT fd.v FROM fd JOIN dd ON fd.d = dd.d WHERE dd.flag"
    ).toPandas()
    assert len(got) == 0


def test_parquet_filter_survives_adaptive_feedback(tmp_path):
    # parquet pruning happens inside the dataset read; the adaptive pass
    # must keep the filter alive (footer-count evidence), not condemn it
    import pyarrow.parquet as pq
    spark = _session()
    rng = np.random.default_rng(13)
    fact = pa.table({"k": rng.integers(0, 1000, 20000),
                     "v": rng.random(20000)})
    fp = str(tmp_path / "fact.parquet")
    pq.write_table(fact, fp)
    spark.sql(f"CREATE TABLE pfact USING parquet LOCATION '{fp}'")
    d = pd.DataFrame({"id": np.arange(30)})
    spark.createDataFrame(d).createOrReplaceTempView("dim")
    sql = "SELECT SUM(pfact.v) FROM pfact JOIN dim ON pfact.k = dim.id"
    for _ in range(2):
        spark.sql(sql).toArrow()
    spark.sql(sql).toArrow()
    prof = profiler.last_profile()
    assert prof.rtf_built >= 1, "adaptive pass must not kill the filter"
    assert prof.rtf_rows_pruned > 0


# ---------------------------------------------------------------------------
# the footers' verdict: bounds that can prune nothing are not pushed
# ---------------------------------------------------------------------------

def _footer_case(tmp_path, dim_ids, in_list_max, statistics=True):
    """``pfact JOIN dim`` over a Parquet fact whose ``k`` covers 0..999
    and a 50-row dimension; returns the statement's profile."""
    import pyarrow.parquet as pq
    spark = _session(
        **{"spark.sail.join.runtimeFilter.inListMax": str(in_list_max)})
    rng = np.random.default_rng(36)
    k = rng.integers(0, 1000, 20000)
    k[:2] = (0, 999)
    fp = str(tmp_path / "fact.parquet")
    pq.write_table(pa.table({"k": k, "v": rng.random(20000)}), fp,
                   write_statistics=statistics)
    spark.read.parquet(fp).createOrReplaceTempView("pfact")
    spark.createDataFrame(pd.DataFrame({"id": dim_ids})) \
        .createOrReplaceTempView("dim")
    got = spark.sql("SELECT COUNT(*) AS n FROM pfact JOIN dim "
                    "ON pfact.k = dim.id").toPandas()
    assert got.n[0] == int(np.isin(k, dim_ids).sum())
    return profiler.last_profile()


def _join_and_fact_scan(prof):
    join, = [s.attributes for s in prof.spans if s.name == "op.JoinExec"]
    scan = max((s.attributes for s in prof.spans
                if s.name == "op.ScanExec"), key=lambda a: a["capacity"])
    return join, scan


WIDE = np.arange(0, 1000, 20)        # 50 keys over the whole of 0..999
NARROW = np.arange(0, 300, 6)        # 50 keys in the lowest 30 %


@pytest.mark.parametrize("dim_ids, in_list_max, statistics, conjuncts", [
    pytest.param(WIDE, 10, True, 0, id="wide-bounds-are-not-pushed"),
    pytest.param(NARROW, 10, True, 2, id="narrow-bounds-are-pushed"),
    pytest.param(WIDE, 8192, True, 3, id="a-list-goes-with-its-bounds"),
    pytest.param(WIDE, 10, False, 2, id="no-statistics-pushed-as-before"),
])
def test_bounds_the_footers_say_prune_nothing_are_not_pushed(
        tmp_path, dim_ids, in_list_max, statistics, conjuncts):
    prof = _footer_case(tmp_path, dim_ids, in_list_max, statistics)
    join, scan = _join_and_fact_scan(prof)
    assert scan["runtime_conjuncts"] == conjuncts
    assert scan["fragment"] == "decoded" and scan["capacity"] >= scan["rows"]
    assert join["rtf_ndv"] == 50
    assert join["rtf_listed"] is (conjuncts == 3)
    assert join["rtf_pushed"] == prof.rtf_pushed == (1 if conjuncts else 0)
    assert join["rtf_dropped_by_footer"] == (0 if conjuncts else 1)
    if conjuncts == 0:
        assert scan["rows"] == 20000 and prof.rtf_rows_pruned == 0
    elif statistics:
        assert prof.rtf_rows_pruned == 20000 - scan["rows"] > 10000


def test_the_footers_cut_share(tmp_path):
    """1 - overlap / (max - min + 1) over EVERY file the scan reads; days
    for a date; nothing where a file's footer lacks the column's range."""
    import datetime
    import pyarrow.parquet as pq
    from sail_tpu.exec.local import _footer_cut_share
    from sail_tpu.spec import data_type as dt
    day = datetime.date(1994, 1, 1)
    d = str(tmp_path / "t")
    import os
    os.mkdir(d)
    for i, (lo, hi) in enumerate([(1, 500), (501, 1000)]):
        pq.write_table(pa.table({
            "k": np.arange(lo, hi + 1),
            "d": [day + datetime.timedelta(days=int(x))
                  for x in np.arange(lo, hi + 1)],
            "s": [str(x) for x in range(lo, hi + 1)]}),
            os.path.join(d, f"part-{i}.parquet"))
    scan = pn.ScanExec(out_schema=(), format="parquet", paths=(d,))
    k = pn.Field("k", dt.LongType(), True)
    assert _footer_cut_share(scan, k, 1, 1000) == 0.0
    assert _footer_cut_share(scan, k, -5, 5000) == 0.0
    assert _footer_cut_share(scan, k, 1, 990) == pytest.approx(0.01)
    assert _footer_cut_share(scan, k, 251, 750) == pytest.approx(0.5)
    assert _footer_cut_share(scan, k, 1, 0) == 1.0      # the empty build
    assert _footer_cut_share(scan, k, 2000, 3000) == 1.0
    epoch = (day - datetime.date(1970, 1, 1)).days
    dd = pn.Field("d", dt.DateType(), True)
    assert _footer_cut_share(scan, dd, epoch + 1, epoch + 500) == \
        pytest.approx(0.5)
    assert _footer_cut_share(scan, pn.Field("s", dt.StringType(), True),
                             1, 2) is None              # no range
    assert _footer_cut_share(scan, pn.Field("absent", dt.LongType(), True),
                             1, 2) is None
    pq.write_table(pa.table({"k": np.arange(5)}),
                   os.path.join(d, "part-2.parquet"), write_statistics=False)
    from sail_tpu.io import cache as io_cache
    io_cache.LISTING_CACHE.clear()
    assert _footer_cut_share(scan, k, 1, 1000) is None  # one file is silent
    assert _footer_cut_share(
        pn.ScanExec(out_schema=(), format="csv", paths=(d,)),
        k, 1, 1000) is None


@pytest.fixture(scope="module")
def q5_parquet(tmp_path_factory):
    """Q5's six tables at SF0.05 as Parquet, by the benchmark's generator:
    500 suppliers, about a hundred of them in ASIA."""
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import datagen
    qdir = os.path.join(root, "benchmark", "queries")
    with open(os.path.join(qdir, "tpch-q5.json")) as f:
        reads = json.load(f)["reads"]
    with open(os.path.join(qdir, "tpch-q5.sql")) as f:
        sql = f.read()
    paths, frames, _rows, _bytes = datagen.write_tables(
        reads, 2**31 + 36, 0.05, str(tmp_path_factory.mktemp("q5_sf005")),
        workers=2)
    return sql, paths, frames


def test_q5_with_no_key_list_keeps_one_lineitem_fragment(q5_parquet):
    """The regression (PR 36): with the ASIA suppliers over
    ``inListMax``, as 20,000 are over 8,192 at SF10, only bounds reach
    ``lineitem``'s scan, and the column's whole range satisfies them.
    They used to ride the first statement's fragment key and, once
    condemned, not the second's: ``lineitem`` was decoded, uploaded and
    kept resident twice."""
    from sail_tpu.exec.result_cache import FRAGMENT_CACHE
    from tpch_oracle import ORACLES
    sql, paths, frames = q5_parquet
    asia = frames["nation"].merge(frames["region"][
        frames["region"].r_name == "ASIA"], left_on="n_regionkey",
        right_on="r_regionkey").n_nationkey
    assert frames["supplier"].s_nationkey.isin(asia).sum() > 20
    spark = _session(**{"spark.sail.cache.result.enabled": "false",
                        "spark.sail.execution.backend.force": "xla",
                        "spark.sail.join.runtimeFilter.inListMax": "20"})
    for name, path in paths.items():
        spark.read.parquet(path).createOrReplaceTempView(name)
    exp = ORACLES[5](frames).reset_index(drop=True)
    lineitem_conjuncts = []
    for i in range(3):
        got = spark.sql(sql).toPandas()
        assert list(got.n_name) == list(exp.n_name)
        np.testing.assert_allclose(got.revenue.astype(float), exp.revenue,
                                   rtol=1e-10)
        prof = profiler.last_profile()
        scans = [s.attributes for s in prof.spans if s.name == "op.ScanExec"]
        assert len(scans) == 6
        lineitem = max(scans, key=lambda a: a["rows"])
        assert lineitem["rows"] == len(frames["lineitem"])
        lineitem_conjuncts.append(lineitem["runtime_conjuncts"])
        if i:
            assert prof.span_count("upload") == 0
            assert {a["fragment"] for a in scans} == {"hit"}
    assert lineitem_conjuncts == [0, 0, 0]
    resident = [e for e in FRAGMENT_CACHE._entries.values()
                if e.table_key == paths["lineitem"]]
    assert len(resident) == 1 and resident[0].rows == len(frames["lineitem"])
    assert len(FRAGMENT_CACHE._entries) == 6


# ---------------------------------------------------------------------------
# spill-join integration
# ---------------------------------------------------------------------------

def test_spill_join_prunes_and_matches(monkeypatch):
    # the scan-side filter can shrink the probe below the spill
    # threshold, switching execution paths — the joined row SET must be
    # identical either way (order of an unordered join is unspecified)
    monkeypatch.setenv("SAIL_EXECUTION__JOIN_SPILL_ROWS", "1000")
    outs = {}
    for mode in ("true", "false"):
        spark = _session(**{"spark.sail.join.runtimeFilter.enabled": mode})
        clear_caches()
        rng = np.random.default_rng(9)
        left = pd.DataFrame({"k": rng.integers(0, 500, 4000),
                             "v": rng.random(4000)})
        right = pd.DataFrame({"k": np.arange(25), "w": rng.random(25)})
        spark.createDataFrame(left).createOrReplaceTempView("l")
        spark.createDataFrame(right).createOrReplaceTempView("r")
        outs[mode] = spark.sql(
            "SELECT l.k, l.v, r.w FROM l JOIN r ON l.k = r.k"
        ).toPandas().sort_values(["k", "v", "w"]).reset_index(drop=True)
    assert outs["true"].equals(outs["false"])


def test_spill_join_masks_probe_partitions(monkeypatch):
    # force BOTH modes down the spill path (threshold below even the
    # pruned probe) and check the per-partition probe mask prunes rows
    monkeypatch.setenv("SAIL_EXECUTION__JOIN_SPILL_ROWS", "100")
    from sail_tpu.metrics import REGISTRY
    spark = _session()
    rng = np.random.default_rng(10)
    left = pd.DataFrame({"k": rng.integers(0, 500, 3000),
                         "v": rng.random(3000)})
    # sparse build keys: most probe rows miss, so the per-partition
    # is_in mask (not the scan push — the computed key below blocks
    # annotation) is what prunes
    right = pd.DataFrame({"k": np.arange(0, 500, 13),
                          "w": rng.random(len(np.arange(0, 500, 13)))})
    spark.createDataFrame(left).createOrReplaceTempView("l")
    spark.createDataFrame(right).createOrReplaceTempView("r")
    before = {(r["name"], r["attributes"]): r["value"]
              for r in REGISTRY.snapshot()}
    got = spark.sql(
        "SELECT ll.k2, ll.v, r.w FROM "
        "(SELECT k + 0 AS k2, v FROM l) ll "
        "JOIN r ON ll.k2 = r.k").toPandas()
    exp = left.assign(k2=left.k).merge(right, left_on="k2", right_on="k")
    assert len(got) == len(exp)
    after = {(r["name"], r["attributes"]): r["value"]
             for r in REGISTRY.snapshot()}
    key = ("execution.runtime_filter.rows_pruned", '{"site": "spill"}')
    assert after.get(key, 0) > before.get(key, 0)


# ---------------------------------------------------------------------------
# cluster-mode filter shipping
# ---------------------------------------------------------------------------

class TestClusterShipping:
    def _graph(self, spark, sql):
        from sail_tpu.exec import job_graph as jg
        return jg.split_job(_resolve(spark, sql), 2)

    def test_driver_computes_stage_filters(self):
        spark = _session()
        _register_star(spark)
        graph = self._graph(
            spark, "SELECT SUM(fact.v) FROM fact JOIN dim "
                   "ON fact.k = dim.id GROUP BY fact.k")
        assert graph is not None and graph.stage_filters
        entries = json.loads(next(iter(graph.stage_filters.values())))
        e = entries[0]
        assert e["name"] == "k"
        assert e["min"] == 0 and e["max"] == 39
        assert sorted(e["values"]) == list(range(40))

    def test_worker_attaches_runtime_predicates(self):
        from sail_tpu.exec import job_graph as jg
        spark = _session()
        _register_star(spark)
        graph = self._graph(
            spark, "SELECT SUM(fact.v) FROM fact JOIN dim "
                   "ON fact.k = dim.id GROUP BY fact.k")
        (sid, js), = graph.stage_filters.items()
        stage = [s for s in graph.stages if s.stage_id == sid][0]
        plan = jg.apply_task_runtime_filters(stage.plan, js)
        scans = [s for s in pn.walk_plan(plan)
                 if isinstance(s, pn.ScanExec) and s.runtime_predicates]
        assert scans
        fns = {c.fn for c in scans[0].runtime_predicates
               if isinstance(c, rx.RCall)}
        assert {">=", "<=", "rtf_member"} <= fns

    @pytest.mark.parametrize("env", ["SAIL_CLUSTER__RUNTIME_FILTERS",
                                     "SAIL_JOIN__RUNTIME_FILTER__ENABLED"])
    def test_gate_disables_shipping(self, monkeypatch, env):
        # both the cluster gate and the master switch must kill shipping
        monkeypatch.setenv(env, "0")
        spark = _session()
        _register_star(spark)
        graph = self._graph(
            spark, "SELECT SUM(fact.v) FROM fact JOIN dim "
                   "ON fact.k = dim.id GROUP BY fact.k")
        assert graph is not None and not graph.stage_filters

    def test_cluster_results_match_local(self):
        from sail_tpu.exec.cluster import LocalCluster
        spark = _session()
        _register_star(spark)
        sql = ("SELECT fact.k AS k, SUM(fact.v) AS s FROM fact "
               "JOIN dim ON fact.k = dim.id GROUP BY fact.k")
        local = spark.sql(sql).toPandas().sort_values("k") \
            .reset_index(drop=True)
        plan = _resolve(spark, sql)
        c = LocalCluster(num_workers=2)
        try:
            dist = c.run_job(plan, num_partitions=2).to_pandas() \
                .sort_values("k").reset_index(drop=True)
        finally:
            c.stop()
        assert len(dist) == len(local)
        np.testing.assert_allclose(dist.s.values, local.s.values)
