"""TPC-H end-to-end correctness: engine vs pandas oracle on generated data.

Mirrors the reference's snapshot-tested TPC-H suite
(python/pysail/tests/spark/test_tpch.py — SURVEY.md §4 tier 3), with a
pandas oracle instead of stored snapshots.
"""

import datetime
import decimal

import numpy as np
import pandas as pd
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.benchmarks.tpch_data import generate_tpch
from sail_tpu.benchmarks.tpch_queries import QUERIES
from sail_tpu.exec.local import clear_caches
from sail_tpu.plan.join_reorder import clear_observed_rows

from tpch_oracle import ORACLES


#: the default route, where on a CPU the router hands chain-absorbing
#: aggregates to the C++ kernel, and the route the chip takes: every
#: stage a single-device XLA program through ``_compile_timed(jax.jit)``
#: (the force also settles the plan-level mesh gate)
ROUTES = {"default": {},
          "xla": {"spark.sail.execution.backend.force": "xla"}}


def _oracle_frames(tables):
    pdf = {}
    for name, table in tables.items():
        df = table.to_pandas()
        # decimals → float for the oracle
        for c in df.columns:
            if df[c].dtype == object and len(df) and \
                    isinstance(df[c].iloc[0], decimal.Decimal):
                df[c] = df[c].astype(np.float64)
            if df[c].dtype == object and len(df) and \
                    isinstance(df[c].iloc[0], datetime.date):
                df[c] = pd.to_datetime(df[c])
        pdf[name] = df
    return pdf


@pytest.fixture(scope="module")
def tpch_data():
    tables = generate_tpch(sf=0.005, seed=7)
    return tables, _oracle_frames(tables)


@pytest.fixture(scope="module", params=list(ROUTES))
def tpch(request, tpch_data):
    tables, pdf = tpch_data
    spark = SparkSession(dict(ROUTES[request.param]))
    for name, table in tables.items():
        spark.createDataFrame(table).createOrReplaceTempView(name)
    return spark, pdf, request.param


def _normalize(df: pd.DataFrame) -> pd.DataFrame:
    out = df.copy()
    out.columns = [f"c{i}" for i in range(len(out.columns))]
    for c in out.columns:
        s = out[c]
        if s.dtype == object and len(s):
            first = next((v for v in s if v is not None), None)
            if first is None:  # all-NULL column (e.g. SUM over zero rows)
                out[c] = pd.Series([np.nan] * len(s), dtype=np.float64)
            elif isinstance(first, decimal.Decimal):
                out[c] = s.astype(np.float64)
            elif isinstance(first, datetime.date):
                out[c] = pd.to_datetime(s)
        if str(out[c].dtype).startswith("datetime64"):
            out[c] = pd.to_datetime(out[c]).dt.normalize()
            out[c] = out[c].astype("datetime64[us]")
        if out[c].dtype.kind in "iu":
            out[c] = out[c].astype(np.int64)
        if out[c].dtype.kind == "f":
            out[c] = out[c].astype(np.float64).round(4)
    return out.reset_index(drop=True)


def _compare(got: pd.DataFrame, exp: pd.DataFrame, q: int, ordered: bool):
    got_n, exp_n = _normalize(got), _normalize(exp)
    assert len(got_n) == len(exp_n), \
        f"Q{q}: row count {len(got_n)} != {len(exp_n)}"
    if not ordered:
        cols = list(got_n.columns)
        got_n = got_n.sort_values(cols).reset_index(drop=True)
        exp_n = exp_n.sort_values(cols).reset_index(drop=True)
    for c in got_n.columns:
        g, e = got_n[c], exp_n[c]
        if g.dtype.kind == "f":
            both_nan = g.isna() & e.isna()
            close = np.isclose(g.fillna(0), e.fillna(0), rtol=1e-6, atol=1e-4)
            assert (both_nan | close).all(), \
                f"Q{q} col {c}: {g[~(both_nan | close)].head()} vs " \
                f"{e[~(both_nan | close)].head()}"
        else:
            eq = (g == e) | (g.isna() & e.isna())
            assert eq.all(), f"Q{q} col {c}:\n{g[~eq].head()}\nvs\n{e[~eq].head()}"


# Q2/Q15 use ties (min/max) where row sets can differ only in order of
# equal keys; all queries here have deterministic output given sorting.
_UNORDERED = {2, 11, 13, 16, 18, 21}  # compare as sets (ties in sort keys)


@pytest.mark.parametrize("q", list(range(1, 23)))
def test_tpch_query(tpch, q):
    spark, pdf, route = tpch
    got = spark.sql(QUERIES[q]).toPandas()
    exp = ORACLES[q](pdf)
    _compare(got, exp, q, ordered=q not in _UNORDERED)
    if route == "xla":
        routes = profiler.last_profile().backend_routes
        assert routes and all(r["backend"] == "xla" for r in routes), \
            f"Q{q}: {routes}"


@pytest.fixture(scope="module")
def q18_sf01():
    """Q18's three tables at SF0.1: its HAVING filter keeps a handful of
    the 150,000 orders, at the aggregate's capacity (163,840 rows, over
    the 131,072 above which no key list used to leave the device)."""
    tables = {name: table for name, table in
              generate_tpch(sf=0.1, seed=18).items()
              if name in ("customer", "orders", "lineitem")}
    return tables, _oracle_frames(tables)


@pytest.mark.parametrize("route", list(ROUTES))
def test_q18s_key_list_reaches_the_orders_and_lineitem_scans(q18_sf01,
                                                             route):
    tables, pdf = q18_sf01
    clear_caches()
    # the row counts an earlier run's scans observed steer which side
    # of a join runs first: start from none
    clear_observed_rows()
    spark = SparkSession({**ROUTES[route],
                          "spark.sail.cache.result.enabled": "false"})
    for name, table in tables.items():
        spark.createDataFrame(table).createOrReplaceTempView(name)
    got = spark.sql(QUERIES[18]).toPandas()
    exp = ORACLES[18](pdf)
    assert 1 <= len(exp) < 100          # one row per qualifying order
    _compare(got, exp, 18, ordered=False)
    if route != "xla":
        return
    spans = profiler.last_profile().spans
    joins = [s.attributes for s in spans if s.name == "op.JoinExec"]
    semi = max(joins, key=lambda a: a["build_capacity"])
    assert semi["build_capacity"] > 131_072
    # the semi join's list goes to orders, and the orders it keeps list
    # their keys on to lineitem
    listed = [a for a in joins if a.get("rtf_listed")]
    assert [a["rtf_list_keys"] for a in listed] == [len(exp)] * 2
    pruned = [s.attributes for s in spans if s.name == "op.ScanExec"
              and s.attributes["runtime_conjuncts"]]
    li = pdf["lineitem"]
    assert sorted(a["rows"] for a in pruned) == sorted(
        [len(exp), int(li.l_orderkey.isin(exp.o_orderkey).sum())])
    # a pruned scan pins its own capacity bucket: the subquery's scan of
    # all of lineitem does not pad the join's few hundred rows to 655,360
    assert all(a["capacity"] <= 1024 for a in pruned)
