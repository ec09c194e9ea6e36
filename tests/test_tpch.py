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

from tpch_oracle import ORACLES


#: the default route, where on a CPU the router hands chain-absorbing
#: aggregates to the C++ kernel, and the route the chip takes: every
#: stage a single-device XLA program through ``_compile_timed(jax.jit)``
#: (the force also settles the plan-level mesh gate)
ROUTES = {"default": {},
          "xla": {"spark.sail.execution.backend.force": "xla"}}


@pytest.fixture(scope="module")
def tpch_data():
    tables = generate_tpch(sf=0.005, seed=7)
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
    return tables, pdf


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
