"""The sorted GROUP BY (``ops/aggregate.py``, route ``sorted``) held to a
plain Python group-by: the same groups, in key order, the same values for
every aggregate, the same NULL, NaN and -0.0 semantics, and the first
``max_groups`` groups when there are more."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from sail_tpu.columnar.batch import Column
from sail_tpu.ops import aggregate as aggk
from sail_tpu.spec import data_type as dt

N = 600


def _keys(case, rng):
    """(key columns as (values, valid, type), sel, max_groups)."""
    sel = np.ones(N, dtype=bool)
    mg = N
    if case == "int64":
        # spread over the whole int64 range: no two keys pack into 64 bits
        pool = rng.integers(-(1 << 62), 1 << 62, 40)
        keys = [(rng.choice(pool, N), None, dt.LongType())]
    elif case == "two_int32":
        keys = [(rng.integers(-5, 5, N).astype(np.int32), None,
                 dt.IntegerType()),
                (rng.integers(0, 4, N).astype(np.int32), None,
                 dt.IntegerType())]
    elif case == "q3_shape":
        keys = [(rng.integers(0, 30, N) * 1_000_003, None, dt.LongType()),
                (rng.integers(9000, 9004, N).astype(np.int32), None,
                 dt.DateType()),
                (np.zeros(N, dtype=np.int32), None, dt.IntegerType())]
    elif case == "float_nan_zero":
        pool = np.array([0.0, -0.0, np.nan, -np.nan, 1.5, -2.25, np.inf])
        keys = [(rng.choice(pool, N), None, dt.DoubleType())]
    elif case == "null_keys":
        keys = [(rng.integers(0, 8, N), rng.random(N) > 0.25,
                 dt.LongType()),
                (rng.integers(0, 2, N).astype(np.int32),
                 rng.random(N) > 0.5, dt.IntegerType())]
    elif case == "dead_rows":
        keys = [(rng.integers(0, 50, N), None, dt.LongType())]
        sel = rng.random(N) > 0.4
    elif case == "overflow":
        keys = [(rng.integers(0, 100, N), rng.random(N) > 0.1,
                 dt.LongType())]
        mg = 37
    elif case == "few_slots_many_rows":
        keys = [(rng.integers(0, 5, N), None, dt.LongType())]
        sel = rng.random(N) > 0.5
        mg = 2 * N     # more slots than rows
    else:
        raise AssertionError(case)
    return keys, sel, mg


def _values(rng):
    vi = rng.integers(-(1 << 40), 1 << 40, N)
    vf = rng.normal(size=N)
    vb = rng.random(N) > 0.5
    return {
        "vi": (vi, rng.random(N) > 0.2, dt.LongType()),
        "vf": (vf, rng.random(N) > 0.2, dt.DoubleType()),
        "vb": (vb, rng.random(N) > 0.2, dt.BooleanType()),
        "vn": (vi, None, dt.LongType()),   # no validity at all
    }


AGGS = [("count", None, {}), ("count", "vi", {}), ("count", "vn", {}),
        ("sum", "vi", {"out_type": dt.LongType()}),
        ("sum", "vn", {"out_type": dt.LongType()}),
        ("sum", "vf", {"out_type": dt.DoubleType()}),
        ("min", "vi", {}), ("max", "vi", {}),
        ("min", "vf", {}), ("max", "vf", {}),
        ("min", "vb", {}), ("max", "vb", {}),
        ("first", "vi", {}), ("last", "vi", {}),
        ("first", "vf", {"ignore_nulls": False}),
        ("last", "vf", {"ignore_nulls": False}),
        ("first", "vn", {}), ("last", "vn", {"ignore_nulls": False}),
        ("bool_and", "vb", {}), ("bool_or", "vb", {})]


def _norm(x):
    """A float key as Spark groups it."""
    if isinstance(x, float):
        if math.isnan(x):
            return math.nan
        return x + 0.0
    return x


def _key_rank(t):
    """Sort order of a group key tuple: per key nulls last, NaN above
    every number."""
    out = []
    for x in t:
        if x is None:
            out.append((1, 0, 0))
        elif isinstance(x, float) and math.isnan(x):
            out.append((0, 1, 0))
        else:
            out.append((0, 0, x))
    return out


def _reference(keys, sel, vals, aggs):
    groups = {}
    order = []
    for i in range(N):
        if not sel[i]:
            continue
        t = tuple(None if (v is not None and not v[i]) else _norm(d[i].item())
                  for d, v, _ in keys)
        # NaN != NaN: one canonical tuple per group
        t = tuple("nan" if isinstance(x, float) and math.isnan(x) else x
                  for x in t)
        if t not in groups:
            groups[t] = []
            order.append(t)
        groups[t].append(i)
    back = {t: tuple(math.nan if x == "nan" else x for x in t) for t in order}
    ordered = sorted(order, key=lambda t: _key_rank(back[t]))
    results = []
    for t in ordered:
        rows = groups[t]
        res = []
        for fn, col, kw in aggs:
            if col is None:
                res.append(len(rows))
                continue
            d, v, _ = vals[col]
            ok = [i for i in rows if v is None or v[i]]
            if fn == "count":
                res.append(len(ok))
            elif fn == "sum":
                if not ok:
                    res.append(None)
                elif d.dtype.kind == "f":
                    res.append(math.fsum(d[i] for i in ok))
                else:
                    res.append(int(sum(int(d[i]) for i in ok)))
            elif fn in ("min", "max", "bool_and", "bool_or"):
                xs = [d[i].item() for i in ok]
                f = min if fn in ("min", "bool_and") else max
                res.append(f(xs) if xs else None)
            else:
                pool = ok if kw.get("ignore_nulls", True) else rows
                if not pool:
                    res.append(None)
                    continue
                i = pool[0] if fn == "first" else pool[-1]
                res.append(d[i].item() if v is None or v[i] else None)
        results.append((back[t], res))
    return results


def _run(keys, sel, vals, aggs, mg):
    kcols = [Column(jnp.asarray(d), None if v is None else jnp.asarray(v), t)
             for d, v, t in keys]
    cols = {k: Column(jnp.asarray(d), None if v is None else jnp.asarray(v),
                      t) for k, (d, v, t) in vals.items()}
    calls = [aggk.AggCall(fn, None if c is None else cols[c], **kw)
             for fn, c, kw in aggs]
    return aggk.group_aggregate(kcols, jnp.asarray(sel), mg, calls)


def _check(g, expected, mg, aggs):
    gsel = np.asarray(g.sel)
    live = min(len(expected), mg)
    assert gsel.shape == (mg,)
    assert gsel[:live].all() and not gsel[live:].any()
    assert int(g.num_groups) == len(expected)
    assert bool(g.overflow) == (len(expected) > mg)
    for slot, (key, res) in enumerate(expected[:live]):
        for kc, want in zip(g.keys, key):
            valid = True if kc.validity is None \
                else bool(np.asarray(kc.validity)[slot])
            got = np.asarray(kc.data)[slot].item()
            if want is None:
                assert not valid
            elif isinstance(want, float) and math.isnan(want):
                assert valid and math.isnan(got)
            else:
                assert valid and got == want
                if isinstance(want, float):
                    assert math.copysign(1, got) == math.copysign(1, want)
        for col, want, (fn, c, _) in zip(g.aggs, res, aggs):
            valid = True if col.validity is None \
                else bool(np.asarray(col.validity)[slot])
            got = np.asarray(col.data)[slot].item()
            where = (slot, fn, c)
            if want is None:
                assert not valid, where
            elif isinstance(want, float):
                assert valid, where
                if math.isnan(want):
                    assert math.isnan(got), where
                else:
                    assert got == pytest.approx(want, rel=1e-12,
                                                abs=1e-12), where
            else:
                assert valid and got == want, where


CASES = ["int64", "two_int32", "q3_shape", "float_nan_zero", "null_keys",
         "dead_rows", "overflow", "few_slots_many_rows"]


@pytest.mark.parametrize("seed", [3, 2_900_000_043])
@pytest.mark.parametrize("case", CASES)
def test_sorted_route_matches_a_python_group_by(case, seed):
    rng = np.random.default_rng(seed)
    keys, sel, mg = _keys(case, rng)
    vals = _values(rng)
    g = _run(keys, sel, vals, AGGS, mg)
    assert g.sort_operands > 1
    _check(g, _reference(keys, sel, vals, AGGS), mg, AGGS)


def test_float_sum_after_a_group_of_1e15_values():
    """A running sum over all rows, differenced per group, would lose the
    small group's digits to the large one before it; the sum restarts at
    each group instead."""
    keys = np.array([0, 0, 0, 1, 1, 1] + [2] * (N - 6), dtype=np.int64)
    vf = np.array([1e15, 3e15, -2.5e15, 0.1, 0.2, 0.3]
                  + [1e-3] * (N - 6))
    vals = {"vf": (vf, None, dt.DoubleType())}
    aggs = [("sum", "vf", {"out_type": dt.DoubleType()})]
    sel = np.ones(N, dtype=bool)
    g = _run([(keys, None, dt.LongType())], sel, vals, aggs, 8)
    got = np.asarray(g.aggs[0].data)[:3]
    assert got[1] == pytest.approx(0.6, rel=1e-15)
    assert got[2] == pytest.approx(1e-3 * (N - 6), rel=1e-12)
    _check(g, _reference([(keys, None, dt.LongType())], sel, vals, aggs),
           8, aggs)


def test_int_sums_stay_exact_past_2_to_the_53():
    """Integer (and decimal) sums are prefix sums differenced per group:
    exact, however large the groups before."""
    keys = np.repeat(np.arange(N // 6, dtype=np.int64), 6)
    vi = np.full(N, (1 << 60) + 7, dtype=np.int64)
    vi[1::2] = -(1 << 60) + 1
    vals = {"vi": (vi, None, dt.LongType())}
    aggs = [("sum", "vi", {"out_type": dt.LongType()})]
    sel = np.ones(N, dtype=bool)
    g = _run([(keys, None, dt.LongType())], sel, vals, aggs, N)
    assert (np.asarray(g.aggs[0].data)[: N // 6] == 3 * 8).all()


def test_sorted_aggregate_span_names_its_sort_operands():
    """A sorted GROUP BY's operator span says what its carried sort moved:
    the dead flag, the key and one array per distinct aggregate input
    (its validity beside it where it has one); a direct one says nothing
    of a sort."""
    import pyarrow as pa

    from sail_tpu import SparkSession, profiler

    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.cache.result.enabled": "false",
                          "spark.sail.execution.backend.force": "xla"})
    rng = np.random.default_rng(43)
    v = rng.integers(0, 1000, N)
    spark.createDataFrame(pa.table({
        "k": rng.integers(0, 1 << 40, N),
        "s": pa.array([str(x) for x in rng.integers(0, 3, N)]),
        "v": v,
        "w": pa.array(v, mask=rng.random(N) < 0.2),
    })).createOrReplaceTempView("sorted_agg_span")

    def agg_span(sql):
        spark.sql(sql).toArrow()
        spans = [s for s in profiler.last_profile().spans
                 if s.name in ("op.AggregateExec", "op.FusedAggregate")]
        assert len(spans) == 1
        return spans[0].attributes

    a = agg_span("SELECT k, sum(v), max(v), count(w) FROM sorted_agg_span "
                 "GROUP BY k")
    assert a["path"] == "sorted" and a["sort_operands"] == 5
    a = agg_span("SELECT s, sum(v) FROM sorted_agg_span GROUP BY s")
    assert a["path"] == "direct" and "sort_operands" not in a
