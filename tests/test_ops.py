"""Device kernel tests: sort, aggregate, join — checked against numpy/pandas."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from sail_tpu.columnar import arrow_interop as ai
from sail_tpu.columnar.batch import Column, DeviceBatch
from sail_tpu.ops import aggregate as agg
from sail_tpu.ops import join as joinops
from sail_tpu.ops import sort as sortops
from sail_tpu.spec import data_type as dt

import jax
import jax.numpy as jnp


def make_batch(table: pa.Table):
    return ai.from_arrow(table).device


def live_rows(batch: DeviceBatch, names=None):
    sel = np.asarray(batch.sel)
    names = names or batch.names
    out = {}
    for n in names:
        c = batch.columns[n]
        data = np.asarray(c.data)[sel]
        if c.validity is not None:
            v = np.asarray(c.validity)[sel]
            data = [None if not vi else di for di, vi in zip(data.tolist(), v.tolist())]
        else:
            data = data.tolist()
        out[n] = data
    return out


class TestSort:
    def test_multi_key_with_nulls(self):
        t = pa.table({
            "a": pa.array([3, 1, None, 1, 2], type=pa.int64()),
            "b": pa.array([1.0, 2.0, 3.0, None, 5.0], type=pa.float64()),
        })
        b = make_batch(t)
        keys = [
            (b.columns["a"].data, b.columns["a"].validity, dt.LongType(), True, None),
            (b.columns["b"].data, b.columns["b"].validity, dt.DoubleType(), False, None),
        ]
        perm = sortops.lexsort_perm(keys, b.sel)
        out = sortops.take_batch(b, perm)
        rows = live_rows(out)
        # asc nulls first on a; desc nulls last on b
        assert rows["a"] == [None, 1, 1, 2, 3]
        assert rows["b"] == [3.0, 2.0, None, 5.0, 1.0]

    def test_limit_offset(self):
        t = pa.table({"x": pa.array(range(10), type=pa.int64())})
        b = make_batch(t)
        out = sortops.limit(b, 3, offset=2)
        assert live_rows(out)["x"] == [2, 3, 4]

    def test_dead_rows_sort_last(self):
        t = pa.table({"x": pa.array([5, 1, 3, 2], type=pa.int64())})
        b = make_batch(t)
        b = b.with_sel(b.sel & jnp.asarray(np.array([True, False, True, True] + [False] * (b.capacity - 4))))
        perm = sortops.lexsort_perm(
            [(b.columns["x"].data, None, dt.LongType(), True, None)], b.sel)
        out = sortops.take_batch(b, perm)
        assert live_rows(out)["x"] == [2, 3, 5]


class TestAggregate:
    def test_grouped_sum_count_min_max(self):
        rng = np.random.default_rng(0)
        n = 500
        keys = rng.integers(0, 7, n)
        vals = rng.normal(size=n)
        null_mask = rng.random(n) < 0.2
        t = pa.table({
            "k": pa.array(keys, type=pa.int64()),
            "v": pa.array([None if m else float(v) for v, m in zip(vals, null_mask)],
                          type=pa.float64()),
        })
        b = make_batch(t)
        v = b.columns["v"]
        g = agg.group_aggregate(
            [b.columns["k"]], b.sel, 16,
            [agg.AggCall("sum", v, dt.DoubleType()), agg.AggCall("count"),
             agg.AggCall("count", v), agg.AggCall("min", v),
             agg.AggCall("max", v)])
        kout, gsel = g.keys[0], g.sel
        s, c_star, c_v, mn, mx = g.aggs

        df = pd.DataFrame({"k": keys, "v": np.where(null_mask, np.nan, vals)})
        expected = df.groupby("k").agg(
            s=("v", lambda x: x.sum(min_count=1)),
            c_star=("v", "size"), c_v=("v", "count"),
            mn=("v", "min"), mx=("v", "max"))
        got = pd.DataFrame({
            "k": np.asarray(kout.data)[np.asarray(gsel)],
            "s": np.asarray(s.data)[np.asarray(gsel)],
            "c_star": np.asarray(c_star.data)[np.asarray(gsel)],
            "c_v": np.asarray(c_v.data)[np.asarray(gsel)],
            "mn": np.asarray(mn.data)[np.asarray(gsel)],
            "mx": np.asarray(mx.data)[np.asarray(gsel)],
        }).set_index("k").sort_index()
        assert got.index.tolist() == expected.index.tolist()
        np.testing.assert_allclose(got["s"], expected["s"], rtol=1e-12)
        np.testing.assert_array_equal(got["c_star"], expected["c_star"])
        np.testing.assert_array_equal(got["c_v"], expected["c_v"])
        np.testing.assert_allclose(got["mn"], expected["mn"])
        np.testing.assert_allclose(got["mx"], expected["mx"])

    def test_null_keys_form_a_group(self):
        t = pa.table({
            "k": pa.array([1, None, 1, None], type=pa.int64()),
            "v": pa.array([1, 2, 3, 4], type=pa.int64()),
        })
        b = make_batch(t)
        g = agg.group_aggregate([b.columns["k"]], b.sel, 8,
                                [agg.AggCall("sum", b.columns["v"],
                                             dt.LongType())])
        gsel = np.asarray(g.sel)
        assert gsel.sum() == 2
        sums = sorted(np.asarray(g.aggs[0].data)[gsel].tolist())
        assert sums == [4, 6]

    def test_global_aggregate_no_keys(self):
        t = pa.table({"v": pa.array([1, 2, None, 4], type=pa.int64())})
        b = make_batch(t)
        g = agg.group_aggregate([], b.sel, 1,
                                [agg.AggCall("sum", b.columns["v"],
                                             dt.LongType()),
                                 agg.AggCall("count", b.columns["v"])])
        s, c = g.aggs
        assert int(np.asarray(s.data)[0]) == 7
        assert int(np.asarray(c.data)[0]) == 3

    def test_multi_key_packed_and_unpacked(self):
        rng = np.random.default_rng(1)
        n = 300
        k1 = rng.integers(0, 5, n).astype(np.int32)
        k2 = rng.integers(0, 3, n).astype(np.int32)
        v = rng.integers(0, 100, n)
        t = pa.table({"k1": pa.array(k1), "k2": pa.array(k2),
                      "v": pa.array(v, type=pa.int64())})
        b = make_batch(t)
        g = agg.group_aggregate([b.columns["k1"], b.columns["k2"]], b.sel, 32,
                                [agg.AggCall("sum", b.columns["v"],
                                             dt.LongType())])
        gsel = np.asarray(g.sel)
        kk1 = np.asarray(g.keys[0].data)[gsel]
        kk2 = np.asarray(g.keys[1].data)[gsel]
        ss = np.asarray(g.aggs[0].data)[gsel]
        expected = pd.DataFrame({"k1": k1, "k2": k2, "v": v}).groupby(["k1", "k2"])["v"].sum()
        got = pd.Series(ss, index=pd.MultiIndex.from_arrays([kk1, kk2])).sort_index()
        np.testing.assert_array_equal(got.values, expected.values)


class TestJoin:
    def _join_df(self, left, right, on, how):
        return left.merge(right, on=on, how=how)

    def test_unique_inner_left(self):
        probe = pa.table({
            "k": pa.array([1, 2, 3, 99, None], type=pa.int64()),
            "p": pa.array([10, 20, 30, 40, 50], type=pa.int64()),
        })
        build = pa.table({
            "k2": pa.array([1, 2, 3, 4], type=pa.int64()),
            "b": pa.array(["a", "b", None, "d"]),
        })
        pb, bb = make_batch(probe), ai.from_arrow(build)
        bt = joinops.build_side([bb.device.columns["k2"]], bb.device.sel)
        ranges = joinops.probe_ranges(bt, [pb.columns["k"]], pb.sel)
        out = joinops.join_unique(bt, ranges, pb, bb.device, "inner", ["b"])
        rows = live_rows(out, ["k", "p", "b"])
        assert rows["k"] == [1, 2, 3]
        assert rows["b"] == [0, 1, None]  # dictionary codes
        out_l = joinops.join_unique(bt, ranges, pb, bb.device, "left", ["b"])
        rows_l = live_rows(out_l, ["k", "b"])
        assert rows_l["k"] == [1, 2, 3, 99, None]
        assert rows_l["b"] == [0, 1, None, None, None]

    def test_semi_anti(self):
        probe = pa.table({"k": pa.array([1, 2, 5], type=pa.int64())})
        build = pa.table({"k2": pa.array([2, 5, 7], type=pa.int64())})
        pb, bb = make_batch(probe), make_batch(build)
        bt = joinops.build_side([bb.columns["k2"]], bb.sel)
        r = joinops.probe_ranges(bt, [pb.columns["k"]], pb.sel)
        semi = joinops.join_unique(bt, r, pb, bb, "semi", [])
        anti = joinops.join_unique(bt, r, pb, bb, "anti", [])
        assert live_rows(semi)["k"] == [2, 5]
        assert live_rows(anti)["k"] == [1]

    def test_expand_many_to_many(self):
        probe = pa.table({
            "k": pa.array([1, 2, 3, None], type=pa.int64()),
            "p": pa.array([10, 20, 30, 40], type=pa.int64()),
        })
        build = pa.table({
            "k2": pa.array([1, 1, 2, 4, None], type=pa.int64()),
            "b": pa.array([100, 101, 200, 400, 500], type=pa.int64()),
        })
        pb, bb = make_batch(probe), make_batch(build)
        bt = joinops.build_side([bb.columns["k2"]], bb.sel)
        r = joinops.probe_ranges(bt, [pb.columns["k"]], pb.sel)
        assert bool(joinops.has_duplicate_build_keys(bt))
        total = int(joinops.join_output_count(r, pb.sel, "inner"))
        assert total == 3  # k=1 matches twice, k=2 once
        out = joinops.join_expand(bt, r, pb, bb, "inner", ["b"], out_capacity=8).batch
        rows = live_rows(out, ["k", "b"])
        assert sorted(zip(rows["k"], rows["b"])) == [(1, 100), (1, 101), (2, 200)]
        # left join: unmatched probe rows appear with null build cols
        total_l = int(joinops.join_output_count(r, pb.sel, "left"))
        assert total_l == 5
        out_l = joinops.join_expand(bt, r, pb, bb, "left", ["b"], out_capacity=8).batch
        rows_l = live_rows(out_l, ["k", "b"])
        assert sorted(zip([(-1 if k is None else k) for k in rows_l["k"]],
                          [(-1 if b is None else b) for b in rows_l["b"]])) == \
            [(-1, -1), (1, 100), (1, 101), (2, 200), (3, -1)]

    def test_build_matched_mask(self):
        probe = pa.table({"k": pa.array([1, 2], type=pa.int64())})
        build = pa.table({"k2": pa.array([1, 3, 2, 1], type=pa.int64())})
        pb, bb = make_batch(probe), make_batch(build)
        bt = joinops.build_side([bb.columns["k2"]], bb.sel)
        r = joinops.probe_ranges(bt, [pb.columns["k"]], pb.sel)
        matched = np.asarray(joinops.build_matched_mask(bt, r, pb.sel))
        np.testing.assert_array_equal(matched[:4], [True, False, True, True])


def _key_col(values, nulls=None):
    data = jnp.asarray(np.asarray(values, dtype=np.int64))
    validity = None if nulls is None else jnp.asarray(~np.asarray(nulls))
    return Column(data, validity, dt.LongType())


def _probe_case(name, rng):
    """(build key columns, build sel, probe key columns, probe sel) of
    one shape the match ranges must hold on."""
    def keys(n, lo, hi, width=1):
        return [rng.integers(lo, hi, n) for _ in range(width)]

    bn, pn = 48, 80
    bsel = psel = bnull = pnull = None
    if name == "duplicate_build":
        bk, pk = keys(bn, 0, 12), keys(pn, -2, 14)
    elif name == "unique_build":
        bk, pk = [rng.permutation(bn)], keys(pn, -5, bn + 5)
    elif name == "empty_build":
        bk, pk = keys(0, 0, 1), keys(pn, 0, 5)
    elif name == "every_build_row_dead":
        bk, pk = keys(bn, 0, 12), keys(pn, 0, 12)
        bsel = np.zeros(bn, dtype=bool)
    elif name == "null_and_dead_rows":
        bk, pk = keys(bn, 0, 12), keys(pn, 0, 12)
        bsel, psel = rng.random(bn) < 0.7, rng.random(pn) < 0.7
        bnull, pnull = rng.random(bn) < 0.2, rng.random(pn) < 0.2
    elif name == "key_max_beside_the_sentinel":
        # int64 -1 packs to the KEY_MAX bit pattern: live rows hold it in
        # the sorted prefix, dead rows are overwritten with it behind them
        bk, pk = keys(bn, -1, 3), keys(pn, -1, 3)
        bsel = rng.random(bn) < 0.5
    elif name == "all_keys_equal":
        bk, pk = [np.full(bn, 7)], [np.full(pn, 7)]
    elif name == "all_keys_equal_none_matching":
        bk, pk = [np.full(bn, 7)], [np.full(pn, 8)]
    elif name == "probe_larger_than_build":
        bk, pk = keys(5, 0, 6), keys(300, 0, 6)
    elif name == "probe_smaller_than_build":
        bk, pk = keys(300, 0, 400), keys(5, 0, 400)
    elif name == "one_probe_row":
        bk, pk = keys(bn, 0, 4), keys(1, 0, 4)
    elif name == "build_capacity_not_a_power_of_two":
        bk, pk = keys(7 * 16, 0, 40), keys(128, 0, 44)
        bsel = np.arange(7 * 16) < 100
    elif name == "extreme_keys":
        edge = np.array([np.iinfo(np.int64).min, -1, 0, 1,
                         np.iinfo(np.int64).max])
        bk, pk = [rng.choice(edge, bn)], [rng.choice(edge, pn)]
        bsel = rng.random(bn) < 0.8
    elif name == "hashed_three_columns":
        bk, pk = keys(bn, 0, 4, width=3), keys(pn, 0, 5, width=3)
        bsel, psel = rng.random(bn) < 0.8, rng.random(pn) < 0.8
        pnull = rng.random(pn) < 0.1
    else:
        raise AssertionError(name)
    bn, pn = len(bk[0]), len(pk[0])
    bsel = np.ones(bn, dtype=bool) if bsel is None else bsel
    psel = np.ones(pn, dtype=bool) if psel is None else psel
    return ([_key_col(k, bnull) for k in bk], jnp.asarray(bsel),
            [_key_col(k, pnull) for k in pk], jnp.asarray(psel))


PROBE_CASES = [
    "duplicate_build", "unique_build", "empty_build", "every_build_row_dead",
    "null_and_dead_rows", "key_max_beside_the_sentinel", "all_keys_equal",
    "all_keys_equal_none_matching", "probe_larger_than_build",
    "probe_smaller_than_build", "one_probe_row",
    "build_capacity_not_a_power_of_two", "extreme_keys",
    "hashed_three_columns",
]


@pytest.mark.parametrize("seed", [11, 2_900_000_029])
@pytest.mark.parametrize("case", PROBE_CASES)
def test_build_side_against_numpy_lexsort(case, seed):
    """``perm`` puts the usable build rows first in key order, equal keys
    in row order, the dead rows behind them in key order too;
    ``sorted_keys`` is the keys in that order with the dead suffix set to
    KEY_MAX; ``num_valid`` counts the usable rows. Held exactly: the order
    of two stable argsorts, by key and then by dead flag."""
    bcols, bsel, _, _ = _probe_case(case, np.random.default_rng(seed))
    bt = joinops.build_side(bcols, bsel)
    keys, usable, exact = joinops._join_keys(bcols, bsel, seed=bt.seed)
    keys, usable = np.asarray(keys), np.asarray(usable)
    n = keys.shape[0]
    perm = np.lexsort((np.arange(n), keys, ~usable))
    num_valid = int(usable.sum())
    sorted_keys = keys[perm]
    sorted_keys[num_valid:] = np.uint64(0xFFFFFFFFFFFFFFFF)
    got_perm, got_keys = np.asarray(bt.perm), np.asarray(bt.sorted_keys)
    assert got_perm.dtype == np.int32 and got_keys.dtype == np.uint64
    assert bt.exact == exact and int(bt.num_valid) == num_valid
    np.testing.assert_array_equal(got_perm, perm)
    np.testing.assert_array_equal(got_keys, sorted_keys)


@pytest.mark.parametrize("case", ["null_and_dead_rows", "hashed_three_columns"])
def test_build_side_lowers_to_one_sort_and_no_gather(case):
    """The build side's order comes from ONE sort that carries key and row
    number: a gather of the keys, the flags or the permutation through a
    sort permutation (24 ns a row at 32Mi rows on a v5e, PERF.md PR 31)
    must not come back unnoticed."""
    bcols, bsel, _, _ = _probe_case(case, np.random.default_rng(11))

    def fn(datas, validities, sel):
        cols = [Column(d, v, dt.LongType()) for d, v in zip(datas, validities)]
        bt = joinops.build_side(cols, sel)
        return bt.perm, bt.sorted_keys, bt.num_valid

    text = jax.jit(fn).lower([c.data for c in bcols],
                             [c.validity for c in bcols], bsel).as_text()
    assert text.count("stablehlo.sort") == 1, text
    assert "gather" not in text and "dynamic_slice" not in text, text


@pytest.mark.parametrize("shape", ["q18_one_int64_key",
                                   "q3_int64_date_int32_keys"])
def test_sorted_aggregate_lowers_to_two_sorts_and_no_gather(shape):
    """The sorted GROUP BY reduces in key order: one sort carries keys and
    values, one sort compacts the groups, and no gather or scatter runs
    over the rows in between (a scatter with unsorted indices is a serial
    loop on a v5e, and a gathered row costs several sorted ones)."""
    n = 1 << 16
    if shape == "q18_one_int64_key":
        keys = [(jnp.int64, dt.LongType())]
    else:
        keys = [(jnp.int64, dt.LongType()), (jnp.int32, dt.DateType()),
                (jnp.int32, dt.IntegerType())]

    def fn(kdatas, value, sel):
        cols = [Column(d, None, t) for d, (_, t) in zip(kdatas, keys)]
        g = agg.group_aggregate(
            cols, sel, n,
            [agg.AggCall("sum", Column(value, None, dt.LongType()),
                         dt.LongType())])
        return [k.data for k in g.keys], g.aggs[0].data, g.sel, g.overflow

    text = jax.jit(fn).lower(
        [jax.ShapeDtypeStruct((n,), d) for d, _ in keys],
        jax.ShapeDtypeStruct((n,), jnp.int64),
        jax.ShapeDtypeStruct((n,), jnp.bool_)).as_text()
    assert text.count("stablehlo.sort") == 2, text
    for op in ("gather", "scatter", "dynamic_slice", "dynamic_update_slice"):
        assert op not in text, op


@pytest.mark.parametrize("seed", [11, 2_900_000_029])
@pytest.mark.parametrize("case", PROBE_CASES)
def test_probe_ranges_against_numpy_searchsorted(case, seed):
    """``cnt`` is searchsorted right (clipped at ``num_valid``) minus
    left over the sorted build keys for every usable probe row and 0 for
    the others; ``lo`` is searchsorted left wherever ``cnt > 0``. On the
    hashed path a range also has to hold the probe row's true key."""
    bcols, bsel, pcols, psel = _probe_case(case, np.random.default_rng(seed))
    bt = joinops.build_side(bcols, bsel)
    assert bt.exact == (len(bcols) == 1)
    if not bt.exact:
        assert not bool(joinops.hash_ambiguous(bt, bcols))
    r = joinops.probe_ranges(bt, pcols, psel,
                             build_key_cols=None if bt.exact else bcols)
    pkeys, pusable, _ = joinops._join_keys(pcols, psel, seed=bt.seed)
    sorted_keys, pkeys = np.asarray(bt.sorted_keys), np.asarray(pkeys)
    pusable, num_valid = np.asarray(pusable), int(bt.num_valid)
    lo = np.searchsorted(sorted_keys, pkeys, side="left")
    hi = np.minimum(np.searchsorted(sorted_keys, pkeys, side="right"),
                    num_valid)
    cnt = np.where(pusable, np.maximum(hi - lo, 0), 0)
    if not bt.exact:
        # a probe key absent from the build can share a hash with a build
        # key only by accident; hold the count to the true keys instead
        build = np.stack([np.asarray(c.data) for c in bcols], axis=1)
        build = build[np.asarray(bsel)]
        probe = np.stack([np.asarray(c.data) for c in pcols], axis=1)
        true_cnt = (probe[:, None, :] == build[None, :, :]).all(-1).sum(1)
        cnt = np.where(pusable, true_cnt, 0)
    got_lo, got_cnt = np.asarray(r.lo), np.asarray(r.cnt)
    assert got_lo.dtype == np.int32 and got_cnt.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(r.usable), pusable)
    np.testing.assert_array_equal(got_cnt, cnt)
    np.testing.assert_array_equal(got_lo[cnt > 0], lo[cnt > 0])
    if not bt.exact and (cnt > 0).any():
        first = np.asarray(bt.perm)[got_lo[cnt > 0]]
        for bc, pc in zip(bcols, pcols):
            np.testing.assert_array_equal(np.asarray(bc.data)[first],
                                          np.asarray(pc.data)[cnt > 0])


@pytest.mark.parametrize("join_type", ["inner", "left"])
@pytest.mark.parametrize("seed", [11, 2_900_000_029])
@pytest.mark.parametrize("case", PROBE_CASES)
def test_join_expand_against_numpy_repeat(case, seed, join_type):
    """Each live probe row in order, repeated once per usable build row
    with its true key (in build row order), or once unmatched on a left
    join: ``probe_index``, ``build_index``, ``is_match`` and the columns
    are exact on the live slots, at a capacity of the total and above
    it, and no slot past the total is selected."""
    rng = np.random.default_rng(seed)
    bcols, bsel, pcols, psel = _probe_case(case, rng)
    if bcols[0].capacity == 0:
        # the engine hands an empty build over as a capacity of dead rows
        bcols = [_key_col(np.zeros(8)) for _ in bcols]
        bsel = jnp.zeros(8, dtype=bool)
    bt = joinops.build_side(bcols, bsel)
    r = joinops.probe_ranges(bt, pcols, psel,
                             build_key_cols=None if bt.exact else bcols)
    bn, pn = bcols[0].capacity, pcols[0].capacity
    bval = rng.random(bn) < 0.8
    probe = DeviceBatch({"k%d" % i: c for i, c in enumerate(pcols)}, psel)
    build = DeviceBatch({"b": Column(jnp.arange(bn, dtype=jnp.int64) * 5,
                                     jnp.asarray(bval), dt.LongType())},
                        bsel)

    def usable(cols, sel):
        ok = np.asarray(sel).copy()
        for c in cols:
            if c.validity is not None:
                ok &= np.asarray(c.validity)
        return ok, np.stack([np.asarray(c.data) for c in cols], axis=1)

    bok, bkeys = usable(bcols, bsel)
    pok, pkeys = usable(pcols, psel)
    matches = [np.flatnonzero(bok & (bkeys == pkeys[i]).all(1)) if pok[i]
               else np.zeros(0, dtype=np.int64) for i in range(pn)]
    live = np.asarray(psel)
    cnt = np.array([len(m) for m in matches])
    eff = np.where(live, cnt if join_type == "inner" else np.maximum(cnt, 1), 0)
    total = int(eff.sum())
    pi = np.repeat(np.arange(pn), eff)
    is_match = np.concatenate([np.arange(e) < c for e, c in zip(eff, cnt)])
    bidx = np.concatenate([m if len(m) else np.full(e, -1)
                           for m, e in zip(matches, eff)])
    assert int(joinops.join_output_count(r, psel, join_type)) == total
    for out_capacity in (max(total, 1), total + 13):
        res = joinops.join_expand(bt, r, probe, build, join_type, ["b"],
                                  out_capacity)
        sel = np.asarray(res.batch.sel)
        assert sel.shape == (out_capacity,)
        np.testing.assert_array_equal(sel, np.arange(out_capacity) < total)
        np.testing.assert_array_equal(np.asarray(res.probe_index)[:total], pi)
        np.testing.assert_array_equal(np.asarray(res.is_match)[:total],
                                      is_match)
        np.testing.assert_array_equal(
            np.asarray(res.build_index)[:total][is_match], bidx[is_match])
        for i, c in enumerate(pcols):
            got = res.batch.columns["k%d" % i]
            np.testing.assert_array_equal(np.asarray(got.data)[:total],
                                          np.asarray(c.data)[pi])
            if c.validity is not None:
                np.testing.assert_array_equal(
                    np.asarray(got.validity)[:total], np.asarray(c.validity)[pi])
        got = res.batch.columns["b"]
        np.testing.assert_array_equal(np.asarray(got.data)[:total][is_match],
                                      bidx[is_match] * 5)
        np.testing.assert_array_equal(np.asarray(got.validity)[:total],
                                      is_match & bval[np.maximum(bidx, 0)])


def test_join_expand_lowers_to_no_search_loop():
    """Each output slot's probe row comes from one scatter of every
    row's first slot and a running max, not from a binary search per
    output slot: ``log2(probe)`` dependent gathers over every slot were
    the largest device op of all four join cells (PERF.md PR 37)."""
    bcols, bsel, pcols, psel = _probe_case("duplicate_build",
                                           np.random.default_rng(11))
    bt = joinops.build_side(bcols, bsel)
    r = joinops.probe_ranges(bt, pcols, psel)
    total = int(joinops.join_output_count(r, psel, "inner"))

    def fn(bt_arrays, ranges, probe, build):
        perm, keys, num_valid = bt_arrays
        b = joinops.BuildTable(perm, keys, True, num_valid)
        return joinops.join_expand(b, joinops.MatchRanges(*ranges), probe,
                                   build, "inner", ["b"], total)

    probe = DeviceBatch({"k": pcols[0]}, psel)
    build = DeviceBatch({"b": bcols[0]}, bsel)
    text = jax.jit(fn).lower((bt.perm, bt.sorted_keys, bt.num_valid),
                             tuple(r), probe, build).as_text()
    assert "stablehlo.while" not in text, text
    assert "stablehlo.sort" not in text, text
    assert text.count('"stablehlo.scatter"') == 1, text


class TestReviewRegressions:
    """Regressions for the round-1 code-review findings."""

    def test_join_on_minus_one_key(self):
        # -1 as int64 key packs to the KEY_MAX bit pattern; must still match.
        probe = pa.table({"k": pa.array([-1, 2], type=pa.int64())})
        build = pa.table({"k2": pa.array([-1, 2], type=pa.int64()),
                          "b": pa.array([7, 8], type=pa.int64())})
        pb, bb = make_batch(probe), make_batch(build)
        bt = joinops.build_side([bb.columns["k2"]], bb.sel)
        r = joinops.probe_ranges(bt, [pb.columns["k"]], pb.sel)
        out = joinops.join_unique(bt, r, pb, bb, "inner", ["b"])
        rows = live_rows(out, ["k", "b"])
        assert sorted(zip(rows["k"], rows["b"])) == [(-1, 7), (2, 8)]
        assert not bool(joinops.has_duplicate_build_keys(bt))

    def test_join_duplicate_minus_one_detected(self):
        build = pa.table({"k2": pa.array([-1, -1], type=pa.int64())})
        bb = make_batch(build)
        bt = joinops.build_side([bb.columns["k2"]], bb.sel)
        assert bool(joinops.has_duplicate_build_keys(bt))

    def test_float_zero_sign_group_and_join(self):
        t = pa.table({"k": pa.array([0.0, -0.0, 1.0], type=pa.float64()),
                      "v": pa.array([1, 2, 4], type=pa.int64())})
        b = make_batch(t)
        g = agg.group_aggregate([b.columns["k"]], b.sel, 8,
                                [agg.AggCall("sum", b.columns["v"],
                                             dt.LongType())])
        gsel = np.asarray(g.sel)
        assert gsel.sum() == 2  # 0.0 and -0.0 merge
        assert sorted(np.asarray(g.aggs[0].data)[gsel].tolist()) == [3, 4]
        # join: -0.0 probe matches 0.0 build
        probe = make_batch(pa.table({"k": pa.array([-0.0], type=pa.float64())}))
        build = make_batch(pa.table({"k2": pa.array([0.0], type=pa.float64()),
                                     "b": pa.array([9], type=pa.int64())}))
        bt = joinops.build_side([build.columns["k2"]], build.sel)
        r = joinops.probe_ranges(bt, [probe.columns["k"]], probe.sel)
        out = joinops.join_unique(bt, r, probe, build, "inner", ["b"])
        assert live_rows(out, ["b"])["b"] == [9]

    def test_nan_groups_together(self):
        t = pa.table({"k": pa.array([float("nan"), float("nan"), 1.0], type=pa.float64()),
                      "v": pa.array([1, 2, 3], type=pa.int64())})
        b = make_batch(t)
        g = agg.group_aggregate([b.columns["k"]], b.sel, 8, [])
        assert int(np.asarray(g.num_groups)) == 2

    def test_group_overflow_detected(self):
        t = pa.table({"k": pa.array(list(range(40)), type=pa.int64()),
                      "v": pa.array([1] * 40, type=pa.int64())})
        b = make_batch(t)
        g = agg.group_aggregate([b.columns["k"]], b.sel, 32, [])
        assert bool(g.overflow)

    def test_hashed_multi_key_join(self):
        # three int64 keys -> not packable -> hashed path with verification
        rng = np.random.default_rng(3)
        bn = 50
        bk = [rng.integers(0, 10, bn).astype(np.int64) for _ in range(3)]
        probe_rows = 80
        pk = [rng.integers(0, 12, probe_rows).astype(np.int64) for _ in range(3)]
        build = pa.table({"a": pa.array(bk[0]), "b": pa.array(bk[1]),
                          "c": pa.array(bk[2]),
                          "val": pa.array(np.arange(bn), type=pa.int64())})
        probe = pa.table({"a": pa.array(pk[0]), "b": pa.array(pk[1]), "c": pa.array(pk[2])})
        pb, bb = make_batch(probe), make_batch(build)
        bkc = [bb.columns[n] for n in ("a", "b", "c")]
        pkc = [pb.columns[n] for n in ("a", "b", "c")]
        bt = joinops.build_side(bkc, bb.sel)
        assert not bt.exact
        assert not bool(joinops.hash_ambiguous(bt, bkc))
        r = joinops.probe_ranges(bt, pkc, pb.sel, build_key_cols=bkc)
        total = int(joinops.join_output_count(r, pb.sel, "inner"))
        out = joinops.join_expand(bt, r, pb, bb, "inner", ["val"],
                                  out_capacity=max(8, total)).batch
        got = live_rows(out, ["a", "b", "c", "val"])
        exp = pd.DataFrame({"a": pk[0], "b": pk[1], "c": pk[2]}).merge(
            pd.DataFrame({"a": bk[0], "b": bk[1], "c": bk[2], "val": np.arange(bn)}),
            on=["a", "b", "c"], how="inner")
        assert total == len(exp)
        assert sorted(zip(got["a"], got["b"], got["c"], got["val"])) == \
            sorted(zip(exp["a"], exp["b"], exp["c"], exp["val"]))

    def test_nan_keys_hashed_join_and_no_livelock(self):
        nan = float("nan")
        build = pa.table({"a": pa.array([nan, 2.0], type=pa.float64()),
                          "b": pa.array([1.0, 2.0], type=pa.float64()),
                          "c": pa.array([1.0, 2.0], type=pa.float64()),
                          "val": pa.array([7, 8], type=pa.int64())})
        probe = pa.table({"a": pa.array([nan, 2.0], type=pa.float64()),
                          "b": pa.array([1.0, 2.0], type=pa.float64()),
                          "c": pa.array([1.0, 2.0], type=pa.float64())})
        pb, bb = make_batch(probe), make_batch(build)
        bkc = [bb.columns[n] for n in ("a", "b", "c")]
        pkc = [pb.columns[n] for n in ("a", "b", "c")]
        bt = joinops.build_side(bkc, bb.sel)
        assert not bt.exact
        # two equal-NaN rows are duplicates, not ambiguity -> no seed livelock
        assert not bool(joinops.hash_ambiguous(bt, bkc))
        r = joinops.probe_ranges(bt, pkc, pb.sel, build_key_cols=bkc)
        assert int(joinops.join_output_count(r, pb.sel, "inner")) == 2

    def test_decimal_literal_precision(self):
        import decimal as _dec
        from sail_tpu.spec.expression import lit
        l = lit(_dec.Decimal("1E+2"))
        assert l.value.data_type.precision >= 3

    def test_decimal_download_roundtrip_large(self):
        import decimal as _dec
        n = 1000
        vals = [_dec.Decimal(i).scaleb(-2) for i in range(-500, 500)]
        t = pa.table({"d": pa.array(vals, type=pa.decimal128(12, 2))})
        hb = ai.from_arrow(t)
        out = ai.to_arrow(hb)
        assert out.column("d").to_pylist() == vals
