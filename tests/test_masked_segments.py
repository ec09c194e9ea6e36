"""The TPU-only masked segment-reduction formulation must agree with the
scatter formulation (it is force-enabled here on CPU for coverage). It
lives in direct binning (``group_rows_direct``), whose bin ids are in row
order."""

import jax.numpy as jnp
import numpy as np
import pytest

from sail_tpu.columnar.batch import Column
from sail_tpu.ops import aggregate as aggk
from sail_tpu.spec import data_type as dt


@pytest.fixture()
def forced_masked(monkeypatch):
    monkeypatch.setattr(aggk, "_masked_max_segments", lambda: 128)


def _direct(keys, sel, domain, aggs):
    cols = [Column(jnp.asarray(keys), None, dt.LongType())]
    return aggk.group_aggregate(cols, jnp.asarray(sel), 0, aggs,
                                domains=[domain])


def test_masked_matches_scatter_all_aggs(forced_masked):
    rng = np.random.default_rng(0)
    n = 5000
    keys = rng.integers(0, 11, n)
    sel = rng.random(n) > 0.1
    vals = rng.normal(size=n)
    validity = rng.random(n) > 0.2
    col = Column(jnp.asarray(vals), jnp.asarray(validity), dt.DoubleType())
    g = _direct(keys, sel, 11,
                [aggk.AggCall("sum", col, dt.DoubleType()),
                 aggk.AggCall("min", col), aggk.AggCall("max", col),
                 aggk.AggCall("count", col)])

    got_sum, got_min, got_max, got_cnt = (np.asarray(c.data) for c in g.aggs)
    gsel = np.asarray(g.sel)
    gkeys = np.asarray(g.keys[0].data)

    import pandas as pd
    df = pd.DataFrame({"k": keys, "v": vals})[sel & validity]
    exp = df.groupby("k")["v"].agg(["sum", "min", "max", "count"])
    live = {int(k): i for i, k in enumerate(gkeys[gsel])}
    for k, row in exp.iterrows():
        i = live[int(k)]
        assert np.isclose(got_sum[gsel][i], row["sum"])
        assert np.isclose(got_min[gsel][i], row["min"])
        assert np.isclose(got_max[gsel][i], row["max"])
        assert got_cnt[gsel][i] == row["count"]


def test_masked_first_last_bool(forced_masked):
    keys = np.array([0, 0, 1, 1, 1, 2])
    sel = np.ones(6, dtype=bool)
    vals = np.array([True, False, False, False, True, True])
    col = Column(jnp.asarray(vals), None, dt.BooleanType())
    g = _direct(keys, sel, 3,
                [aggk.AggCall("first", col), aggk.AggCall("last", col),
                 aggk.AggCall("bool_or", col), aggk.AggCall("bool_and", col)])
    first, last, any_, all_ = (np.asarray(c.data) for c in g.aggs)
    assert first[:3].tolist() == [True, False, True]
    assert last[:3].tolist() == [False, True, True]
    assert any_[:3].tolist() == [True, True, True]
    assert all_[:3].tolist() == [False, False, True]
