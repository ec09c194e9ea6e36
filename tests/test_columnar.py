"""Arrow ⇄ device round-trip and batch invariants."""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pytest

from sail_tpu.columnar import arrow_interop as ai
from sail_tpu.columnar.batch import round_capacity


def test_round_capacity_buckets():
    assert round_capacity(0) == 8
    assert round_capacity(8) == 8
    assert round_capacity(9) >= 9
    # bucketing: nearby sizes share a capacity (jit cache friendliness)
    caps = {round_capacity(n) for n in range(1000, 1100)}
    assert len(caps) <= 2


def test_arrow_roundtrip_fixed_width():
    t = pa.table({
        "i32": pa.array([1, 2, None, 4], type=pa.int32()),
        "i64": pa.array([10, None, 30, 40], type=pa.int64()),
        "f64": pa.array([1.5, 2.5, 3.5, None], type=pa.float64()),
        "b": pa.array([True, False, None, True]),
    })
    batch = ai.from_arrow(t)
    assert batch.capacity >= 4
    out = ai.to_arrow(batch)
    assert out.num_rows == 4
    assert out.column("i32").to_pylist() == [1, 2, None, 4]
    assert out.column("i64").to_pylist() == [10, None, 30, 40]
    assert out.column("f64").to_pylist() == [1.5, 2.5, 3.5, None]
    assert out.column("b").to_pylist() == [True, False, None, True]


def test_arrow_roundtrip_strings_dates_decimals():
    t = pa.table({
        "s": pa.array(["foo", "bar", None, "foo"]),
        "d": pa.array([datetime.date(2024, 1, 1), None,
                       datetime.date(1969, 12, 31), datetime.date(1970, 1, 2)]),
        "ts": pa.array([datetime.datetime(2024, 1, 1, 12, 0, 0), None,
                        datetime.datetime(1970, 1, 1), None],
                       type=pa.timestamp("us")),
        "dec": pa.array([decimal.Decimal("1.23"), decimal.Decimal("-4.50"),
                         None, decimal.Decimal("0.01")],
                        type=pa.decimal128(10, 2)),
    })
    batch = ai.from_arrow(t)
    # decimals upload as unscaled int64
    dec_col = batch.device.columns["dec"]
    np.testing.assert_array_equal(np.asarray(dec_col.data)[:2], [123, -450])
    out = ai.to_arrow(batch)
    assert out.column("s").to_pylist() == ["foo", "bar", None, "foo"]
    assert out.column("d").to_pylist() == [datetime.date(2024, 1, 1), None,
                                           datetime.date(1969, 12, 31),
                                           datetime.date(1970, 1, 2)]
    assert out.column("dec").to_pylist() == [decimal.Decimal("1.23"),
                                             decimal.Decimal("-4.50"), None,
                                             decimal.Decimal("0.01")]
    ts = out.column("ts").to_pylist()
    assert ts[0] == datetime.datetime(2024, 1, 1, 12, 0, 0)
    assert ts[1] is None


def _decoded(batch, name):
    return ai.to_arrow(batch).column(name).to_pylist()


_WIDE = [f"v{i:06d}" for i in range(ai.INTERN_MAX_VALUES + 1)]


@pytest.mark.parametrize("first,second,same", [
    # one value set in two first-appearance orders, nulls among them
    (pa.array(["x", "y", None, "z", "x"]),
     pa.array(["z", None, "y", "x", "z"]), True),
    (pa.array(["b", "a", None], pa.large_string()),
     pa.array(["a", None, "b"], pa.large_string()), True),
    (pa.array([b"\x02", b"\x01", b""], pa.binary()),
     pa.array([b"", b"\x02", b"\x01"], pa.binary()), True),
    # an already-dictionary-typed column is canonicalised too
    (pa.array(["y", "x", "z"]),
     pa.DictionaryArray.from_arrays(pa.array([0, None, 2, 1], pa.int8()),
                                    pa.array(["z", "y", "x"])), True),
    # another value set, another object
    (pa.array(["x", "y"]), pa.array(["x", "y", "w"]), False),
    # over the limit: left as the encoder made it, not interned
    (pa.array(_WIDE[1::2] + _WIDE[::2]), pa.array(_WIDE[::-1]), False),
], ids=["string", "large_string", "binary", "dictionary_typed",
        "other_values", "over_limit"])
def test_small_dictionaries_are_sorted_and_interned_by_content(
        first, second, same):
    ai.DICTIONARIES.clear()
    a = ai.from_arrow(pa.table({"s": first}))
    b = ai.from_arrow(pa.table({"s": second}))
    assert (a.dicts["s"] is b.dicts["s"]) is same
    for batch, arr in ((a, first), (b, second)):
        # codes remapped with their dictionary: every row decodes back
        assert _decoded(batch, "s") == arr.cast(
            arr.type.value_type if pa.types.is_dictionary(arr.type)
            else arr.type).to_pylist()
        values = batch.dicts["s"].to_pylist()
        assert (values == sorted(values)) is \
            (len(values) <= ai.INTERN_MAX_VALUES)


def test_intern_table_is_bounded_by_values_and_checks_content(monkeypatch):
    table = ai.DictionaryInterner(capacity_values=5)
    d1, d2 = pa.array(["a", "b", "c"]), pa.array(["d", "e", "f"])

    def interned(values):
        got, hit = table.intern(values)
        return (got is d1) + 2 * (got is d2), hit

    assert interned(d1) == (1, False)
    assert interned(pa.array(["a", "b", "c"])) == (1, True)
    assert interned(d2) == (2, False)  # 6 values held: d1 goes
    assert interned(pa.array(["a", "b", "c"])) == (0, False)
    # equal digests of unequal contents never hand out the stored array
    monkeypatch.setattr(ai, "_content_key", lambda values: ("k",))
    table.clear()
    assert interned(d1) == (1, False)
    assert interned(d2) == (2, False)
    assert interned(pa.array(["a", "b", "c"])) == (1, True)


def test_concurrent_conversions_share_one_dictionary():
    import sys
    import threading

    ai.DICTIONARIES.clear()
    orders = [["c", "a", "b"], ["b", "c", "a"], ["a", "b", "c"]] * 4
    got = [None] * len(orders)
    start = threading.Barrier(len(orders))

    def convert(i):
        start.wait(timeout=30)
        got[i] = ai.from_arrow(pa.table({"s": orders[i] * 50})).dicts["s"]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=convert, args=(i,))
                   for i in range(len(orders))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert all(d is got[0] for d in got)
    assert got[0].to_pylist() == ["a", "b", "c"]


def test_dictionary_unify_and_ranks():
    a = pa.array(["b", "a"]).dictionary_encode().dictionary
    b = pa.array(["c", "a"]).dictionary_encode().dictionary
    merged, ra, rb = ai.unify_dictionaries(a, b)
    vals = merged.to_pylist()
    assert vals[ra[0]] == "b" and vals[ra[1]] == "a"
    assert vals[rb[0]] == "c" and vals[rb[1]] == "a"
    ranks = ai.dictionary_ranks(merged)
    ordered = sorted(vals)
    for code, v in enumerate(vals):
        assert ordered[ranks[code]] == v
