"""The benchmark's generator: the same seed gives the same tables,
another seed other values in the same number of rows, keys line up
across tables, and the schema is the one the configuration states."""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from bench_copy import ROOT, load_json

import sys
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402

SF = 0.01
BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def tables():
    return {name: datagen.generate_table(name, 7, SF)
            for name in datagen.TABLES}


@pytest.mark.parametrize("name", datagen.TABLES)
def test_same_seed_same_table(tables, name):
    assert datagen.generate_table(name, 7, SF).equals(tables[name])


@pytest.mark.parametrize("name", ["supplier", "customer", "part",
                                  "partsupp", "orders", "lineitem"])
def test_another_seed_other_values_same_rows(tables, name):
    other = datagen.generate_table(name, BIG_SEED, SF)
    assert other.num_rows == tables[name].num_rows
    assert not other.equals(tables[name])


@pytest.mark.parametrize("name", datagen.TABLES)
def test_row_counts_are_those_of_table_rows(tables, name):
    assert tables[name].num_rows == datagen.table_rows(SF)[name]


def test_lineitem_rows_at_sf1_and_sf10_without_generating():
    assert datagen.table_rows(1)["lineitem"] == 5_999_995
    assert datagen.table_rows(10)["lineitem"] == 59_999_997


@pytest.mark.parametrize("config", ["tpch-sf1-resident",
                                    "tpch-sf10-lineitem-stream"])
def test_schema_is_the_configurations(tables, config):
    doc = load_json(os.path.join(ROOT, "benchmark", "configs",
                                 config + ".json"))

    def kind(t):
        if pa.types.is_decimal(t):
            return f"decimal({t.precision},{t.scale})"
        if pa.types.is_dictionary(t) or pa.types.is_string(t):
            return "string"
        if pa.types.is_date(t):
            return "date"
        return str(t)

    for table in doc["tables"]:
        got = {f.name: kind(f.type) for f in tables[table].schema}
        assert got == doc["schema"][table], table
        assert list(got) == list(doc["schema"][table]), table


def col(table, name):
    c = table.column(name)
    if pa.types.is_decimal(c.type):
        c = c.cast(pa.float64())
    return c.to_numpy(zero_copy_only=False)


def test_keys_are_consistent_across_tables(tables):
    rows = datagen.table_rows(SF)
    o, li, ps = tables["orders"], tables["lineitem"], tables["partsupp"]
    assert set(col(li, "l_orderkey")) == set(col(o, "o_orderkey"))
    cust = col(o, "o_custkey")
    assert cust.min() >= 1 and cust.max() <= rows["customer"]
    assert not (cust % 3 == 0).any()
    supp = col(li, "l_suppkey")
    assert supp.min() >= 1 and supp.max() <= rows["supplier"]
    part = col(li, "l_partkey")
    assert part.min() >= 1 and part.max() <= rows["part"]
    pairs = set(zip(col(ps, "ps_partkey"), col(ps, "ps_suppkey")))
    assert set(zip(part, supp)) <= pairs
    assert set(col(tables["nation"], "n_regionkey")) \
        <= set(col(tables["region"], "r_regionkey"))
    for t, c in (("supplier", "s_nationkey"), ("customer", "c_nationkey")):
        assert set(col(tables[t], c)) <= set(range(25))


def test_lineitem_follows_its_order(tables):
    o, li = tables["orders"], tables["lineitem"]
    odate = dict(zip(col(o, "o_orderkey"),
                     o.column("o_orderdate").cast(pa.int32()).to_numpy()))
    ship = li.column("l_shipdate").cast(pa.int32()).to_numpy()
    delta = ship - np.array([odate[k] for k in col(li, "l_orderkey")])
    assert delta.min() >= 1 and delta.max() <= 121
    number = col(li, "l_linenumber")
    first = np.flatnonzero(number == 1)
    assert (np.diff(np.append(first, len(number))) <= 7).all()
    price = np.round(col(li, "l_extendedprice") * 100).astype(np.int64)
    qty = np.round(col(li, "l_quantity")).astype(np.int64)
    assert (price == qty * datagen.retail_cents(
        col(li, "l_partkey").astype(np.int64))).all()


def test_money_is_exact_and_the_frame_is_its_nearest_double():
    cents = np.array([-99999, -1, 0, 1, 5, 123456789012], dtype=np.int64)
    c = datagen.money_col(cents)
    assert c.arrow.type == pa.decimal128(15, 2)
    assert [int(v.as_py() * 100) for v in c.arrow] == list(cents)
    assert list(c.frame) == [float(v.as_py()) for v in c.arrow]


def test_write_tables_writes_parquet_and_keeps_the_reference_columns(
        tmp_path):
    import pyarrow.parquet as pq
    wanted = {"lineitem": ["l_quantity", "l_shipdate", "l_returnflag"],
              "nation": ["n_name"]}
    paths, frames, rows, nbytes = datagen.write_tables(
        wanted, 3, SF, str(tmp_path))
    assert rows == {"lineitem": datagen.table_rows(SF)["lineitem"],
                    "nation": 25}
    assert nbytes > 0
    back = pq.read_table(paths["lineitem"])
    assert back.num_rows == rows["lineitem"]
    made = datagen.generate_table("lineitem", 3, SF)
    assert back.column("l_quantity").equals(made.column("l_quantity"))
    # pooled strings are written as plain strings
    assert back.schema.field("l_returnflag").type == pa.string()
    assert back.column("l_returnflag").to_pylist() == \
        made.column("l_returnflag").to_pylist()
    assert list(frames["lineitem"].columns) == wanted["lineitem"]
    assert frames["lineitem"]["l_quantity"].dtype == np.float64
    assert frames["lineitem"]["l_shipdate"].dtype.kind == "M"
    assert (frames["lineitem"]["l_quantity"].to_numpy()
            == back.column("l_quantity").cast(pa.float64()).to_numpy()).all()


def test_a_column_with_no_pandas_form_is_refused(tmp_path):
    with pytest.raises(KeyError):
        datagen.write_tables({"supplier": ["s_name"]}, 3, SF, str(tmp_path))


def test_an_unknown_table_is_refused():
    with pytest.raises(KeyError):
        datagen.table_parts("warehouse", 0, SF)
