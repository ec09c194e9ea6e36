"""Vectorised TPC-H generator for the benchmark: Parquet for the server,
pandas frames for the plain reference, both from ``--seed``.

The schemas and value domains are those of the program's own
``benchmarks/tpch_data.generate_tpch`` (int64 keys, decimal(15,2)
money, date32 dates, strings), made without a Python loop over rows so
that SF1 costs seconds and SF10 lineitem about a minute:

- money is drawn as integer cents and laid out as decimal128 directly;
- text columns are dictionary arrays over small fixed pools (no query
  of the benchmark reads a column whose text is made up; ``assumed``
  in the configuration files says so);
- ``lineitem`` is made in chunks of ``ORDERS_PER_CHUNK`` orders, one
  Parquet file each, so SF10 never holds 60M x 16 columns at once;
- every seed gives the same row counts: lines per order are a
  seed-shuffled run of 1..7 in equal shares, so a table's capacity
  bucket, and with it the compiled program, does not depend on the
  seed. The values, and so every answer, do.

Keys are consistent across tables: ``o_custkey`` names a customer,
``l_orderkey`` an order (and ``l_shipdate`` follows its ``o_orderdate``),
``l_partkey``/``l_suppkey`` a ``partsupp`` row.
"""

from __future__ import annotations

import datetime
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = ("almond antique aquamarine azure beige bisque black blanched blue "
          "blush brown burlywood chartreuse chiffon chocolate coral "
          "cornflower cornsilk cream cyan dark deep dim dodger drab "
          "firebrick floral forest frosted gainsboro ghost gold").split()
COMMENT_WORDS = ("the of with regular final special express pending unusual "
                 "requests deposits packages accounts instructions "
                 "theodolites foxes ideas carefully slyly quickly blithely "
                 "furiously bold even silent daring Customer "
                 "Complaints").split()

_EPOCH = datetime.date(1970, 1, 1)
START_DAY = (datetime.date(1992, 1, 1) - _EPOCH).days
END_DAY = (datetime.date(1998, 12, 1) - _EPOCH).days
CUTOFF_DAY = (datetime.date(1995, 6, 17) - _EPOCH).days

#: orders behind one lineitem Parquet file (about 6M rows)
ORDERS_PER_CHUNK = 1_500_000
#: rows of the base tables at scale factor 1 (TPC-H spec, clause 4.2.5;
#: lineitem is four lines an order here, 6,001,215 in the spec)
BASE_ROWS = {"region": 5, "nation": 25, "supplier": 10_000,
             "customer": 150_000, "part": 200_000, "partsupp": 800_000,
             "orders": 1_500_000, "lineitem": 6_000_000}
_TABLE_IDS = {name: i for i, name in enumerate(BASE_ROWS)}


def table_rows(sf: float) -> dict:
    """Rows of every table at scale factor ``sf``; the same for every
    seed."""
    rows = {"region": 5, "nation": 25}
    for name in ("supplier", "customer", "part", "orders"):
        rows[name] = max(1, int(BASE_ROWS[name] * sf))
    rows["partsupp"] = rows["part"] * 4
    rows["lineitem"] = int(_lines_per(np.arange(rows["orders"])).sum())
    return rows


def _rng(seed: int, table: str, chunk: int = 0) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), _TABLE_IDS[table], chunk])


def _lines_per(order_index: np.ndarray) -> np.ndarray:
    return order_index % 7 + 1


# -- columns ----------------------------------------------------------------

class Col:
    """One generated column in both forms: Arrow for Parquet, and what
    the pandas reference reads (money as the float64 nearest the
    decimal's value, dates as datetime64, pooled strings as
    categoricals). The pandas form is made only when asked for: most
    columns are read by no statement."""

    __slots__ = ("arrow", "_frame")

    def __init__(self, arrow, frame=None):
        self.arrow, self._frame = arrow, frame

    @property
    def frame(self):
        return self._frame() if self._frame is not None else None


def int_col(values, kind=pa.int64()) -> Col:
    values = np.asarray(values)
    return Col(pa.array(values, type=kind),
               lambda: values.astype(np.int64))


def money_col(cents) -> Col:
    """decimal(15,2) from integer cents: the 128-bit little-endian
    two's complement laid out directly, no pass through float."""
    cents = np.ascontiguousarray(cents, dtype=np.int64)
    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0] = cents
    words[:, 1] = cents >> 63
    arrow = pa.Array.from_buffers(pa.decimal128(15, 2), len(cents),
                                  [None, pa.py_buffer(words)])
    return Col(arrow, lambda: cents / 100.0)


def date_col(days) -> Col:
    days = np.asarray(days, dtype=np.int32)
    return Col(pa.array(days, type=pa.int32()).cast(pa.date32()),
               lambda: days.astype("datetime64[D]").astype("datetime64[us]"))


def pool_col(codes, pool) -> Col:
    """A string column drawn from a pool of distinct strings: a
    dictionary array for Parquet (written as plain strings), and for
    pandas a categorical whose categories are in lexical order, so that
    it sorts and groups as the strings would, at a fraction of their
    cost."""
    codes = np.asarray(codes, dtype=np.int32)
    pool = list(pool)
    arrow = pa.DictionaryArray.from_arrays(pa.array(codes), pa.array(pool))

    def frame():
        import pandas as pd
        order = np.argsort(np.asarray(pool, dtype=object), kind="stable")
        rank = np.empty(len(pool), dtype=np.int32)
        rank[order] = np.arange(len(pool), dtype=np.int32)
        return pd.Categorical.from_codes(rank[codes],
                                         [pool[i] for i in order])

    return Col(arrow, frame)


def _comment_pool(n: int = 1024) -> list:
    rng = np.random.default_rng(7)
    words = np.asarray(COMMENT_WORDS, dtype=object)
    picks = rng.integers(0, len(words), (n, 6))
    counts = rng.integers(3, 7, n)
    return list(dict.fromkeys(" ".join(words[picks[i, :counts[i]]])
                              for i in range(n)))


COMMENTS = _comment_pool()


def comment_col(rng, n: int) -> Col:
    return pool_col(rng.integers(0, len(COMMENTS), n), COMMENTS)


def numbered_col(prefix: str, keys) -> Col:
    """``Supplier#000000123``-style names, through Arrow's own string
    kernels."""
    import pyarrow.compute as pc
    digits = pc.utf8_lpad(pa.array(np.asarray(keys)).cast(pa.string()), 9,
                          "0")
    arrow = pc.binary_join_element_wise(pa.scalar(prefix), digits,
                                        pa.scalar(""))
    return Col(arrow)


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice in cents (TPC-H spec 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def supp_of_part(partkey, j, n_supp):
    """The j-th supplier of a part (TPC-H spec 4.2.3, ps_suppkey)."""
    return (partkey + j * (n_supp // 4 + 1)) % n_supp + 1


# -- tables -----------------------------------------------------------------

def gen_region(seed, sf):
    rng = _rng(seed, "region")
    return {"r_regionkey": int_col(np.arange(5)),
            "r_name": pool_col(np.arange(5), REGIONS),
            "r_comment": comment_col(rng, 5)}


def gen_nation(seed, sf):
    rng = _rng(seed, "nation")
    return {"n_nationkey": int_col(np.arange(25)),
            "n_name": pool_col(np.arange(25), [n for n, _ in NATIONS]),
            "n_regionkey": int_col([r for _, r in NATIONS]),
            "n_comment": comment_col(rng, 25)}


def gen_supplier(seed, sf):
    rng = _rng(seed, "supplier")
    n = table_rows(sf)["supplier"]
    key = np.arange(1, n + 1)
    return {"s_suppkey": int_col(key),
            "s_name": numbered_col("Supplier#", key),
            "s_address": comment_col(rng, n),
            "s_nationkey": int_col(rng.integers(0, 25, n)),
            "s_phone": comment_col(rng, n),
            "s_acctbal": money_col(rng.integers(-99999, 1000000, n)),
            "s_comment": comment_col(rng, n)}


def gen_customer(seed, sf):
    rng = _rng(seed, "customer")
    n = table_rows(sf)["customer"]
    key = np.arange(1, n + 1)
    return {"c_custkey": int_col(key),
            "c_name": numbered_col("Customer#", key),
            "c_address": comment_col(rng, n),
            "c_nationkey": int_col(rng.integers(0, 25, n)),
            "c_phone": comment_col(rng, n),
            "c_acctbal": money_col(rng.integers(-99999, 1000000, n)),
            "c_mktsegment": pool_col(rng.integers(0, 5, n), SEGMENTS),
            "c_comment": comment_col(rng, n)}


def gen_part(seed, sf):
    rng = _rng(seed, "part")
    n = table_rows(sf)["part"]
    key = np.arange(1, n + 1)
    mfgr = rng.integers(1, 6, n)
    brand = mfgr * 10 + rng.integers(1, 6, n)
    names = [" ".join(COLORS[(i + k * 7) % len(COLORS)] for k in range(5))
             for i in range(len(COLORS))]
    return {"p_partkey": int_col(key),
            "p_name": pool_col(rng.integers(0, len(names), n), names),
            "p_mfgr": pool_col(mfgr - 1,
                               [f"Manufacturer#{m}" for m in range(1, 6)]),
            "p_brand": pool_col(brand - 11,
                                [f"Brand#{b}" for b in range(11, 56)]),
            "p_type": pool_col(rng.integers(0, len(TYPES), n), TYPES),
            "p_size": int_col(rng.integers(1, 51, n), pa.int32()),
            "p_container": pool_col(rng.integers(0, len(CONTAINERS), n),
                                    CONTAINERS),
            "p_retailprice": money_col(retail_cents(key)),
            "p_comment": comment_col(rng, n)}


def gen_partsupp(seed, sf):
    rng = _rng(seed, "partsupp")
    rows = table_rows(sf)
    part = np.repeat(np.arange(1, rows["part"] + 1), 4)
    j = np.tile(np.arange(4), rows["part"])
    n = len(part)
    return {"ps_partkey": int_col(part),
            "ps_suppkey": int_col(supp_of_part(part, j, rows["supplier"])),
            "ps_availqty": int_col(rng.integers(1, 10000, n), pa.int32()),
            "ps_supplycost": money_col(rng.integers(100, 100001, n)),
            "ps_comment": comment_col(rng, n)}


def _order_fields(seed, lo, hi):
    """What orders and lineitem share for the orders [lo, hi): key,
    date and lines per order. Drawn per chunk so that both tables see
    the same values whichever is made."""
    chunk = lo // ORDERS_PER_CHUNK
    rng = np.random.default_rng([abs(int(seed)), 99, chunk])
    index = np.arange(lo, hi)
    key = (index + 1) * 4 - 3            # sparse order keys, as dbgen's
    date = rng.integers(START_DAY, END_DAY - 151, hi - lo)
    lines = rng.permutation(_lines_per(index))
    return key, date, lines


def _order_chunks(sf):
    n = table_rows(sf)["orders"]
    return [(lo, min(lo + ORDERS_PER_CHUNK, n))
            for lo in range(0, n, ORDERS_PER_CHUNK)]


def gen_orders_chunk(seed, sf, lo, hi):
    rng = _rng(seed, "orders", lo // ORDERS_PER_CHUNK)
    key, date, _lines = _order_fields(seed, lo, hi)
    n = hi - lo
    n_cust = table_rows(sf)["customer"]
    cust = rng.integers(1, n_cust + 1, n)
    # a third of the customers place no order (spec 4.2.3): fold the
    # multiples of three onto their neighbour
    cust = np.where(cust % 3 == 0, np.maximum(cust - 1, 1), cust)
    return {"o_orderkey": int_col(key),
            "o_custkey": int_col(cust),
            "o_orderstatus": pool_col(rng.integers(0, 3, n),
                                      ["F", "O", "P"]),
            "o_totalprice": money_col(rng.integers(85000, 55000000, n)),
            "o_orderdate": date_col(date),
            "o_orderpriority": pool_col(rng.integers(0, 5, n), PRIORITIES),
            "o_clerk": numbered_col("Clerk#", rng.integers(1, 1001, n)),
            "o_shippriority": int_col(np.zeros(n, dtype=np.int32),
                                      pa.int32()),
            "o_comment": comment_col(rng, n)}


def gen_lineitem_chunk(seed, sf, lo, hi):
    rng = _rng(seed, "lineitem", lo // ORDERS_PER_CHUNK)
    rows = table_rows(sf)
    key, odate, lines = _order_fields(seed, lo, hi)
    n = int(lines.sum())
    first = np.cumsum(lines) - lines
    order = np.repeat(key, lines)
    odate = np.repeat(odate, lines)
    number = np.arange(n) - np.repeat(first, lines) + 1
    qty = rng.integers(1, 51, n)
    part = rng.integers(1, rows["part"] + 1, n)
    supp = supp_of_part(part, rng.integers(0, 4, n), rows["supplier"])
    ship = odate + rng.integers(1, 122, n)
    commit = odate + rng.integers(30, 92, n)
    receipt = ship + rng.integers(1, 31, n)
    flag = np.where(receipt <= CUTOFF_DAY, rng.integers(0, 2, n), 2)
    return {"l_orderkey": int_col(order),
            "l_partkey": int_col(part),
            "l_suppkey": int_col(supp),
            "l_linenumber": int_col(number, pa.int32()),
            "l_quantity": money_col(qty * 100),
            "l_extendedprice": money_col(qty * retail_cents(part)),
            "l_discount": money_col(rng.integers(0, 11, n)),
            "l_tax": money_col(rng.integers(0, 9, n)),
            "l_returnflag": pool_col(flag, ["R", "A", "N"]),
            "l_linestatus": pool_col((ship > CUTOFF_DAY).astype(np.int32),
                                     ["F", "O"]),
            "l_shipdate": date_col(ship),
            "l_commitdate": date_col(commit),
            "l_receiptdate": date_col(receipt),
            "l_shipinstruct": pool_col(rng.integers(0, 4, n), INSTRUCTS),
            "l_shipmode": pool_col(rng.integers(0, 7, n), SHIPMODES),
            "l_comment": comment_col(rng, n)}


_WHOLE = {"region": gen_region, "nation": gen_nation,
          "supplier": gen_supplier, "customer": gen_customer,
          "part": gen_part, "partsupp": gen_partsupp}
_CHUNKED = {"orders": gen_orders_chunk, "lineitem": gen_lineitem_chunk}
TABLES = tuple(_WHOLE) + tuple(_CHUNKED)


def table_parts(name: str, seed: int, sf: float):
    """The table as a list of thunks, one per Parquet file, each giving
    {column: Col}."""
    if name in _WHOLE:
        return [lambda: _WHOLE[name](seed, sf)]
    if name in _CHUNKED:
        return [lambda lo=lo, hi=hi: _CHUNKED[name](seed, sf, lo, hi)
                for lo, hi in _order_chunks(sf)]
    raise KeyError(f"no generator for table {name!r}; known: {TABLES}")


def generate_table(name: str, seed: int, sf: float) -> pa.Table:
    """The whole table as Arrow (tests; small scale factors)."""
    parts = [part() for part in table_parts(name, seed, sf)]
    return pa.concat_tables(
        pa.table({c: col.arrow for c, col in part.items()})
        for part in parts)


def _write_part(thunk, path: str, keep: list) -> tuple:
    cols = thunk()
    table = pa.table({c: col.arrow for c, col in cols.items()})
    pq.write_table(table, path, store_schema=False)
    # on disk before the window opens, not written back during it
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    kept = {c: cols[c].frame for c in keep if c in cols}
    missing = [c for c in keep if kept.get(c) is None]
    if missing:
        raise KeyError(f"{path}: the reference asks for {missing}, which "
                       f"the generator keeps no pandas form of")
    return table.num_rows, os.path.getsize(path), kept


def _concat(pieces: list):
    import pandas as pd
    if isinstance(pieces[0], pd.Categorical):
        from pandas.api.types import union_categoricals
        return pieces[0] if len(pieces) == 1 else \
            union_categoricals(pieces, sort_categories=False)
    return pieces[0] if len(pieces) == 1 else np.concatenate(pieces)


def write_tables(wanted: dict, seed: int, sf: float, out_dir: str,
                 workers: int = 6):
    """Generate ``wanted`` = {table: [columns the reference reads]} and
    write one directory of Parquet files per table under ``out_dir``.

    Returns ({table: dir}, {table: pandas frame of the kept columns},
    {table: rows}, parquet bytes). Parts are made and written by a few
    threads (numpy and Arrow release the interpreter lock), and only
    the kept columns outlive their part."""
    import pandas as pd
    jobs = []
    paths = {}
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for name, keep in wanted.items():
            paths[name] = os.path.join(out_dir, name)
            os.makedirs(paths[name], exist_ok=True)
            for i, thunk in enumerate(table_parts(name, seed, sf)):
                path = os.path.join(paths[name], f"part-{i:03d}.parquet")
                jobs.append((name, pool.submit(_write_part, thunk, path,
                                               list(keep))))
        done = [(name, job.result()) for name, job in jobs]
    frames, rows, nbytes = {}, {}, 0
    for name, keep in wanted.items():
        parts = [r for n, r in done if n == name]
        rows[name] = sum(p[0] for p in parts)
        nbytes += sum(p[1] for p in parts)
        frames[name] = pd.DataFrame(
            {c: _concat([p[2][c] for p in parts]) for c in keep},
            copy=False)
    return paths, frames, rows, nbytes
