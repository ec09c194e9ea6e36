"""ClickBench: the 43-query web-analytics suite over a synthetic hits
table.

Reference role: python/pysail/data/clickbench/queries.sql +
tests/spark/test_clickbench.py (snapshot-tested there). The real dataset
is 100M rows of ClickHouse web logs; this generator produces a
schema-compatible synthetic table at any scale with the high-cardinality
string columns (URL, Title, SearchPhrase, Referer) that make the suite a
stress test for string-heavy execution.
"""

from __future__ import annotations

import datetime
import os
from typing import Dict, List

import numpy as np

QUERIES_PATH = os.path.join(os.path.dirname(__file__), "data",
                            "clickbench_queries.sql")


def load_queries() -> List[str]:
    with open(QUERIES_PATH, "r", encoding="utf-8") as f:
        text = f.read()
    return [q.strip() for q in text.split(";") if q.strip()]


def generate_hits(n_rows: int = 100_000, seed: int = 0):
    """Synthetic hits table covering every column the 43 queries touch."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    n = n_rows

    # user/session shape: Zipf-ish heavy hitters, many singletons
    user_pool = rng.integers(1, max(n // 3, 10), n).astype(np.uint64)
    user_id = (user_pool * np.uint64(2_654_435_761)
               % np.uint64(1 << 62)).astype(np.int64)

    epoch = datetime.date(1970, 1, 1)
    d0 = (datetime.date(2013, 7, 1) - epoch).days
    event_date = (d0 + rng.integers(0, 31, n)).astype("datetime64[D]")
    event_time = (event_date.astype("datetime64[s]")
                  + rng.integers(0, 86400, n).astype("timedelta64[s]"))

    phrases = np.array(
        ["", "", "", "", "", "", "",  # most hits have no search phrase
         "weather", "news today", "cat videos", "python tutorial",
         "cheap flights", "karelia wood", "holiday photos"])
    search_phrase = phrases[rng.integers(0, len(phrases), n)]

    # near-unique URLs: the high-cardinality string cliff the engine must
    # survive
    host_ids = rng.integers(0, 500, n)
    page_ids = rng.integers(0, max(n // 2, 10), n)
    url = np.char.add(
        np.char.add("http://site", host_ids.astype(str)),
        np.char.add(".example/page?id=", page_ids.astype(str)))
    referer = np.where(rng.random(n) < 0.4, "",
                       np.char.add("http://ref", host_ids.astype(str)))
    title = np.char.add("Page title ", rng.integers(0, max(n // 4, 10),
                                                    n).astype(str))
    mobile_models = np.array(["", "", "", "iPhone", "Galaxy S4", "Nexus 4",
                              "Lumia 920"])

    def u8(hi):
        return rng.integers(0, hi, n).astype(np.int16)

    table = pa.table({
        "WatchID": pa.array(rng.integers(1, 1 << 62, n), type=pa.int64()),
        "UserID": pa.array(user_id, type=pa.int64()),
        "CounterID": pa.array(rng.integers(1, 10_000, n), type=pa.int32()),
        "ClientIP": pa.array(rng.integers(0, 1 << 31, n), type=pa.int64()),
        "RegionID": pa.array(rng.integers(1, 6_000, n), type=pa.int32()),
        "AdvEngineID": pa.array(
            np.where(rng.random(n) < 0.95, 0,
                     rng.integers(1, 60, n)).astype(np.int16),
            type=pa.int16()),
        "SearchEngineID": pa.array(
            np.where(search_phrase == "", 0,
                     rng.integers(1, 100, n)).astype(np.int16),
            type=pa.int16()),
        "SearchPhrase": pa.array(search_phrase),
        "MobilePhone": pa.array(u8(8), type=pa.int16()),
        "MobilePhoneModel": pa.array(
            mobile_models[rng.integers(0, len(mobile_models), n)]),
        "EventDate": pa.array(event_date),
        "EventTime": pa.array(event_time),
        "ResolutionWidth": pa.array(
            rng.choice(np.array([0, 1024, 1280, 1366, 1440, 1536, 1600,
                                 1920], dtype=np.int32), n),
            type=pa.int32()),
        "WindowClientWidth": pa.array(rng.integers(0, 2000, n),
                                      type=pa.int32()),
        "WindowClientHeight": pa.array(rng.integers(0, 1200, n),
                                       type=pa.int32()),
        "IsRefresh": pa.array((rng.random(n) < 0.1).astype(np.int16),
                              type=pa.int16()),
        "IsLink": pa.array((rng.random(n) < 0.2).astype(np.int16),
                           type=pa.int16()),
        "IsDownload": pa.array((rng.random(n) < 0.02).astype(np.int16),
                               type=pa.int16()),
        "DontCountHits": pa.array((rng.random(n) < 0.05).astype(np.int16),
                                  type=pa.int16()),
        "TraficSourceID": pa.array(rng.integers(-1, 10, n).astype(np.int16),
                                   type=pa.int16()),
        "Title": pa.array(title),
        "URL": pa.array(url),
        "Referer": pa.array(referer),
        "URLHash": pa.array(
            rng.integers(-(1 << 62), 1 << 62, n), type=pa.int64()),
        "RefererHash": pa.array(
            rng.integers(-(1 << 62), 1 << 62, n), type=pa.int64()),
    })
    return table


def register_hits(spark, n_rows: int = 100_000, seed: int = 0):
    table = generate_hits(n_rows, seed)
    spark.createDataFrame(table).createOrReplaceTempView("hits")
    return table
