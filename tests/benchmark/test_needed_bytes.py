"""The configurations' needed-bytes tables (the numerator of
``scan_hbm_roofline``) against the generator's row counts and the
statements' column lists."""

import os
import sys

import pytest

from bench_copy import ROOT, load_json

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CASES = [(c, q) for c in ("tpch-sf1-resident", "tpch-sf10-lineitem-stream")
         for q in load_json(os.path.join(BENCH, "configs", c + ".json"))
         ["needed_bytes"]]


@pytest.mark.parametrize("config,query", CASES)
def test_needed_bytes_are_rows_times_logical_widths(config, query):
    doc = load_json(os.path.join(BENCH, "configs", config + ".json"))
    reads = load_json(os.path.join(BENCH, "queries", query + ".json"))["reads"]
    widths = doc["logical_widths_bytes"]
    nominal = sum(doc["rows"][t] * sum(widths[doc["schema"][t][c]]
                                       for c in cols)
                  for t, cols in reads.items())
    assert doc["needed_bytes"][query] == nominal
    # the generator's own rows are the nominal ones to a part in 10^5
    actual = datagen.table_rows(doc["scale_factor"])
    generated = sum(actual[t] * sum(widths[doc["schema"][t][c]]
                                    for c in cols)
                    for t, cols in reads.items())
    assert abs(generated - nominal) <= 1e-5 * nominal


@pytest.mark.parametrize("config", ["tpch-sf1-resident",
                                    "tpch-sf10-lineitem-stream"])
def test_configuration_rows_are_the_generators(config):
    doc = load_json(os.path.join(BENCH, "configs", config + ".json"))
    actual = datagen.table_rows(doc["scale_factor"])
    for table, rows in doc["rows"].items():
        assert abs(actual[table] - rows) <= 1e-5 * rows, table


def test_the_issues_two_figures():
    doc = load_json(os.path.join(BENCH, "configs", "tpch-sf1-resident.json"))
    assert doc["needed_bytes"]["tpch-q1"] == 264_000_000
    assert doc["needed_bytes"]["tpch-q6"] == 168_000_000
