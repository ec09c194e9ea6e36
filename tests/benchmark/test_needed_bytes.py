"""``needed_bytes.needed_bytes`` (the numerator of ``scan_hbm_roofline``)
over every pair of a configuration and a query file whose tables the
configuration has: rows x logical widths by hand, against the
generator's row counts, the seven figures earlier PRs wrote into the
configurations, and a statement added as a file and nothing else."""

import os
import sys
import types

import pytest

import bench_copy
from bench_copy import ROOT, load_json, statements_of

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402
from needed_bytes import needed_bytes  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


def config_doc(name):
    return load_json(os.path.join(BENCH, "configs", name + ".json"))


def query_doc(name):
    return load_json(os.path.join(BENCH, "queries", name + ".json"))


CASES = [(c, q) for c in CONFIGS for q in statements_of(config_doc(c))]


def test_the_cases_hold_every_pair_the_configurations_can_serve():
    """The ten pairs of PR 33's files; later files add to them."""
    four = ["tpch-q1", "tpch-q3", "tpch-q5", "tpch-q6"]
    assert set(CASES) >= set(
        [("tpch-sf1-resident", q) for q in four]
        + [("tpch-sf10-lineitem-stream", q) for q in ("tpch-q1", "tpch-q6")]
        + [("tpch-sf10-resident", q) for q in four])
    assert ("tpch-sf10-lineitem-stream", "tpch-q3") not in CASES


@pytest.mark.parametrize("config,query", CASES)
def test_needed_bytes_are_rows_times_logical_widths(config, query):
    doc, reads = config_doc(config), query_doc(query)["reads"]
    widths = doc["logical_widths_bytes"]
    by_hand = 0
    for table, columns in reads.items():
        for column in columns:
            by_hand += doc["rows"][table] * widths[doc["schema"][table][column]]
    assert needed_bytes(query_doc(query), doc) == by_hand > 0
    # the generator's own rows are the nominal ones to a part in 10^5
    actual = dict(doc, rows=datagen.table_rows(doc["scale_factor"]))
    generated = needed_bytes(query_doc(query), actual)
    assert abs(generated - by_hand) <= 1e-5 * by_hand


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_rows_are_the_generators(config):
    doc = config_doc(config)
    actual = datagen.table_rows(doc["scale_factor"])
    for table, rows in doc["rows"].items():
        assert abs(actual[table] - rows) <= 1e-5 * rows, table


@pytest.mark.parametrize("config", CONFIGS)
def test_no_configuration_lists_what_is_computed(config):
    doc = config_doc(config)
    assert "needed_bytes" not in doc
    assert set(doc["tables"]) <= set(doc["rows"])
    assert set(doc["tables"]) <= set(doc["schema"])
    kinds = {kind for cols in doc["schema"].values() for kind in cols.values()}
    assert kinds <= set(doc["logical_widths_bytes"])


@pytest.mark.parametrize("config,query,figure", [
    ("tpch-sf1-resident", "tpch-q1", 264_000_000),
    ("tpch-sf1-resident", "tpch-q6", 168_000_000),
    ("tpch-sf1-resident", "tpch-q3", 205_800_000),
    ("tpch-sf1-resident", "tpch-q5", 224_560_560),
    ("tpch-sf10-lineitem-stream", "tpch-q1", 2_640_000_000),
    ("tpch-sf10-lineitem-stream", "tpch-q6", 1_680_000_000),
    ("tpch-sf10-resident", "tpch-q3", 2_058_000_000),
])
def test_the_figures_the_configurations_listed(config, query, figure):
    """What the three ``needed_bytes`` tables held until PR 33."""
    assert needed_bytes(query_doc(query), config_doc(config)) == figure


@pytest.mark.parametrize("broken", ["table", "column", "kind"])
def test_a_statement_the_configuration_cannot_serve_is_an_error(broken):
    doc = config_doc("tpch-sf10-lineitem-stream")
    reads = {"table": {"orders": ["o_orderkey"]},
             "column": {"lineitem": ["l_nosuch"]},
             "kind": {"lineitem": ["l_orderkey"]}}[broken]
    if broken == "kind":
        doc["logical_widths_bytes"] = {"int32": 4}
    with pytest.raises(KeyError):
        needed_bytes({"reads": reads}, doc)


def test_a_query_file_added_to_a_copy_is_read_with_no_configuration_edited(
        tmp_path, capsys):
    """A statement no configuration ever listed: its query file alone
    makes the cell run and the roofline's reader find its bytes."""
    cell = bench_copy.make_copy(tmp_path,
                                cycle=("tpch-q1", "throwaway-q6"))
    bdir = os.path.join(str(tmp_path), "benchmark")
    added = dict(query_doc("tpch-q6"), name="throwaway-q6")
    added["reads"] = {"lineitem": added["reads"]["lineitem"] + ["l_tax"]}
    bench_copy.write_json(os.path.join(bdir, "queries", "throwaway-q6.json"),
                          added)
    for name in CONFIGS:
        with open(os.path.join(bdir, "configs", name + ".json")) as copy, \
                open(os.path.join(BENCH, "configs", name + ".json")) as ours:
            assert copy.read() == ours.read()
    run = bench_copy.load_run_module(tmp_path)
    capsys.readouterr()
    rc = run.main(["--workload", cell, "--seed", "33", "--seconds", "0.2",
                   "--trace", "0"], require_platform="cpu",
                  root=str(tmp_path))
    result = bench_copy.result_line(capsys.readouterr().out)
    assert rc == 0 and result["correct"] is True
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0

    # the reader, on a window that held one of each whole
    c = run.Cell(cell, str(tmp_path))
    rows = c.config["rows"]["lineitem"]
    assert needed_bytes(c.queries["throwaway-q6"], c.config) == rows * 36

    def st(query, wall0, wall1):
        return types.SimpleNamespace(query=query, wall0=wall0, wall1=wall1)

    done = [st("tpch-q1", 10.0, 11.0), st("throwaway-q6", 11.0, 12.0)]
    value = run.load_reader(c.bench_dir, "readers/scan_hbm_roofline.py:read")(
        run.Run(config=c.config, queries=c.queries, statements=done,
                done=done, peaks={"hbm_bytes_per_s": 1e9},
                trace={"wall": [9.0, 13.0], "busy_s": 0.5}))
    assert value == pytest.approx(100.0 * rows * (44 + 36) / 1e9 / 0.5)
