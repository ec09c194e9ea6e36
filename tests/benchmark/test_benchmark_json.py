"""``BENCHMARK.json`` and the data files it names, held to the rules the
driver refuses a benchmark by before any run: keys, names, units,
bounds, and that every name finds its file."""

import os
import re
import sys

import pytest

from bench_copy import ROOT, load_json

sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from needed_bytes import needed_bytes  # noqa: E402

BENCH = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert 1 <= len(config["source"]) <= 200 and 1 <= len(config["why"]) <= 200
    assert config["file"].startswith("benchmark/")
    doc = load_json(os.path.join(ROOT, config["file"]))
    assert doc["name"] == config["name"]
    assert doc["reduced"] == config["reduced"]
    assert all(NAME.match(k) for k in config["reduced"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for key in ("source", "deployment", "scale_factor", "tables", "rows",
                "schema", "logical_widths_bytes", "guarantees",
                "session_options", "limits", "assumed", "trace"):
        assert key in doc, key
    # the backends its stages may take (the harness's default: xla
    # alone) are the ones its guarantees name
    for backend in doc.get("backends", ["xla"]):
        assert backend in doc["guarantees"]["executed_on"], backend
    assert doc["session_options"]["spark.sail.cache.result.enabled"] == "false"


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    traffic = load_json(os.path.join(ROOT, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    assert traffic["loop"] == "closed" and traffic["streams"] >= 1
    config = load_json(os.path.join(ROOT, "benchmark", "configs",
                                    cell["config"] + ".json"))
    for q in traffic["cycle"]:
        doc = load_json(os.path.join(ROOT, "benchmark", "queries",
                                     q + ".json"))
        assert os.path.exists(os.path.join(ROOT, "benchmark", "queries",
                                           doc["sql_file"]))
        assert needed_bytes(doc, config) > 0
        assert set(doc["reads"]) <= set(config["tables"])
        for table, cols in doc["reads"].items():
            assert set(cols) <= set(config["schema"][table])


def test_cells_are_distinct_pairs_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len(set(CELLS)) == len(CELLS)
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | \
        ({"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
        assert set(metric.get("workloads", CELLS)) <= \
            set(moved.get("workloads", CELLS))
        assert 1 <= len(metric["layer"]) <= 200
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_metric_names_are_unique_and_setup_s_is_there():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert setup["bound"] == 0.25 and "workloads" not in setup


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_of_its_own(metric):
    spec = load_json(os.path.join(ROOT, "benchmark", "metrics",
                                  metric["name"] + ".json"))
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    rel, _, function = spec["reader"].partition(":")
    assert rel == f"readers/{metric['name']}.py" and function == "read"
    with open(os.path.join(ROOT, "benchmark", rel)) as f:
        assert "def read(run)" in f.read()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    def reported(metrics):
        return [m["name"] for m in metrics
                if cell in m.get("workloads", CELLS)]
    end_to_end = reported(BENCH["end_to_end"])
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert reported(BENCH["per_layer"])


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_peaks_name_the_v5e_and_their_source():
    peaks = load_json(os.path.join(ROOT, "benchmark", "peaks.json"))
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "v5e" in peaks["source"]


def test_files_under_paths_are_named_from_plain_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), ROOT)
                assert ok.match(rel), rel
