"""``dict_intern_share``: the share of the string columns converted from
Arrow whose dictionary the intern table handed back (``dicts_interned``
over ``strings`` on the ``arrow.convert`` spans under ``execute``). Its
reader on hand-built span trees, and in the result line of a traced run
of the harness on the CPU over a streamed Q1."""

import contextlib
import importlib
import importlib.util
import io
import os
import sys
import types

import pytest

import bench_copy
from bench_copy import ROOT, load_json, result_line, write_json

sys.path.insert(0, ROOT)

from sail_tpu import profiler  # noqa: E402
from sail_tpu import tracing as tr  # noqa: E402

METRIC = "dict_intern_share"


def _reader():
    spec = load_json(os.path.join(ROOT, "benchmark", "metrics",
                                  METRIC + ".json"))
    rel, _, function = spec["reader"].partition(":")
    module_spec = importlib.util.spec_from_file_location(
        "reader_" + METRIC, os.path.join(ROOT, "benchmark", rel))
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return getattr(module, function)


def _profile(*converts, parent="execute"):
    """A statement whose ``arrow.convert`` spans (one per attribute dict)
    lie under an ``op.ScanExec`` under ``parent``."""
    p = profiler.QueryProfile(query_id="q")
    spans = [("query", "a", None, {}), (parent, "b", "a", {}),
             ("op.ScanExec", "c", "b", {})]
    spans += [("arrow.convert", f"d{i}", "c", attrs)
              for i, attrs in enumerate(converts)]
    for name, sid, parent_id, attrs in spans:
        p.add_span(tr.Span(trace_id="t" * 32, span_id=sid,
                           parent_id=parent_id, name=name, start_ns=0,
                           end_ns=10**6, thread_id=1, attributes=attrs))
    return p


def _run(*profiles):
    return types.SimpleNamespace(
        done=[types.SimpleNamespace(profile=p) for p in profiles])


def _convert(strings, interned=None):
    attrs = {"rows": 8, "columns": 3, "strings": strings, "decimals": 0}
    if interned is not None:
        attrs["dicts_interned"] = interned
    return attrs


@pytest.mark.parametrize("profiles,expected", [
    # summed over the statements, not a median of their shares
    ([_profile(_convert(2, 0), _convert(2, 2)),
      _profile(_convert(6, 6), _convert(0, 0))], 80.0),
    ([_profile(_convert(2, 2)), _profile()], 100.0),
    # a program from before the count: nothing to read
    ([_profile(_convert(2)), _profile(_convert(2))], None),
    # no string column converted (Q6 alone)
    ([_profile(_convert(0, 0))], None),
    # a conversion outside execute is not the executor's
    ([_profile(_convert(2, 2), parent="fetch")], None),
    # a program that keeps no span tree
    ([types.SimpleNamespace()], None),
], ids=["summed", "a_statement_without_conversions", "no_count",
        "no_strings", "outside_execute", "no_span_tree"])
def test_reader(profiles, expected):
    assert _reader()(_run(*profiles)) == expected


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of a throw-away cell of Q1 streamed in chunks, the
    metric listing the cell."""
    dest = tmp_path_factory.mktemp("bench_dict_intern")
    cell = bench_copy.make_copy(dest, cycle=("tpch-q1", "tpch-q6"))
    config_path = os.path.join(dest, "benchmark", "configs",
                               "throwaway-config.json")
    config = load_json(config_path)
    config["session_options"]["spark.sail.scan.chunkRows"] = "20000"
    write_json(config_path, config)
    bench_path = os.path.join(dest, "BENCHMARK.json")
    bench = load_json(bench_path)
    for m in bench["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"].append(cell)
    write_json(bench_path, bench)

    run = bench_copy.load_run_module(dest)
    tracered = importlib.import_module("tracered")
    real_devices = tracered.device_planes
    # the CPU's trace has no device plane: its host plane stands in
    tracered.device_planes = lambda planes: ["/host:CPU"]
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", cell, "--seed", str(2**31 + 39),
                           "--seconds", "2", "--trace", "1"],
                          require_platform="cpu", root=str(dest))
    finally:
        tracered.device_planes = real_devices
    assert rc == 0
    return result_line(out.getvalue())


def test_every_chunk_of_the_window_finds_its_dictionaries(traced):
    assert traced["correct"] is True
    # set-up's first calls and warm cycle met each value set first
    assert traced["metrics"][METRIC] == {"value": 100.0, "unit": "%"}
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
