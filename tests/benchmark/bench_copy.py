"""Helpers of the benchmark's tests: a throw-away copy of the benchmark
with a cell, a configuration and a per-layer metric ADDED as new files
and new entries in ``BENCHMARK.json`` — no file that was there is
edited — and the copy's ``run.py`` loaded from there."""

import importlib.util
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the chip cells' session options plus what a CPU test run needs: the
#: eight virtual devices of tests/conftest.py must not draw the mesh
#: executor in, and the stages must take the XLA route the chip takes
TEST_SESSION_OPTIONS = {
    "spark.sail.cache.result.enabled": "false",
    "spark.sail.execution.mesh": "off",
    "spark.sail.execution.backend.force": "xla",
}


def write_json(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def make_copy(dest, sf=0.01, cycle=("tpch-q1", "tpch-q6"), streams=1,
              cell="throwaway-cell"):
    """Copy ``BENCHMARK.json`` and ``benchmark/`` to ``dest`` and add a
    configuration at scale ``sf``, a traffic mix, a per-layer metric
    with its reader, and a cell that uses them. Returns the cell's
    name."""
    dest = str(dest)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bdir = os.path.join(dest, "benchmark")

    config = load_json(os.path.join(bdir, "configs",
                                    "tpch-sf1-resident.json"))
    config["name"] = "throwaway-config"
    config["scale_factor"] = sf
    config["session_options"] = dict(TEST_SESSION_OPTIONS)
    config["trace"] = {"after_seconds": 0.2, "seconds": 1.0}
    config["rows"] = {t: rows if t in ("region", "nation")
                      else int(rows * sf)
                      for t, rows in config["rows"].items()}
    write_json(os.path.join(bdir, "configs", "throwaway-config.json"),
               config)
    write_json(os.path.join(bdir, "traffic", "throwaway-traffic.json"),
               {"name": "throwaway-traffic", "loop": "closed",
                "streams": streams, "cycle": list(cycle),
                "warm_cycles": 2})
    write_json(os.path.join(bdir, "metrics", "throwaway_rows.json"),
               {"name": "throwaway_rows", "unit": "rows",
                "reader": "readers/throwaway_rows.py:read"})
    with open(os.path.join(bdir, "readers", "throwaway_rows.py"), "w") as f:
        f.write("def read(run):\n"
                "    return sum(st.table.num_rows for st in run.done)\n")

    bench["configs"].append({
        "name": "throwaway-config", "source": "test",
        "file": "benchmark/configs/throwaway-config.json",
        "reduced": ["scale_factor"], "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": "throwaway-config",
        "traffic": "throwaway-traffic", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "Entry points",
        "moves": "queries_per_hour", "workloads": [cell]})
    write_json(os.path.join(dest, "BENCHMARK.json"), bench)
    return cell


def load_run_module(dest):
    """The copy's own ``run.py``, with the copy's directory first on
    the path so that its helpers, not the checkout's, are imported."""
    dest = str(dest)
    bdir = os.path.join(dest, "benchmark")
    for name in ("compare", "datagen", "tracered", "tpch_oracle",
                 "span_metrics", "needed_bytes"):
        sys.modules.pop(name, None)
    sys.path.insert(0, bdir)
    spec = importlib.util.spec_from_file_location(
        "bench_run_copy", os.path.join(bdir, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def statements_of(config):
    """Names of the query files whose tables the configuration has:
    the statements a cell over it can send."""
    qdir = os.path.join(ROOT, "benchmark", "queries")
    names = sorted(f[:-5] for f in os.listdir(qdir) if f.endswith(".json"))
    return [n for n in names
            if set(load_json(os.path.join(qdir, n + ".json"))["reads"])
            <= set(config["tables"])]


def result_line(text):
    """The last line of a run's standard output, parsed."""
    return json.loads(text.strip().splitlines()[-1])
