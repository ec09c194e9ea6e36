#!/usr/bin/env python3
"""The readings a limit is set from: the numbers ``compare.py`` holds a
cell to, over many seeds in ONE process (set-up is most of a run), and
the control's beside them.

    python3 benchmark/readings.py --workload <cell> --seconds 5 \
        --seeds 11,12,13 [--control-seeds 11,12,13]

For every seed it drives a whole run of ``run.py`` (its own set-up,
window at the cell's own load, comparison) and prints the run's
``checks``. For every control seed it makes the same data, computes the
reference in float32 (``compare.control_reading``) and holds it to the
float64 reference: the control has to fail a limit. The benchmark's own
runs never come here.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import shutil
import sys
import tempfile

import run as bench_run


def program_reading(workload: str, seed: int, seconds: float) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        bench_run.main(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", "0"])
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    return {"seed": seed, "correct": result["correct"],
            "attempted": result["attempted"],
            "checks": {k: v[0] for k, v in result["checks"].items()}}


def control_reading(workload: str, seed: int) -> dict:
    import compare
    import datagen
    cell = bench_run.Cell(workload)
    tmp = tempfile.mkdtemp(prefix="sail_bench_control_")
    try:
        _paths, frames, _rows, _bytes = datagen.write_tables(
            cell.wanted_tables(), seed, cell.config["scale_factor"], tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    numbers = compare.control_reading(cell.queries, frames)
    limits = cell.config["limits"]
    correct, _checks = compare.verdict(
        numbers, {k: limits[k] for k in numbers})
    return {"seed": seed, "control": True, "correct": correct,
            "checks": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    for seed in filter(None, args.control_seeds.split(",")):
        print(json.dumps(control_reading(args.workload, int(seed))),
              flush=True)
    for seed in filter(None, args.seeds.split(",")):
        print(json.dumps(program_reading(args.workload, int(seed),
                                         args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
