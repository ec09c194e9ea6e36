#!/usr/bin/env python3
"""One run of one cell through ``run.py``, and afterwards what the
statements' span trees say, per statement of the mix.

    python3 benchmark/span_report.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--out <file.json>]

``run.py``'s result line holds one number per metric; this is the table
behind them, for PERF.md's "where the time goes": per span name the
count, summed and self time per statement (medians over the window's
statements), the ``dispatch`` spans by program, the ``sync`` spans by
site, the ``compile`` spans by program and cause, and the client's
latency inside and outside the traced seconds (what tracing costs while
it is on). It measures nothing itself and changes nothing of the run:
the profiles are the ones the flight recorder kept.

The driver never runs this file; its output is not a result line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def span_table(profiles: list) -> dict:
    """{span name: {"count", "ms", "self_ms"}}, medians per statement."""
    names = sorted({s.name for p in profiles for s in p.spans})
    return {name: {
        "count": median(p.span_count(name) for p in profiles),
        "ms": median(p.span_ms(name) for p in profiles),
        "self_ms": median(p.self_ms(name) for p in profiles)}
        for name in names}


def by_attribute(profiles: list, name: str, *keys: str) -> dict:
    """The spans called ``name`` grouped by their attributes ``keys``:
    {"k1|k2": {"count", "ms", "bytes"}} per statement (means)."""
    groups = {}
    for p in profiles:
        for s in p.spans:
            if s.name == name:
                label = "|".join(str(s.attributes.get(k)) for k in keys)
                g = groups.setdefault(label, [0, 0.0, 0])
                g[0] += 1
                g[1] += s.ms
                g[2] += int(s.attributes.get("bytes", 0) or 0)
    n = max(len(profiles), 1)
    return {label: {"count": c / n, "ms": ms / n, "bytes": b / n}
            for label, (c, ms, b) in sorted(groups.items())}


def report(statements: list, trace_wall: dict) -> dict:
    out = {}
    done = [st for st in statements
            if st.error is None and st.profile is not None
            and hasattr(st.profile, "spans")]
    for query in sorted({st.query for st in done}):
        sts = [st for st in done if st.query == query]
        profiles = [st.profile for st in sts]
        entry = {
            "statements": len(sts),
            "client_ms_p50": median(st.ms for st in sts),
            "phases_ms": {ph: median(p.phases.get(ph, 0.0)
                                     for p in profiles)
                          for ph in sorted({k for p in profiles
                                            for k in p.phases})},
            "spans_per_statement": median(len(p.spans) for p in profiles),
            "spans_dropped": sum(p.spans_dropped for p in profiles),
            "host_syncs": median(p.host_syncs for p in profiles),
            "sync_wait_ms": median(p.sync_wait_ms for p in profiles),
            "spans": span_table(profiles),
            "dispatch_by_program": by_attribute(profiles, "dispatch",
                                                "program"),
            "sync_by_site": by_attribute(profiles, "sync", "site"),
            "compile_by_program_cause": by_attribute(
                profiles, "compile", "program", "source", "cause"),
            "read_source": by_attribute(profiles, "resolve.read_source",
                                        "format", "files", "bytes_read"),
        }
        if trace_wall:
            w0, w1 = trace_wall["wall0"], trace_wall["wall1"]
            inside = [st.ms for st in sts if st.wall0 >= w0
                      and st.wall1 <= w1]
            outside = [st.ms for st in sts if st.wall1 < w0
                       or st.wall0 > w1]
            entry["client_ms_p50_inside_trace"] = median(inside)
            entry["client_ms_p50_outside_trace"] = median(outside)
            entry["statements_inside_trace"] = len(inside)
        out[query] = entry
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default="tpu")
    args, rest = ap.parse_known_args(argv)

    statements, trace_wall = [], {}
    real_call, real_traced = run.call, run.traced

    def call(*a, **kw):
        st = real_call(*a, **kw)
        statements.append(st)
        return st

    def traced(*a, **kw):
        walls = real_traced(*a, **kw)
        trace_wall.update(walls)
        return walls

    run.call, run.traced = call, traced
    try:
        return run.main(rest, require_platform=args.platform)
    finally:
        run.call, run.traced = real_call, real_traced
        doc = json.dumps(report(statements, trace_wall), indent=1,
                         default=str)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                f.write(doc + "\n")
        else:
            print(doc, file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
