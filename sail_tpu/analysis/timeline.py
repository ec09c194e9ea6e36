"""Derived views over the event stream: task timeline, decision
sequence, and critical-path attribution.

Everything here operates on a plain list of event records (dicts in the
:mod:`sail_tpu.events` shape) so it works identically on the live
in-memory ring (``system.telemetry.task_timeline``), on a durable JSONL
log replayed offline (``scripts/sail_timeline.py``), and in tests — the
event log is the single source of truth, the live run holds no
privileged state.

Critical-path attribution walks the task/fetch dependency edges the
events record: starting from the last-finishing task of a query's job,
each hop charges the task's wall time to categories —

- ``queue``      dispatch → worker start (slot/governor wait)
- ``fetch-wait`` time the task blocked on stage-input fetches
- ``compile``    JIT compile events inside the task's execution window
- ``compute``    the execution remainder
- ``replan``     gap between the gating producer's finish and this
                 task's dispatch when adaptive decisions fired inside it
                 (otherwise the gap is ``queue``)

and follows the fetch edge to the producer task that finished LAST (the
fetch that actually gated), until a leaf task with no inputs.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

#: decision-bearing event types, in the order the replay reports them
DECISION_TYPES = ("adaptive_applied", "adaptive_rollback",
                  "speculation_launch", "speculation_win",
                  "worker_evict", "worker_quarantine",
                  "epoch_stage", "epoch_commit", "epoch_replay",
                  "admission_enqueue", "admission_admit",
                  "admission_defer", "admission_shed", "quota_debit",
                  "deadline_cancel", "backend_route",
                  "task_resident", "marker_inject", "marker_align",
                  "backpressure")

CATEGORIES = ("compute", "fetch-wait", "queue", "compile", "replan",
              "credit-stall")


def _for_query(events: List[dict],
               query_id: Optional[str]) -> List[dict]:
    if query_id is None:
        return list(events)
    return [e for e in events if e.get("query_id") == query_id]


def query_ids(events: List[dict]) -> List[str]:
    """Distinct non-empty query ids, in first-appearance order."""
    seen: Dict[str, None] = {}
    for e in events:
        q = e.get("query_id")
        if q:
            seen.setdefault(q, None)
    return list(seen)


# ---------------------------------------------------------------------------
# task timeline
# ---------------------------------------------------------------------------

def task_timeline(events: List[dict],
                  query_id: Optional[str] = None) -> List[dict]:
    """One row per task ATTEMPT: dispatch/start/finish timestamps and
    the derived queue/run/fetch-wait durations, ordered by (query, job,
    stage, partition, attempt)."""
    rows: Dict[Tuple, dict] = {}
    for e in _for_query(events, query_id):
        t = e.get("type")
        if t not in ("task_dispatch", "task_start", "task_finish"):
            continue
        key = (e.get("query_id", ""), e.get("job_id", ""),
               e.get("stage"), e.get("partition"), e.get("attempt"))
        row = rows.setdefault(key, {
            "query_id": key[0], "job_id": key[1], "stage": key[2],
            "partition": key[3], "attempt": key[4], "worker": "",
            "dispatch_time": None, "start_time": None,
            "finish_time": None, "state": "", "rows_out": 0,
            "fetch_wait_ms": 0.0})
        if t == "task_dispatch":
            row["dispatch_time"] = e.get("ts")
            row["worker"] = e.get("worker", "") or row["worker"]
        elif t == "task_start":
            row["start_time"] = e.get("ts")
            row["worker"] = e.get("worker", "") or row["worker"]
        else:
            row["finish_time"] = e.get("ts")
            row["state"] = e.get("state", "")
            row["rows_out"] = int(e.get("rows", 0) or 0)
            row["fetch_wait_ms"] = float(e.get("fetch_wait_ms", 0.0)
                                         or 0.0)
            row["worker"] = e.get("worker", "") or row["worker"]
    out = []
    for key in sorted(rows, key=lambda k: tuple(
            (v is None, v) for v in k)):
        row = rows[key]
        d, s, f = (row["dispatch_time"], row["start_time"],
                   row["finish_time"])
        row["queue_ms"] = round((s - d) * 1000.0, 3) \
            if d is not None and s is not None else None
        row["run_ms"] = round((f - s) * 1000.0, 3) \
            if s is not None and f is not None else None
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# decision sequence
# ---------------------------------------------------------------------------

def decisions(events: List[dict],
              query_id: Optional[str] = None) -> List[dict]:
    """Decision events in log (append) order — the sequence a replay
    must reproduce bit-identically for a fixed fault seed."""
    return [e for e in _for_query(events, query_id)
            if e.get("type") in DECISION_TYPES]


def adaptive_decisions(events: List[dict],
                       query_id: Optional[str] = None) -> List[dict]:
    """The adaptive decision records exactly as the live profile stores
    them (``QueryProfile.adaptive_events``): the ``detail`` payload of
    every ``adaptive_applied`` event, in order."""
    out = []
    for e in _for_query(events, query_id):
        if e.get("type") != "adaptive_applied":
            continue
        try:
            out.append(json.loads(e.get("detail", "") or "{}"))
        except ValueError:
            out.append({"kind": e.get("kind", ""), "detail": "malformed"})
    return out


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------

def _winning_tasks(evs: List[dict]) -> Dict[Tuple, dict]:
    """Per (job_id, stage, partition): the attempt whose ``task_finish``
    the driver accepted as succeeded (first in log order), merged with
    its dispatch/start events. Keys carry the job id — one query
    profile can span several jobs (a streaming trigger dispatches more
    than one graph, each numbering stages from 0), and their tasks must
    never collide."""
    finishes: Dict[Tuple, dict] = {}
    for e in evs:
        if e.get("type") == "task_finish" and \
                e.get("state") == "succeeded":
            key = (e.get("job_id", ""), e.get("stage"),
                   e.get("partition"))
            finishes.setdefault(key, dict(e))
    for e in evs:
        t = e.get("type")
        if t not in ("task_dispatch", "task_start"):
            continue
        key = (e.get("job_id", ""), e.get("stage"), e.get("partition"))
        win = finishes.get(key)
        if win is None or e.get("attempt") != win.get("attempt"):
            continue
        win["dispatch_ts" if t == "task_dispatch" else "start_ts"] = \
            e.get("ts")
    return finishes


def _fetch_edges(evs: List[dict]) -> Dict[Tuple, List[Tuple]]:
    """(job_id, dst_stage, dst_partition) → fetched (job_id, producer
    stage, producer partition) keys, from ``fetch_end`` events."""
    edges: Dict[Tuple, List[Tuple]] = {}
    for e in evs:
        if e.get("type") != "fetch_end":
            continue
        job = e.get("job_id", "")
        dst = (job, e.get("dst_stage"), e.get("dst_partition"))
        edges.setdefault(dst, []).append(
            (job, e.get("stage"), e.get("partition")))
    return edges


def _compiles_in(evs: List[dict], t0: float, t1: float,
                 task: Optional[str]) -> float:
    """JIT compile ms attributable to one task's execution window.
    Worker-shipped compile events carry the driver-stamped ``task``
    envelope ("s<stage>p<partition>a<attempt>") and match by identity;
    unstamped events (driver/local compiles) fall back to the time
    window."""
    ms = 0.0
    for e in evs:
        if e.get("type") != "compile" or e.get("ts") is None:
            continue
        stamped = e.get("task")
        if stamped is not None:
            if task is None or stamped != task:
                continue
        elif not (t0 <= e["ts"] <= t1):
            continue
        ms += float(e.get("ms", 0.0) or 0.0)
    return ms


def _credit_stalls_in(evs: List[dict], t0: float, t1: float,
                      task: Optional[str]) -> float:
    """Credit-stall ms attributable to one task's execution window:
    worker-shipped ``backpressure`` events carry the driver-stamped
    ``task`` envelope and match by identity; unstamped (driver-side)
    events fall back to the time window."""
    ms = 0.0
    for e in evs:
        if e.get("type") != "backpressure" or e.get("ts") is None:
            continue
        stamped = e.get("task")
        if stamped is not None:
            if task is None or stamped != task:
                continue
        elif not (t0 <= e["ts"] <= t1):
            continue
        ms += float(e.get("stall_ms", 0.0) or 0.0)
    return ms


def wait_evidence(events: List[dict],
                  query_id: Optional[str] = None) -> Dict[str, dict]:
    """Per-category wait evidence for one query, from its events alone:
    the raw material the anomaly classifier (analysis/anomaly.py) ranks
    a verdict from. Each entry is ``{"ms", "events"}`` — the wall time
    the events themselves carry (retrace compile ms excluding the
    benign first-ever cold compile, backpressure ``stall_ms``,
    ``admission_admit`` ``waited_ms``, ``task_finish``
    ``fetch_wait_ms``) and how many events contributed;
    ``governor_defer`` carries no duration, so it contributes a count
    only. Works identically on the live ring and a replayed durable
    log."""
    out: Dict[str, dict] = {
        "retrace": {"ms": 0.0, "events": 0},
        "credit-stall": {"ms": 0.0, "events": 0},
        "admission-queue-wait": {"ms": 0.0, "events": 0},
        "fetch-wait": {"ms": 0.0, "events": 0},
        "governor-defer": {"ms": 0.0, "events": 0},
    }
    for e in _for_query(events, query_id):
        t = e.get("type")
        if t == "retrace":
            if e.get("cause") == "first-ever":
                continue
            out["retrace"]["ms"] += float(e.get("ms", 0.0) or 0.0)
            out["retrace"]["events"] += 1
        elif t == "backpressure":
            ms = float(e.get("stall_ms", 0.0) or 0.0)
            if ms > 0.0:
                out["credit-stall"]["ms"] += ms
                out["credit-stall"]["events"] += 1
        elif t == "admission_admit":
            ms = float(e.get("waited_ms", 0.0) or 0.0)
            if ms > 0.0:
                out["admission-queue-wait"]["ms"] += ms
                out["admission-queue-wait"]["events"] += 1
        elif t == "task_finish":
            ms = float(e.get("fetch_wait_ms", 0.0) or 0.0)
            if ms > 0.0:
                out["fetch-wait"]["ms"] += ms
                out["fetch-wait"]["events"] += 1
        elif t == "governor_defer":
            out["governor-defer"]["events"] += 1
    for v in out.values():
        v["ms"] = round(v["ms"], 3)
    return out


def continuous_progress(events: List[dict],
                        query_id: Optional[str] = None) -> List[dict]:
    """Marker progress of a continuous pipeline, replayable from the
    log alone: per marker, the inject time, every mid-flight alignment
    (stage/partition, wait, buffered bytes), and the credit stalls that
    landed between this inject and the next."""
    evs = _for_query(events, query_id)
    markers: Dict[int, dict] = {}
    order: List[int] = []
    for e in evs:
        t = e.get("type")
        if t == "marker_inject":
            m = int(e.get("marker", 0) or 0)
            if m not in markers:
                order.append(m)
            markers.setdefault(m, {"marker": m,
                                   "inject_ts": e.get("ts"),
                                   "aligns": [],
                                   "stall_ms": 0.0})
        elif t == "marker_align":
            m = int(e.get("marker", 0) or 0)
            rec = markers.get(m)
            if rec is None:
                order.append(m)
                rec = markers.setdefault(
                    m, {"marker": m, "inject_ts": None, "aligns": [],
                        "stall_ms": 0.0})
            rec["aligns"].append({
                "stage": e.get("stage"), "partition": e.get("partition"),
                "wait_ms": float(e.get("wait_ms", 0.0) or 0.0),
                "buffered_bytes": int(e.get("buffered_bytes", 0) or 0),
                "ts": e.get("ts")})
    stalls = [e for e in evs if e.get("type") == "backpressure"]
    bounds = sorted((m, markers[m].get("inject_ts")) for m in markers
                    if markers[m].get("inject_ts") is not None)
    for e in stalls:
        ts = e.get("ts")
        target = None
        for m, t0 in bounds:
            if t0 is not None and ts is not None and ts >= t0:
                target = m
        if target is None and bounds:
            target = bounds[0][0]
        if target is not None:
            markers[target]["stall_ms"] += float(
                e.get("stall_ms", 0.0) or 0.0)
    out = []
    for m in order:
        rec = markers[m]
        aligned_ts = [a["ts"] for a in rec["aligns"]
                      if a["ts"] is not None]
        if rec["inject_ts"] is not None and aligned_ts:
            rec["align_ms"] = round(
                (max(aligned_ts) - rec["inject_ts"]) * 1000.0, 3)
        else:
            rec["align_ms"] = None
        rec["stall_ms"] = round(rec["stall_ms"], 3)
        out.append(rec)
    return out


def critical_path(events: List[dict],
                  query_id: Optional[str] = None) -> Optional[dict]:
    """Walk the gating chain of a query's distributed job. Returns
    ``{"total_ms", "categories": {cat: ms}, "chain": [...], "top":
    [{"category", "ms", "at"}]}`` (top-3 contributors, largest first)
    or None when the events carry no finished tasks."""
    evs = _for_query(events, query_id)
    tasks = _winning_tasks(evs)
    if not tasks:
        return None
    edges = _fetch_edges(evs)
    adaptive_ts = [e.get("ts") for e in evs
                   if e.get("type") in ("adaptive_applied",
                                        "adaptive_rollback")
                   and e.get("ts") is not None]
    entries: List[dict] = []
    chain: List[dict] = []

    def charge(at: str, category: str, ms: float) -> None:
        if ms > 0.0:
            entries.append({"at": at, "category": category,
                            "ms": round(ms, 3)})

    # the driver's root-stage merge (dst_partition -1) gates on the
    # last-finishing producer overall; start the walk there
    cur = max(tasks, key=lambda k: tasks[k].get("ts", 0.0))
    visited = set()
    while cur is not None and cur not in visited:
        visited.add(cur)
        win = tasks[cur]
        at = f"s{cur[1]}p{cur[2]}"
        finish = float(win.get("ts", 0.0) or 0.0)
        start = win.get("start_ts")
        dispatch = win.get("dispatch_ts")
        chain.append({"job_id": cur[0], "stage": cur[1],
                      "partition": cur[2],
                      "attempt": win.get("attempt"),
                      "worker": win.get("worker", "")})
        if start is not None:
            window_ms = max(0.0, (finish - start) * 1000.0)
            fetch_wait = min(window_ms, float(
                win.get("fetch_wait_ms", 0.0) or 0.0))
            task_label = (f"{cur[0]}/s{cur[1]}p{cur[2]}"
                          f"a{win.get('attempt')}")
            compile_ms = min(window_ms - fetch_wait,
                             _compiles_in(evs, start, finish,
                                          task_label))
            stall_ms = min(window_ms - fetch_wait - compile_ms,
                           _credit_stalls_in(evs, start, finish,
                                             task_label))
            charge(at, "fetch-wait", fetch_wait)
            charge(at, "compile", compile_ms)
            charge(at, "credit-stall", stall_ms)
            charge(at, "compute",
                   window_ms - fetch_wait - compile_ms - stall_ms)
        if dispatch is not None and start is not None:
            charge(at, "queue", max(0.0, (start - dispatch) * 1000.0))
        # follow the fetch edge to the producer that finished last (the
        # fetch that actually gated this task's start)
        preds = [p for p in edges.get(cur, ()) if p in tasks]
        nxt = max(preds, key=lambda k: tasks[k].get("ts", 0.0)) \
            if preds else None
        if nxt is not None and dispatch is not None:
            pred_finish = float(tasks[nxt].get("ts", 0.0) or 0.0)
            gap_ms = max(0.0, (dispatch - pred_finish) * 1000.0)
            replanned = any(pred_finish <= t <= dispatch
                            for t in adaptive_ts)
            charge(at, "replan" if replanned else "queue", gap_ms)
        cur = nxt

    if not entries:
        return None
    categories = {c: 0.0 for c in CATEGORIES}
    for entry in entries:
        categories[entry["category"]] += entry["ms"]
    categories = {c: round(ms, 3) for c, ms in categories.items() if ms}
    top = sorted(entries, key=lambda e: -e["ms"])[:3]
    return {"total_ms": round(sum(e["ms"] for e in entries), 3),
            "categories": categories, "chain": chain, "top": top}


def render_critical_path(cp: Optional[dict]) -> str:
    """The EXPLAIN ANALYZE line: top-3 contributors with category."""
    if not cp or not cp.get("top"):
        return ""
    parts = [f"{e['category']} {e['ms']:.1f}ms ({e['at']})"
             for e in cp["top"]]
    return f"critical path: {', '.join(parts)}"


# ---------------------------------------------------------------------------
# offline reconstruction (scripts/sail_timeline.py)
# ---------------------------------------------------------------------------

def reconstruct(events: List[dict], query_id: str) -> dict:
    """Everything the replay tool derives for one query."""
    evs = _for_query(events, query_id)
    stages = []
    for e in evs:
        if e.get("type") == "stage_submit":
            stages.append({"stage": e.get("stage"),
                           "partitions": e.get("partitions"),
                           "pipelined": bool(e.get("pipelined")),
                           "submit_time": e.get("ts"),
                           "complete_time": None, "rows": None})
        elif e.get("type") == "stage_complete":
            for s in stages:
                if s["stage"] == e.get("stage") and \
                        s["complete_time"] is None:
                    s["complete_time"] = e.get("ts")
                    s["rows"] = e.get("rows")
                    break
    start = next((e for e in evs if e.get("type") == "query_start"), None)
    end = next((e for e in evs if e.get("type") == "query_end"), None)
    return {
        "query_id": query_id,
        "trace_id": next((e.get("trace_id") for e in evs
                          if e.get("trace_id")), None),
        "statement": (start or {}).get("statement", ""),
        "status": (end or {}).get("status", ""),
        "stages": stages,
        "tasks": task_timeline(evs),
        "decisions": decisions(evs),
        "adaptive_decisions": adaptive_decisions(evs),
        "continuous": continuous_progress(evs),
        "critical_path": critical_path(evs),
    }


def render_timeline(events: List[dict], query_id: str,
                    width: int = 60) -> str:
    """Text Gantt of one query's stages/tasks plus the decision log and
    critical-path line — the human view of a replayed run."""
    rec = reconstruct(events, query_id)
    lines = [f"query {query_id}"
             + (f" [{rec['status']}]" if rec["status"] else "")]
    if rec["statement"]:
        lines.append(f"  {rec['statement'][:100]}")
    tasks = [t for t in rec["tasks"] if t["dispatch_time"] is not None
             and t["finish_time"] is not None]
    if tasks:
        t0 = min(t["dispatch_time"] for t in tasks)
        t1 = max(t["finish_time"] for t in tasks)
        span = max(t1 - t0, 1e-9)

        def bar(a: float, b: float) -> str:
            lo = int((a - t0) / span * width)
            hi = max(lo + 1, int((b - t0) / span * width))
            return " " * lo + "#" * (hi - lo)

        lines.append(f"  timeline ({span * 1000.0:.1f}ms across "
                     f"{len(tasks)} task attempts)")
        for t in tasks:
            label = (f"  s{t['stage']}p{t['partition']}"
                     f"a{t['attempt']}").ljust(12)
            state = "" if t["state"] == "succeeded" else f" {t['state']}"
            lines.append(
                f"{label}|{bar(t['dispatch_time'], t['finish_time'])}"
                f"|{state} {t['worker']}")
    if rec["decisions"]:
        lines.append(f"  decisions ({len(rec['decisions'])}):")
        for d in rec["decisions"]:
            attrs = {k: v for k, v in d.items()
                     if k not in ("v", "seq", "ts", "type", "query_id",
                                  "trace_id")}
            lines.append(f"    {d['type']}: "
                         f"{json.dumps(attrs, sort_keys=True)}")
    if rec["continuous"]:
        lines.append(f"  markers ({len(rec['continuous'])}):")
        for m in rec["continuous"]:
            align = f"{m['align_ms']:.1f}ms" \
                if m.get("align_ms") is not None else "?"
            buffered = sum(a["buffered_bytes"] for a in m["aligns"])
            lines.append(
                f"    m{m['marker']}: inject→align {align}, "
                f"{len(m['aligns'])} align point(s), "
                f"{buffered}B buffered, "
                f"credit stalls {m['stall_ms']:.1f}ms")
    cp_line = render_critical_path(rec["critical_path"])
    if cp_line:
        lines.append("  " + cp_line)
    return "\n".join(lines)
