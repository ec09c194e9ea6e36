"""Cluster flight-data recorder: one append-only structured event log.

Reference role: the Spark event-log analogue for sail-tpu, motivated by
Theseus (arXiv:2508.05029 — at scale the engine is a data-movement
scheduler, so wall-clock attribution is a scheduling question only a
cluster-wide timeline can answer) and Tailwind (arXiv:2604.28079 — the
same event stream is the ops surface of a multi-tenant serving layer).

Every autonomous runtime decision the engine makes — task dispatch and
retry, governor admission, adaptive replanning, speculation, eviction,
streaming epoch commits — lands in ONE typed, versioned, replayable
stream spanning driver and workers:

- a bounded in-memory ring (``telemetry.event_ring_capacity``), always
  on, queryable as ``system.telemetry.events`` /
  ``system.telemetry.task_timeline``;
- an optional durable JSONL log (``telemetry.event_log.{enabled,dir,
  max_mb,max_segments}``, surfaced as
  ``spark.sail.telemetry.eventLog.*``) rotated in bounded segments
  that ``scripts/sail_timeline.py`` replays offline across segment
  boundaries — the post-mortem ground truth for "why was this query
  slow";
- worker-side events ship to the driver piggybacked on the terminal
  task-status report (``ReportTaskStatusRequest.events_json``), so the
  driver's log is the cluster-wide merge;
- every event carries the query's ``trace_id``, so OTLP spans and the
  event log cross-reference.

The vocabulary is DECLARED (:data:`EVENT_TYPES`) and enforced both at
emit time (unknown type / undeclared attribute raises) and statically
by the ``events`` lint (scripts/sail_lint.py): every ``emit(EventType.X)``
call site must use a declared type with the declared attribute set, and
every declared type must be emitted somewhere.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger("sail_tpu.events")

#: bump when a record's shape changes incompatibly; replay tooling keys
#: off it (``sail_timeline.py`` refuses records from the future)
EVENT_SCHEMA_VERSION = 1

#: record keys owned by the envelope — never event attributes. ``task``
#: is stamped by the DRIVER when it ingests a worker report's events
#: ("s<stage>p<partition>a<attempt>"), so records the worker could not
#: scope itself (compile events) still attribute to the right task.
RESERVED_KEYS = ("v", "seq", "ts", "type", "query_id", "trace_id",
                 "task")

#: typed causes a compile miss can be attributed to (exec/retrace.py).
#: The ``slo-taxonomy`` lint enforces that every cause literal emitted
#: in code appears here and vice versa. ``first-ever`` is the benign
#: cold compile of a never-seen program; everything else is a RETRACE —
#: a program the process had and lost, or a shape drift.
RETRACE_CAUSES: Tuple[str, ...] = (
    "first-ever",          # fingerprint never compiled in this process
    "new-aval-signature",  # genuinely new arg structure/dtype/shape
    "capacity-bucket",     # same structure, only a leading (padded
                           # capacity) dim changed — round_capacity churn
    "eviction",            # in-memory op-cache evicted the program
)

#: ranked root-cause verdict categories the anomaly classifier
#: (analysis/anomaly.py) may emit; lint-enforced both ways like
#: :data:`RETRACE_CAUSES`.
VERDICT_CATEGORIES: Tuple[str, ...] = (
    "retrace",
    "credit-stall",
    "admission-queue-wait",
    "fetch-wait",
    "spill",
    "cache-invalidation",
    "governor-defer",
    "unexplained",
)

#: the declared vocabulary: event type → attribute keys. ``stage`` /
#: ``partition`` on fetch events are the PRODUCER task's coordinates;
#: ``dst_stage`` / ``dst_partition`` the consuming task's
#: (``dst_partition`` -1 = the driver's root-stage merge fetch).
EVENT_TYPES: Dict[str, Tuple[str, ...]] = {
    # query lifecycle (driver/session side, all execution paths)
    "query_start": ("statement", "session", "tenant"),
    "query_end": ("status", "rows_out", "total_ms", "fingerprint",
                  "spill_bytes", "cache_status"),
    # a stage program was bound: a compiled-operator cache miss (JIT
    # wall time in ms); source is always ``trace``
    "compile": ("key", "ms", "source"),
    # a compile miss attributed to a typed cause (exec/retrace.py):
    # ``fp`` is the program fingerprint the retrace ledger keys on,
    # ``cause`` ∈ RETRACE_CAUSES, ``ms`` the compile wall time,
    # ``site`` the decision site (memory)
    "retrace": ("key", "fp", "cause", "ms", "site"),
    # per-stage backend routing decision (exec/router.py): backend in
    # native | xla | mesh; stage -1 = the plan-level mesh-vs-local
    # gate; reason names the deciding rule (forced, cost-model,
    # compile-bound, dispatch-bound, unsupported, default, unavailable)
    "backend_route": ("stage", "kind", "backend", "reason"),
    # distributed stage lifecycle (driver)
    "stage_submit": ("job_id", "stage", "partitions", "pipelined"),
    "stage_complete": ("job_id", "stage", "rows"),
    # per-attempt task lifecycle: dispatch + finish on the driver,
    # start on the worker (shipped back in the terminal report)
    "task_dispatch": ("job_id", "stage", "partition", "attempt",
                      "worker", "reason"),
    "task_start": ("job_id", "stage", "partition", "attempt", "worker",
                   "tenant"),
    "task_finish": ("job_id", "stage", "partition", "attempt", "worker",
                    "state", "rows", "fetch_wait_ms", "error"),
    # shuffle fetch over the peer data plane (worker + driver consumers)
    "fetch_begin": ("job_id", "stage", "partition", "channel", "addr",
                    "dst_stage", "dst_partition"),
    "fetch_end": ("job_id", "stage", "partition", "channel", "addr",
                  "dst_stage", "dst_partition", "bytes", "ms", "ok"),
    # memory-footprint task governor (driver)
    "governor_admit": ("job_id", "stage", "partition", "worker",
                       "projected_bytes"),
    "governor_defer": ("job_id", "stage", "partition", "attempt"),
    # multi-tenant admission control (exec/admission.py): job_id is ""
    # for session-path (local query) decisions; ``cost`` is the DRR
    # cost — stage-launch opportunities for cluster jobs, 1 per query
    # on the session path
    "admission_enqueue": ("job_id", "tenant", "queue_depth", "cost"),
    "admission_admit": ("job_id", "tenant", "waited_ms"),
    "admission_defer": ("job_id", "tenant", "reason", "stage",
                        "partition"),
    "admission_shed": ("job_id", "tenant", "reason", "queue_depth"),
    # per-tenant memory-quota ledger: ``bytes`` is the task's projected
    # decoded input (observed producer channel sizes — AQE stats, not
    # static estimates); ``used_bytes`` the tenant total after debit
    "quota_debit": ("job_id", "tenant", "stage", "partition", "bytes",
                    "used_bytes"),
    # per-query deadline enforcement through the CancelJob path
    "deadline_cancel": ("job_id", "tenant", "deadline_ms",
                        "overrun_ms"),
    # adaptive query execution: ``detail`` is the canonical JSON of the
    # decision record (sort_keys), bit-identical to the profile's
    # adaptive event — replaying the log reconstructs the decision
    # sequence exactly
    "adaptive_applied": ("job_id", "kind", "detail"),
    "adaptive_rollback": ("job_id", "kind", "stages"),
    # speculative execution (driver)
    "speculation_launch": ("job_id", "stage", "partition", "attempt",
                           "worker"),
    "speculation_win": ("job_id", "stage", "partition", "attempt"),
    # worker pool health (driver, cluster-scoped: no query id)
    "worker_evict": ("worker", "reason"),
    "worker_quarantine": ("worker", "failures"),
    # elastic autoscaler (exec/autoscaler.py): one record per policy
    # tick that changes fleet intent. ``action`` ∈ scale_up |
    # scale_down | hold, ``worker`` the drain target ("" for
    # scale-up/hold), ``pool`` the live pool size the decision saw,
    # ``detail`` the canonical sort_keys JSON of the full signal
    # snapshot + decision record — replaying the durable log re-derives
    # the decision sequence bit-identically (same contract as
    # adaptive_applied / anomaly)
    "autoscaler_decision": ("action", "worker", "reason", "pool",
                            "detail"),
    # graceful-drain lifecycle for one worker (driver): ``phase`` ∈
    # begin | handoff | done | abort; ``channels``/``bytes`` count the
    # shuffle channels donated to peers so far, ``ms`` the elapsed
    # drain wall time at the phase edge
    "worker_drain": ("worker", "phase", "channels", "bytes", "ms"),
    # streaming epoch commit protocol (streaming.py)
    "epoch_stage": ("epoch", "rows"),
    "epoch_commit": ("epoch", "commit_ms"),
    "epoch_replay": ("epoch",),
    # continuous record-at-a-time streaming (exec/continuous.py):
    # a resident (long-lived) stage task dispatched; a marker injected
    # at the sources; a marker aligning mid-flight at one task's inputs
    # (wait_ms = first-input-blocked → all-aligned, buffered_bytes =
    # post-marker entries held for the slow sibling); a sender stalled
    # on exhausted channel credit (the backpressure signal)
    "task_resident": ("job_id", "stage", "partition", "attempt",
                      "worker"),
    "marker_inject": ("job_id", "marker"),
    "marker_align": ("job_id", "stage", "partition", "marker",
                     "wait_ms", "buffered_bytes"),
    "backpressure": ("job_id", "stage", "partition", "channel",
                     "stall_ms"),
    # a completed profile classified as a tail-latency outlier
    # (analysis/anomaly.py): ``verdict`` ∈ VERDICT_CATEGORIES,
    # ``excess_ms`` total_ms minus the baseline p50, ``detail`` the
    # canonical sort_keys JSON of the ranked evidence — replaying the
    # durable log re-derives verdicts bit-identically
    "anomaly": ("fingerprint", "verdict", "excess_ms", "detail"),
}


class EventType:
    """Symbolic names for the declared vocabulary — every emit site must
    use one of these (the ``events`` lint enforces it)."""

    QUERY_START = "query_start"
    QUERY_END = "query_end"
    COMPILE = "compile"
    RETRACE = "retrace"
    ANOMALY = "anomaly"
    BACKEND_ROUTE = "backend_route"
    STAGE_SUBMIT = "stage_submit"
    STAGE_COMPLETE = "stage_complete"
    TASK_DISPATCH = "task_dispatch"
    TASK_START = "task_start"
    TASK_FINISH = "task_finish"
    FETCH_BEGIN = "fetch_begin"
    FETCH_END = "fetch_end"
    GOVERNOR_ADMIT = "governor_admit"
    GOVERNOR_DEFER = "governor_defer"
    ADMISSION_ENQUEUE = "admission_enqueue"
    ADMISSION_ADMIT = "admission_admit"
    ADMISSION_DEFER = "admission_defer"
    ADMISSION_SHED = "admission_shed"
    QUOTA_DEBIT = "quota_debit"
    DEADLINE_CANCEL = "deadline_cancel"
    ADAPTIVE_APPLIED = "adaptive_applied"
    ADAPTIVE_ROLLBACK = "adaptive_rollback"
    SPECULATION_LAUNCH = "speculation_launch"
    SPECULATION_WIN = "speculation_win"
    WORKER_EVICT = "worker_evict"
    WORKER_QUARANTINE = "worker_quarantine"
    AUTOSCALER_DECISION = "autoscaler_decision"
    WORKER_DRAIN = "worker_drain"
    EPOCH_STAGE = "epoch_stage"
    EPOCH_COMMIT = "epoch_commit"
    EPOCH_REPLAY = "epoch_replay"
    TASK_RESIDENT = "task_resident"
    MARKER_INJECT = "marker_inject"
    MARKER_ALIGN = "marker_align"
    BACKPRESSURE = "backpressure"


def _validate(etype: str, attrs: Dict[str, object]) -> None:
    declared = EVENT_TYPES.get(etype)
    if declared is None:
        raise KeyError(f"event type {etype!r} is not declared in "
                       f"events.EVENT_TYPES")
    extra = set(attrs) - set(declared)
    if extra:
        raise KeyError(f"event {etype!r} does not declare attributes "
                       f"{sorted(extra)}")


def _drop_metric(count: int, reason: str) -> None:
    try:
        from .metrics import record as _record_metric
        _record_metric("telemetry.events.dropped_count", count,
                       reason=reason)
    except Exception:  # noqa: BLE001 — telemetry must never raise
        pass


class EventLog:
    """Bounded ring of event records + optional durable JSONL tail.

    The ring keeps the NEWEST ``capacity`` records (deque eviction).
    When a JSONL path is configured every appended record is also
    written as one ``json.dumps`` line and flushed, so a crash loses at
    most the half-written final line — the replay loader tolerates a
    truncated tail.

    ``max_bytes`` bounds each SEGMENT: a line that would push the
    active file past it first ROTATES — the active file shifts to
    ``<path>.1`` (older segments to ``.2``, ``.3``, …) and a fresh
    active segment opens, keeping at most ``max_segments`` files in
    total. Only when the oldest segment falls off the retention window
    are its events actually dropped from the durable log (counted per
    line in ``telemetry.events.dropped_count{reason=rotated}``).
    :func:`load_event_log` and ``scripts/sail_timeline.py`` read across
    segment boundaries, so replay sees one continuous stream."""

    def __init__(self, capacity: int = 4096, path: Optional[str] = None,
                 max_bytes: int = 0, max_segments: int = 4):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._seq = 0
        self._path = path
        self._file = None
        self._max_bytes = max(0, int(max_bytes))
        self._max_segments = max(1, int(max_segments))
        self._written = 0
        self._file_failed = False

    @property
    def path(self) -> Optional[str]:
        return self._path

    def emit(self, etype: str, query_id: str = "",
             trace_id: Optional[str] = None,
             ts: Optional[float] = None, **attrs) -> None:
        _validate(etype, attrs)
        record = {"v": EVENT_SCHEMA_VERSION,
                  "ts": ts if ts is not None else time.time(),
                  "type": etype, "query_id": query_id or "",
                  "trace_id": trace_id}
        record.update(attrs)
        self.append(record)

    def ingest(self, record: dict, query_id: str = "",
               trace_id: Optional[str] = None,
               task: Optional[str] = None) -> None:
        """Adopt a record produced elsewhere (a worker's shipped task
        events): stamp the envelope the remote side could not know and
        append. Unknown types are dropped, not raised — a version-skewed
        worker must not poison the driver's log."""
        if not isinstance(record, dict) or \
                record.get("type") not in EVENT_TYPES:
            _drop_metric(1, "malformed")
            return
        record.setdefault("v", EVENT_SCHEMA_VERSION)
        record.setdefault("ts", time.time())
        if query_id:
            record["query_id"] = query_id
        else:
            record.setdefault("query_id", "")
        if trace_id is not None:
            record["trace_id"] = trace_id
        else:
            record.setdefault("trace_id", None)
        if task is not None:
            record.setdefault("task", task)
        self.append(record)

    def append(self, record: dict) -> None:
        with self._lock:
            self._seq += 1
            record["seq"] = self._seq
            self._ring.append(record)
            if self._path is not None:
                self._write_line(record)

    @staticmethod
    def _count_lines(path: str) -> int:
        """Complete lines in one segment (drop accounting at rotation
        — segments are bounded by max_bytes, so this is one bounded
        read on a rare path)."""
        try:
            n = 0
            with open(path, "rb") as f:
                while True:
                    chunk = f.read(1 << 20)
                    if not chunk:
                        return n
                    n += chunk.count(b"\n")
        except OSError:
            return 0

    def _rotate(self) -> None:
        # under self._lock; the active file is open and full
        if self._file is not None:
            try:
                self._file.close()
            except OSError:
                pass
            self._file = None
        keep = self._max_segments - 1     # rotated slots beside active
        oldest = f"{self._path}.{keep}" if keep else self._path
        if os.path.exists(oldest):
            dropped = self._count_lines(oldest)
            try:
                os.remove(oldest)
            except OSError:
                pass
            if dropped:
                _drop_metric(dropped, "rotated")
        for i in range(keep - 1, 0, -1):
            src = f"{self._path}.{i}"
            if os.path.exists(src):
                try:
                    os.replace(src, f"{self._path}.{i + 1}")
                except OSError:
                    pass
        if keep and os.path.exists(self._path):
            try:
                os.replace(self._path, f"{self._path}.1")
            except OSError:
                pass
        self._written = 0

    def _write_line(self, record: dict) -> None:
        # under self._lock
        if self._file_failed:
            _drop_metric(1, "log_error")
            return
        try:
            if self._file is None:
                d = os.path.dirname(self._path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._file = open(self._path, "a", encoding="utf-8")
                self._written = self._file.tell()
            line = json.dumps(record, default=str,
                              separators=(",", ":")) + "\n"
            if self._max_bytes and self._written and \
                    self._written + len(line) > self._max_bytes:
                self._rotate()
            if self._file is None:
                self._file = open(self._path, "a", encoding="utf-8")
                self._written = self._file.tell()
            self._file.write(line)
            self._file.flush()
            self._written += len(line)
        except OSError:
            # an unwritable log must never fail the query path: fall
            # back to ring-only, keep COUNTING every skipped event, and
            # say so once — a clean-looking truncated file must not
            # masquerade as a complete log
            self._file_failed = True
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
            _drop_metric(1, "log_error")
            logger.warning(
                "event log %s became unwritable; further events stay "
                "in the ring only (dropped events count in "
                "telemetry.events.dropped_count{reason=log_error})",
                self._path)

    def events(self, query_id: Optional[str] = None) -> List[dict]:
        """Snapshot, oldest → newest (append order = decision order)."""
        with self._lock:
            out = list(self._ring)
        if query_id is not None:
            out = [e for e in out if e.get("query_id") == query_id]
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


class TaskEventCollector:
    """Worker-side per-task event buffer: execution threads (and the
    task's fetch pool threads) emit here; the terminal task-status
    report ships the drained buffer to the driver, which stamps the
    query envelope and merges it into the cluster-wide log."""

    #: events one task may buffer; beyond it the newest are dropped
    #: (counted) — a pathological task must not balloon its report
    LIMIT = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._dropped = 0

    def emit(self, etype: str, ts: Optional[float] = None,
             **attrs) -> None:
        if not enabled():
            return
        _validate(etype, attrs)
        record = {"v": EVENT_SCHEMA_VERSION,
                  "ts": ts if ts is not None else time.time(),
                  "type": etype}
        record.update(attrs)
        with self._lock:
            if len(self._events) >= self.LIMIT:
                self._dropped += 1
                dropped = True
            else:
                self._events.append(record)
                dropped = False
        if dropped:
            # count EVERY drop (only the overflow path pays the metric)
            _drop_metric(1, "collector_cap")

    def drain(self) -> List[dict]:
        with self._lock:
            out, self._events = self._events, []
        return out


# ---------------------------------------------------------------------------
# process-global log + the module-level emit every call site uses
# ---------------------------------------------------------------------------

def _log_from_config() -> EventLog:
    from .config import get as config_get
    from .config import truthy
    try:
        cap = int(config_get("telemetry.event_ring_capacity", 4096))
    except (TypeError, ValueError):
        cap = 4096
    path = None
    max_bytes = 0
    max_segments = 4
    try:
        if truthy("telemetry.event_log.enabled", default="false"):
            d = str(config_get("telemetry.event_log.dir", "") or "")
            if d:
                path = os.path.join(d, f"events-{os.getpid()}.jsonl")
                max_mb = float(config_get(
                    "telemetry.event_log.max_mb", 64))
                max_bytes = int(max_mb * (1 << 20))
                max_segments = int(config_get(
                    "telemetry.event_log.max_segments", 4))
    except (TypeError, ValueError):
        path = None
    return EventLog(cap, path=path, max_bytes=max_bytes,
                    max_segments=max_segments)


EVENT_LOG = _log_from_config()

_ENABLED: "bool | None" = None
_tls = threading.local()


def enabled() -> bool:
    """``telemetry.events_enabled`` gate, read once per process (emit
    sits on scheduling hot paths). The bench A/B knob
    ``SAIL_BENCH_DISABLE_EVENTS=1`` flips it for a whole run."""
    global _ENABLED
    if _ENABLED is None:
        try:
            from .config import truthy
            _ENABLED = truthy("telemetry.events_enabled")
        except Exception:  # noqa: BLE001 — events must not break imports
            _ENABLED = True
    return _ENABLED


def reload() -> None:
    """Re-read the event config and swap in a fresh global log (tests,
    bench A/B runs)."""
    global _ENABLED, EVENT_LOG
    _ENABLED = None
    old = EVENT_LOG
    EVENT_LOG = _log_from_config()
    old.close()


@contextmanager
def collecting(collector: TaskEventCollector):
    """Install a worker-task collector as this thread's event sink:
    events emitted on the thread (e.g. compile events from the local
    executor) buffer into the task's report instead of the global log."""
    prev = getattr(_tls, "collector", None)
    _tls.collector = collector
    try:
        yield collector
    finally:
        _tls.collector = prev


def emit(etype: str, query_id: Optional[str] = None,
         trace_id: Optional[str] = None, ts: Optional[float] = None,
         **attrs) -> None:
    """Emit one event. Routes to the thread's task collector when one
    is installed (worker task threads), otherwise to the global log.
    ``query_id``/``trace_id`` default from the thread's active query
    profile; driver-side sites pass them explicitly (the driver actor
    thread profiles nothing)."""
    if not enabled():
        return
    collector = getattr(_tls, "collector", None)
    if collector is not None:
        collector.emit(etype, ts=ts, **attrs)
        return
    if query_id is None:
        from . import profiler
        p = profiler.current_profile()
        query_id = p.query_id if p is not None else ""
        if trace_id is None and p is not None:
            trace_id = p.trace_id
    EVENT_LOG.emit(etype, query_id=query_id or "", trace_id=trace_id,
                   ts=ts, **attrs)


def events(query_id: Optional[str] = None) -> List[dict]:
    """Snapshot of the global ring (convenience for tables/tests)."""
    return EVENT_LOG.events(query_id=query_id)


# ---------------------------------------------------------------------------
# durable-log replay
# ---------------------------------------------------------------------------

def _load_one(path: str) -> Tuple[List[dict], bool]:
    """One segment: (records, clean). ``clean`` is False when the file
    ended at a truncated or malformed line — everything after that
    point (including NEWER segments) is untrusted."""
    out: List[dict] = []
    clean = True
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            if not line.endswith("\n"):
                clean = False
                break  # truncated tail: the crash cut this record short
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except ValueError:
                clean = False
                break
            if not isinstance(record, dict):
                clean = False
                break
            if int(record.get("v", 0)) > EVENT_SCHEMA_VERSION:
                raise ValueError(
                    f"event log {path} carries schema v{record.get('v')} "
                    f"(this build reads ≤ v{EVENT_SCHEMA_VERSION})")
            out.append(record)
    return out, clean


def log_segments(path: str) -> List[str]:
    """Every existing segment of a rotated log, OLDEST first:
    ``<path>.N`` … ``<path>.1``, then the active ``<path>``."""
    rotated = []
    i = 1
    while os.path.exists(f"{path}.{i}"):
        rotated.append(f"{path}.{i}")
        i += 1
    return rotated[::-1] + ([path] if os.path.exists(path)
                            else [])


def load_event_log(path: str) -> List[dict]:
    """Read a JSONL event log back — across rotated segments
    (``<path>.N`` oldest → ``<path>`` newest) — tolerating a truncated
    tail: a crash mid-write leaves at most one partial final line, and
    replay must reconstruct everything up to the last COMPLETE record.
    A malformed line mid-segment ends the replay there (everything
    after it, newer segments included, is untrusted). Records from a
    future schema version raise."""
    segments = log_segments(path)
    if not segments:
        # preserve the single-file contract: a missing log raises
        with open(path, "r", encoding="utf-8"):
            pass
        return []
    out: List[dict] = []
    for seg in segments:
        records, clean = _load_one(seg)
        out.extend(records)
        if not clean:
            break
    return out
