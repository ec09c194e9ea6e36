"""Per-query profiler + in-process flight recorder.

Reference role: the compile/data-movement accounting that Flare and
Theseus show is the prerequisite for optimizing a native/accelerator
query engine (PAPERS.md), grafted onto sail's telemetry surface. One
``QueryProfile`` is threaded from the session entry point through the
planner and both executors, recording

- the statement's span tree (``spans``): ``profile_query`` opens the
  root ``query`` span with the profile as its sink, and every
  ``tracing.span`` opened beneath it, on this thread or handed to
  another, is kept here when it ends (see tracing.py: the same span
  also lies on the xplane while a ``jax.profiler`` session runs, and
  goes to OTLP). ``span_ms`` / ``span_count`` / ``self_ms`` read it;
- phase wall times in execution order: parse, resolve, optimize,
  compile, execute, fetch, each the summed duration of the phase's
  spans. Parse/resolve/optimize/execute/fetch are disjoint; compile
  is accounted *inside* execute — it is the JIT wall time of operator
  cache misses — so it does not sum with the others;
- JIT accounting from the compiled-operator cache: hits, misses, and
  per-key compile wall time (also exported through the registry as
  ``execution.compile.{cache_hit_count,cache_miss_count,compile_time}``);
- device-transfer and spill bytes;
- per-operator metrics (under EXPLAIN ANALYZE) and, in cluster mode,
  per-task operator metrics merged per {stage, partition}.

Completed profiles land in a bounded flight-recorder ring (newest N),
plus a slow-query log that retains queries above
``spark.sail.telemetry.slowQueryMs`` even after the ring evicts them.
Both surfaces are SQL-queryable via ``system.telemetry.query_profiles``
and ``system.telemetry.active_queries``; the OTLP exporter receives the
``query`` span with the phase breakdown as attributes, and the phase
spans as its children.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import uuid
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .metrics import record as _record_metric

logger = logging.getLogger("sail_tpu.profiler")

#: canonical phase order for rendering (a profile only reports phases it
#: actually entered, in first-entry order)
PHASES = ("parse", "resolve", "optimize", "compile", "execute", "fetch")

_STATEMENT_MAX = 4096
#: spans one profile keeps (QueryProfile.admit_span)
_SPANS_MAX = 512


@dataclass
class QueryProfile:
    query_id: str
    statement: str = ""
    session: str = ""
    # admission-control tenant the query billed to (multi-tenant
    # serving; "" for unattributed internal queries)
    tenant: str = ""
    start_time: float = 0.0
    end_time: float = 0.0
    status: str = "running"          # running | succeeded | failed
    error: str = ""
    # phase → accumulated wall ms, insertion-ordered by first entry
    phases: Dict[str, float] = field(default_factory=dict)
    compile_cache_hits: int = 0
    compile_cache_misses: int = 0
    compile_ms: float = 0.0
    # programs that actually traced + XLA-compiled this query (every
    # note_compile_time call) — NOT compile_cache_misses: those count
    # per key, a key compiles once per argument signature
    compiled_programs: int = 0
    # per-key compile events: [{"key": str, "ms": float, "source":
    # "trace"}] — one per stage program compiled
    compile_events: List[dict] = field(default_factory=list)
    # retrace forensics (exec/retrace.py): every compile this query paid
    # attributed by typed cause. ``retrace_count``/``retrace_ms``
    # EXCLUDE first-ever (the benign cold compile) — they count
    # programs the process HAD and lost, or shape drift; the causes
    # dict keeps the full breakdown including first-ever
    retrace_count: int = 0
    retrace_ms: float = 0.0
    retrace_causes: Dict[str, int] = field(default_factory=dict)
    # plan fingerprint the baseline store and anomaly classifier key on
    # (session.py: sha of the structural plan key; "" when the plan is
    # unfingerprintable)
    plan_fingerprint: str = ""
    # anomaly classification (analysis/anomaly.py, set at finalize):
    # verdict ∈ events.VERDICT_CATEGORIES when the query was a
    # tail-latency outlier against its fingerprint baseline, else ""
    anomaly_verdict: str = ""
    anomaly_excess_ms: float = 0.0
    # admission-control queue wait this query paid before running
    admission_wait_ms: float = 0.0
    # per-stage backend routing decisions (exec/router.py):
    # [{"stage": int, "kind": str, "backend": str, "reason": str}]
    backend_routes: List[dict] = field(default_factory=list)
    transfer_bytes: int = 0
    spill_bytes: int = 0
    # runtime join filters: filters built / pushed into scans, probe+scan
    # rows pruned, and filter-build wall time for this query
    rtf_built: int = 0
    rtf_pushed: int = 0
    rtf_rows_pruned: int = 0
    rtf_build_ms: float = 0.0
    # cluster fault tolerance: task retries (failure/eviction/dispatch),
    # speculative duplicates launched and how many of those won
    ft_retries: int = 0
    ft_speculative_launched: int = 0
    ft_speculative_won: int = 0
    # shuffle data plane: raw vs compressed wire bytes published by this
    # query's distributed tasks, consumer-side fetch wait + IPC decode
    # time, and tasks the memory governor deferred for capacity
    shuffle_wire_bytes: int = 0
    shuffle_wire_compressed: int = 0
    shuffle_fetch_wait_ms: float = 0.0
    shuffle_decode_ms: float = 0.0
    governor_deferred: int = 0
    # adaptive query execution: stage-boundary replanning decisions the
    # driver took from observed shuffle statistics, plus the per-shuffle
    # skew ratios and per-channel size reports they were based on (the
    # skew surface records even when adaptive execution is off)
    adaptive_coalesced: int = 0
    adaptive_split: int = 0
    adaptive_broadcast: int = 0
    adaptive_reordered: int = 0
    adaptive_events: List[dict] = field(default_factory=list)
    skew: List[dict] = field(default_factory=list)
    shuffle_channels: List[dict] = field(default_factory=list)
    # plan-invariant validator walks that ran for this query (optimizer
    # pass boundaries + job-graph stage checks)
    validated_passes: int = 0
    # whole-stage fusion: pipeline stages the splitter produced, Filter/
    # Project operators inlined into a consumer's program, and pipelines
    # that declined fusion at execution time (host-only expressions)
    fusion_stages: int = 0
    fusion_fused_ops: int = 0
    fusion_fallbacks: int = 0
    # streaming: the epoch this profile's trigger executed, the wall
    # time of its commit protocol (stage → checkpoint → finalize →
    # marker), the keyed-state rows retained after it, and whether the
    # trigger was a marker-skipped replay (-1 epoch = not a streaming
    # trigger; the block is omitted from to_dict/render then)
    streaming_epoch: int = -1
    streaming_commit_ms: float = 0.0
    streaming_state_rows: int = 0
    streaming_replayed: bool = False
    # result/fragment cache (exec/result_cache.py): how this query's
    # data was served — "" = cache not consulted, else hit | miss |
    # shared-scan | view — plus the cache fragments substituted into
    # the plan, the bytes they served, and concurrent-scan sharing
    # attach counts (followers riding another query's decode pass)
    cache_status: str = ""
    cache_fragments: List[str] = field(default_factory=list)
    cache_bytes_served: int = 0
    scan_share_attached: int = 0
    scan_share_saved: int = 0
    rows_out: int = 0
    slow: bool = False
    # critical-path attribution derived from the query's event stream
    # (analysis/timeline.py): {"total_ms", "categories", "chain",
    # "top"} — set by the cluster runner after the job completes, None
    # for queries without a distributed task timeline
    critical_path: Optional[dict] = None
    # operator metric trees (dicts, telemetry.OperatorMetrics.to_dict)
    operators: List[dict] = field(default_factory=list)
    # cluster mode: per-task operator metrics, one entry per
    # {stage, partition} of the last distributed job
    tasks: List[dict] = field(default_factory=list)
    trace_id: Optional[str] = None
    # the statement's span tree (tracing.Span, in order of completion):
    # one root ``query`` (under ``spark_connect:execute_plan`` when the
    # statement came over the wire), a child per phase, and below them
    # the executor's ``op.*``, ``dispatch``, ``compile``, ``sync``,
    # ``upload``, ``scan.decode`` and ``scan.wait``. At most _SPANS_MAX
    # are admitted, parents before children; the rest are counted
    spans: List = field(default_factory=list, repr=False)
    spans_dropped: int = 0
    # blocking device->host fetches on the execute path (host_sync) and
    # the wall time the host spent blocked in them
    host_syncs: int = 0
    sync_wait_ms: float = 0.0
    _spans_admitted: int = field(default=0, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)
    # stack of phases currently OPEN on this profile (nested executors
    # re-enter "execute"; re-entry must not double-count)
    _open: List[str] = field(default_factory=list, repr=False)

    # -- recording -----------------------------------------------------
    def add_phase(self, name: str, ms: float) -> None:
        with self._lock:
            self.phases[name] = self.phases.get(name, 0.0) + ms

    @contextmanager
    def phase(self, name: str):
        """Time a phase: a child span of that name, its duration added
        to ``phases[name]``."""
        with self._lock:
            reentered = name in self._open
            if not reentered:
                self._open.append(name)
        if reentered:
            # a nested executor re-opened the same phase (e.g. a scalar
            # subquery executing inside "execute"): the outer span
            # already covers this wall time
            yield
            return
        from . import tracing as tr
        sp = None
        try:
            with tr.span(name) as sp:
                yield
        finally:
            with self._lock:
                if name in self._open:
                    self._open.remove(name)
            self.add_phase(name, sp.ms if sp is not None else 0.0)

    # -- the span tree ---------------------------------------------------
    def admit_span(self, parent_recorded: bool = True) -> bool:
        """A span is opening under this profile: room for it? A span
        whose parent was dropped is dropped too, so every span kept has
        its parent."""
        with self._lock:
            if parent_recorded and self._spans_admitted < _SPANS_MAX:
                self._spans_admitted += 1
                return True
            self.spans_dropped += 1
            return False

    def add_span(self, span) -> None:
        with self._lock:
            self.spans.append(span)

    def _spans_named(self, name: str, under: Optional[str] = None) -> List:
        with self._lock:
            spans = list(self.spans)
        named = [s for s in spans if s.name == name]
        if under is None:
            return named
        by_id = {s.span_id: s for s in spans}

        def below(s) -> bool:
            parent = by_id.get(s.parent_id)
            while parent is not None:
                if parent.name == under:
                    return True
                parent = by_id.get(parent.parent_id)
            return False

        return [s for s in named if below(s)]

    def span_ms(self, name: str, under: Optional[str] = None) -> float:
        """Summed duration of the spans called ``name`` (those with an
        ancestor called ``under``, where given)."""
        return sum(s.ms for s in self._spans_named(name, under))

    def span_count(self, name: str, under: Optional[str] = None) -> int:
        return len(self._spans_named(name, under))

    def self_ms(self, name: str) -> float:
        """Summed self time of the spans called ``name``: a span's
        duration minus what its children on the same thread cover (a
        child on another thread runs beside its parent, not in it)."""
        with self._lock:
            spans = list(self.spans)
        children = {}
        for s in spans:
            children.setdefault(s.parent_id, []).append(s)
        total = 0.0
        for s in spans:
            if s.name != name:
                continue
            covered, at = 0, s.start_ns
            for c in sorted((c for c in children.get(s.span_id, ())
                             if c.thread_id == s.thread_id),
                            key=lambda c: c.start_ns):
                lo, hi = max(c.start_ns, at), min(c.end_ns, s.end_ns)
                if hi > lo:
                    covered += hi - lo
                    at = hi
            total += (s.end_ns - s.start_ns - covered) / 1e6
        return total

    def note_host_sync(self, ms: float) -> None:
        with self._lock:
            self.host_syncs += 1
            self.sync_wait_ms += ms

    def is_open(self, name: str) -> bool:
        with self._lock:
            return name in self._open

    def note_compile(self, hit: bool) -> None:
        with self._lock:
            if hit:
                self.compile_cache_hits += 1
            else:
                self.compile_cache_misses += 1

    def note_compile_time(self, seconds: float, key: str = "") -> None:
        ms = seconds * 1000.0
        with self._lock:
            self.compile_ms += ms
            self.compiled_programs += 1
            self.phases["compile"] = self.phases.get("compile", 0.0) + ms
            if len(self.compile_events) < 256:
                self.compile_events.append(
                    {"key": key[:120], "ms": round(ms, 3),
                     "source": "trace"})

    def note_retrace(self, cause: str, seconds: float) -> None:
        """One attributed compile (exec/retrace.py). First-ever cold
        compiles ride the causes breakdown only."""
        with self._lock:
            self.retrace_causes[cause] = \
                self.retrace_causes.get(cause, 0) + 1
            if cause != "first-ever":
                self.retrace_count += 1
                self.retrace_ms += seconds * 1000.0

    def note_admission_wait(self, waited_ms: float) -> None:
        with self._lock:
            self.admission_wait_ms += float(waited_ms)

    def note_backend_routes(self, routes) -> None:
        with self._lock:
            room = 64 - len(self.backend_routes)
            if room > 0 and routes:
                self.backend_routes.extend(list(routes)[:room])

    def note_transfer(self, nbytes: int) -> None:
        with self._lock:
            self.transfer_bytes += int(nbytes)

    def note_spill(self, nbytes: int) -> None:
        with self._lock:
            self.spill_bytes += int(nbytes)

    def note_rtf(self, built: int = 0, pushed: int = 0,
                 rows_pruned: int = 0, build_ms: float = 0.0) -> None:
        with self._lock:
            self.rtf_built += int(built)
            self.rtf_pushed += int(pushed)
            self.rtf_rows_pruned += int(rows_pruned)
            self.rtf_build_ms += float(build_ms)

    def note_fault_tolerance(self, retries: int = 0,
                             speculative_launched: int = 0,
                             speculative_won: int = 0) -> None:
        with self._lock:
            self.ft_retries += int(retries)
            self.ft_speculative_launched += int(speculative_launched)
            self.ft_speculative_won += int(speculative_won)

    def note_validated(self, passes: int = 1) -> None:
        with self._lock:
            self.validated_passes += int(passes)

    def note_shuffle(self, wire_bytes: int = 0,
                     wire_bytes_compressed: int = 0,
                     fetch_wait_s: float = 0.0, decode_s: float = 0.0,
                     governor_deferred: int = 0) -> None:
        with self._lock:
            self.shuffle_wire_bytes += int(wire_bytes)
            self.shuffle_wire_compressed += int(wire_bytes_compressed)
            self.shuffle_fetch_wait_ms += float(fetch_wait_s) * 1000.0
            self.shuffle_decode_ms += float(decode_s) * 1000.0
            self.governor_deferred += int(governor_deferred)

    def note_adaptive(self, coalesced: int = 0, split: int = 0,
                      broadcast: int = 0, reordered: int = 0,
                      events=None) -> None:
        with self._lock:
            self.adaptive_coalesced += int(coalesced)
            self.adaptive_split += int(split)
            self.adaptive_broadcast += int(broadcast)
            self.adaptive_reordered += int(reordered)
            if events:
                room = 128 - len(self.adaptive_events)
                if room > 0:
                    self.adaptive_events.extend(list(events)[:room])

    def note_skew(self, entries) -> None:
        with self._lock:
            room = 32 - len(self.skew)
            if room > 0 and entries:
                self.skew.extend(list(entries)[:room])

    def note_shuffle_channels(self, entries) -> None:
        with self._lock:
            room = 32 - len(self.shuffle_channels)
            if room > 0 and entries:
                self.shuffle_channels.extend(list(entries)[:room])

    def note_fusion(self, stages: int = 0, fused_ops: int = 0,
                    fallbacks: int = 0) -> None:
        with self._lock:
            self.fusion_stages += int(stages)
            self.fusion_fused_ops += int(fused_ops)
            self.fusion_fallbacks += int(fallbacks)

    def note_streaming(self, epoch: int, commit_ms: float = 0.0,
                       state_rows: int = 0,
                       replayed: bool = False) -> None:
        with self._lock:
            self.streaming_epoch = int(epoch)
            self.streaming_commit_ms = float(commit_ms)
            self.streaming_state_rows = int(state_rows)
            self.streaming_replayed = bool(replayed)

    def note_result_cache(self, status: str = "",
                          fragment: Optional[str] = None,
                          nbytes: int = 0, attached: int = 0,
                          saved: int = 0) -> None:
        """Result/fragment cache activity. Status precedence: a whole-
        query hit outranks a view read outranks a shared scan outranks
        a miss (fragment-only hits ride the fragments/bytes fields)."""
        order = {"": 0, "miss": 1, "shared-scan": 2, "view": 3, "hit": 4}
        with self._lock:
            if status and order.get(status, 0) >= \
                    order.get(self.cache_status, 0):
                self.cache_status = status
            if fragment and len(self.cache_fragments) < 32 \
                    and fragment not in self.cache_fragments:
                self.cache_fragments.append(fragment)
            self.cache_bytes_served += int(nbytes)
            self.scan_share_attached += int(attached)
            self.scan_share_saved += int(saved)

    def add_task(self, stage: int, partition: int, worker_id: str,
                 operators: List[dict], rows_out: int = 0) -> None:
        """Merge one distributed task's operator metrics (driver side)."""
        with self._lock:
            self.tasks = [t for t in self.tasks
                          if not (t["stage"] == stage
                                  and t["partition"] == partition)]
            self.tasks.append({
                "stage": int(stage), "partition": int(partition),
                "worker_id": worker_id, "rows_out": int(rows_out),
                "operators": operators})

    # -- shape ---------------------------------------------------------
    @property
    def total_ms(self) -> float:
        end = self.end_time or time.time()
        return max(0.0, (end - self.start_time) * 1000.0)

    def current_phase(self) -> str:
        with self._lock:
            if self._open:          # the phase actually RUNNING now
                return self._open[-1]
            names = [n for n in self.phases if n != "compile"]
        return names[-1] if names else "submitted"

    def phase_items(self) -> List:
        """(name, ms) in canonical order, then any custom phases."""
        with self._lock:
            phases = dict(self.phases)
        out = [(n, phases.pop(n)) for n in PHASES if n in phases]
        out.extend(sorted(phases.items()))
        return out

    def critical_path_summary(self) -> Optional[dict]:
        """Per-category wall-time attribution for the bench artifact:
        the event-derived critical path when the query ran distributed,
        else a phase-derived approximation for the local path (execute
        split into compile / fetch-wait / compute)."""
        if self.critical_path:
            return {"derived": False,
                    "categories": dict(
                        self.critical_path.get("categories", {}))}
        phases = {n: ms for n, ms in self.phase_items()}
        if not phases:
            return None
        execute = float(phases.get("execute", 0.0))
        compile_ms = min(execute, float(phases.get("compile", 0.0)))
        fetch_wait = min(execute - compile_ms,
                         float(self.shuffle_fetch_wait_ms))
        cats = {"compute": round(execute - compile_ms - fetch_wait, 3),
                "compile": round(compile_ms, 3),
                "fetch-wait": round(fetch_wait, 3)}
        return {"derived": True,
                "categories": {c: ms for c, ms in cats.items() if ms}}

    def to_dict(self) -> dict:
        return {
            "query_id": self.query_id,
            "statement": self.statement,
            "session": self.session,
            "tenant": self.tenant,
            "status": self.status,
            "error": self.error,
            "start_time": self.start_time,
            "total_ms": round(self.total_ms, 3),
            "phases": {n: round(ms, 3) for n, ms in self.phase_items()},
            "compile": {
                "cache_hits": self.compile_cache_hits,
                "cache_misses": self.compile_cache_misses,
                "compiled_programs": self.compiled_programs,
                "time_ms": round(self.compile_ms, 3),
                "events": list(self.compile_events),
            },
            "plan_fingerprint": self.plan_fingerprint,
            "retraces": {
                "count": self.retrace_count,
                "ms": round(self.retrace_ms, 3),
                "causes": dict(self.retrace_causes),
            },
            "admission_wait_ms": round(self.admission_wait_ms, 3),
            "anomaly_verdict": self.anomaly_verdict,
            "anomaly_excess_ms": round(self.anomaly_excess_ms, 3),
            "backends": list(self.backend_routes),
            "transfer_bytes": self.transfer_bytes,
            "spill_bytes": self.spill_bytes,
            "runtime_filter": {
                "built": self.rtf_built,
                "pushed": self.rtf_pushed,
                "rows_pruned": self.rtf_rows_pruned,
                "build_ms": round(self.rtf_build_ms, 3),
            },
            "fault_tolerance": {
                "retries": self.ft_retries,
                "speculative_launched": self.ft_speculative_launched,
                "speculative_won": self.ft_speculative_won,
            },
            "shuffle": {
                "wire_bytes": self.shuffle_wire_bytes,
                "wire_bytes_compressed": self.shuffle_wire_compressed,
                "fetch_wait_ms": round(self.shuffle_fetch_wait_ms, 3),
                "decode_ms": round(self.shuffle_decode_ms, 3),
                "governor_deferred": self.governor_deferred,
                "channels": list(self.shuffle_channels),
            },
            "adaptive": {
                "coalesced": self.adaptive_coalesced,
                "split": self.adaptive_split,
                "broadcast": self.adaptive_broadcast,
                "reordered": self.adaptive_reordered,
                "events": list(self.adaptive_events),
            },
            "skew": list(self.skew),
            "validated_passes": self.validated_passes,
            "fusion": {
                "stages": self.fusion_stages,
                "fused_ops": self.fusion_fused_ops,
                "fallbacks": self.fusion_fallbacks,
            },
            "streaming": {
                "epoch": self.streaming_epoch,
                "commit_ms": round(self.streaming_commit_ms, 3),
                "state_rows": self.streaming_state_rows,
                "replayed": self.streaming_replayed,
            } if self.streaming_epoch >= 0 else None,
            "result_cache": {
                "status": self.cache_status,
                "fragments": list(self.cache_fragments),
                "bytes_served": self.cache_bytes_served,
                "scan_share_attached": self.scan_share_attached,
                "scan_share_saved": self.scan_share_saved,
            } if self.cache_status or self.cache_fragments
            or self.scan_share_attached else None,
            "rows_out": self.rows_out,
            "slow": self.slow,
            "critical_path": self.critical_path,
            "operators": list(self.operators),
            "tasks": list(self.tasks),
            "trace_id": self.trace_id,
            "host_syncs": self.host_syncs,
            "sync_wait_ms": round(self.sync_wait_ms, 3),
            "spans": [sp.to_dict() for sp in list(self.spans)],
            "spans_dropped": self.spans_dropped,
        }

    def render(self) -> str:
        """Human text: the EXPLAIN ANALYZE phase header."""
        lines = [f"total: {self.total_ms:.1f}ms"]
        for name, ms in self.phase_items():
            extra = ""
            if name == "compile":
                extra = (f" (cache hits={self.compile_cache_hits} "
                         f"misses={self.compile_cache_misses})")
            lines.append(f"phase {name}: {ms:.1f}ms{extra}")
        if self.compile_cache_hits or self.compile_cache_misses:
            # per stage program: in-memory hit (nothing bound) or a
            # trace + XLA compile, counted directly
            lines.append(
                f"compile: memory_hits={self.compile_cache_hits} "
                f"misses={self.compiled_programs}")
        if self.retrace_causes:
            causes = " ".join(
                f"{c}={n}"
                for c, n in sorted(self.retrace_causes.items()))
            lines.append(f"retraces: {self.retrace_count} "
                         f"({causes}) {self.retrace_ms:.1f}ms")
        if self.anomaly_verdict:
            lines.append(f"anomaly: {self.anomaly_verdict} "
                         f"(+{self.anomaly_excess_ms:.1f}ms vs baseline)")
        if self.admission_wait_ms:
            lines.append(
                f"admission wait: {self.admission_wait_ms:.1f}ms")
        if self.backend_routes:
            routed = " ".join(
                f"s{r.get('stage')}={r.get('backend')}"
                f"({r.get('reason')})" for r in self.backend_routes)
            lines.append(f"backend: {routed}")
        if self.transfer_bytes:
            lines.append(f"device transfer: {self.transfer_bytes} bytes")
        if self.spill_bytes:
            lines.append(f"spill: {self.spill_bytes} bytes")
        if self.rtf_built or self.rtf_rows_pruned:
            lines.append(
                f"runtime filters: built={self.rtf_built} "
                f"pushed={self.rtf_pushed} "
                f"rows_pruned={self.rtf_rows_pruned} "
                f"build={self.rtf_build_ms:.1f}ms")
        if self.ft_retries or self.ft_speculative_launched:
            lines.append(
                f"fault tolerance: retries={self.ft_retries} "
                f"speculative={self.ft_speculative_launched} "
                f"won={self.ft_speculative_won}")
        if self.shuffle_wire_bytes or self.shuffle_fetch_wait_ms:
            ratio = (self.shuffle_wire_bytes
                     / self.shuffle_wire_compressed) \
                if self.shuffle_wire_compressed else 0.0
            line = (f"shuffle: wire={self.shuffle_wire_bytes}B "
                    f"compressed={self.shuffle_wire_compressed}B")
            if ratio:
                line += f" ({ratio:.2f}x)"
            line += (f" fetch_wait={self.shuffle_fetch_wait_ms:.1f}ms "
                     f"decode={self.shuffle_decode_ms:.1f}ms")
            if self.governor_deferred:
                line += f" governor_deferred={self.governor_deferred}"
            lines.append(line)
        for entry in self.skew:
            lines.append(
                f"skew: stage {entry.get('stage')} max/median="
                f"{entry.get('ratio')}x (max={entry.get('max_bytes')}B "
                f"median={entry.get('median_bytes')}B over "
                f"{entry.get('channels')} channels)")
        if (self.adaptive_coalesced or self.adaptive_split
                or self.adaptive_broadcast or self.adaptive_reordered):
            lines.append(
                f"adaptive: coalesced={self.adaptive_coalesced} "
                f"split={self.adaptive_split} "
                f"broadcast={self.adaptive_broadcast} "
                f"reordered={self.adaptive_reordered}")
        if self.fusion_stages:
            extra = f" ({self.fusion_fused_ops} ops inlined"
            if self.fusion_fallbacks:
                extra += f", {self.fusion_fallbacks} fallbacks"
            extra += ")"
            lines.append(f"fused: {self.fusion_stages} stages{extra}")
        if self.streaming_epoch >= 0:
            line = (f"streaming: epoch={self.streaming_epoch} "
                    f"commit={self.streaming_commit_ms:.1f}ms "
                    f"state_rows={self.streaming_state_rows}")
            if self.streaming_replayed:
                line += " (replayed)"
            lines.append(line)
        if self.cache_status or self.cache_fragments \
                or self.scan_share_attached:
            line = f"cache: {self.cache_status or 'miss'}"
            if self.cache_fragments:
                line += " fragments=" + ",".join(self.cache_fragments)
            if self.cache_bytes_served:
                line += f" bytes={self.cache_bytes_served}"
            if self.scan_share_attached:
                line += (f" attached={self.scan_share_attached} "
                         f"saved={self.scan_share_saved}")
            lines.append(line)
        if self.validated_passes:
            lines.append(f"validated: {self.validated_passes} passes")
        if self.critical_path:
            from .analysis.timeline import render_critical_path
            line = render_critical_path(self.critical_path)
            if line:
                lines.append(line)
        if self.tasks:
            from .telemetry import OperatorMetrics
            lines.append(f"tasks: {len(self.tasks)}")
            for t in sorted(self.tasks, key=lambda t: (t["stage"],
                                                       t["partition"])):
                lines.append(f"  stage {t['stage']} partition "
                             f"{t['partition']} ({t['worker_id']}) "
                             f"rows={t['rows_out']}")
                for op in t["operators"]:
                    lines.append(
                        OperatorMetrics.from_dict(op).render(indent=2))
        return "\n".join(lines)


class FlightRecorder:
    """Bounded in-process store of completed profiles.

    ``capacity`` newest profiles ride the ring; queries whose total time
    exceeded the slow threshold are retained separately in a
    ``slow_capacity``-bounded log so a burst of fast queries cannot
    evict the evidence of a slow one."""

    def __init__(self, capacity: int = 128, slow_capacity: int = 64):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, int(capacity)))
        self._slow: deque = deque(maxlen=max(1, int(slow_capacity)))
        self._active: "OrderedDict[str, QueryProfile]" = OrderedDict()

    def start(self, profile: QueryProfile) -> None:
        with self._lock:
            self._active[profile.query_id] = profile
            while len(self._active) > 1024:  # leak guard
                self._active.popitem(last=False)

    def finish(self, profile: QueryProfile) -> None:
        with self._lock:
            self._active.pop(profile.query_id, None)
            self._ring.append(profile)
            if profile.slow:
                self._slow.append(profile)

    def discard(self, profile: QueryProfile) -> None:
        with self._lock:
            self._active.pop(profile.query_id, None)

    def profiles(self) -> List[QueryProfile]:
        """Completed profiles, newest first: ring ∪ retained slow log."""
        with self._lock:
            seen = set()
            out = []
            for p in list(self._ring)[::-1] + list(self._slow)[::-1]:
                if id(p) not in seen:
                    seen.add(id(p))
                    out.append(p)
        return out

    def active(self) -> List[QueryProfile]:
        with self._lock:
            return list(self._active.values())

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()
            self._active.clear()


def _recorder_from_config() -> FlightRecorder:
    from .config import get as config_get
    try:
        cap = int(config_get("telemetry.profile_ring_capacity", 128))
        slow_cap = int(config_get("telemetry.slow_log_capacity", 64))
    except (TypeError, ValueError):
        cap, slow_cap = 128, 64
    return FlightRecorder(cap, slow_cap)


FLIGHT_RECORDER = _recorder_from_config()

_local = threading.local()

#: default slow-query threshold when the session conf doesn't set
#: spark.sail.telemetry.slowQueryMs (0 disables the slow log)
DEFAULT_SLOW_QUERY_MS = 1000.0


def current_profile() -> Optional[QueryProfile]:
    return getattr(_local, "profile", None)


def _slow_threshold_ms(conf) -> float:
    value = None
    if conf is not None:
        get = getattr(conf, "get", None)
        if get is not None:
            value = get("spark.sail.telemetry.slowQueryMs")
    if value is None:
        from .config import get as config_get
        value = config_get("telemetry.slow_query_ms",
                           DEFAULT_SLOW_QUERY_MS)
    try:
        return float(value)
    except (TypeError, ValueError):
        return DEFAULT_SLOW_QUERY_MS


@contextmanager
def profile_query(statement: str = "", session: str = "", conf=None,
                  enabled: bool = True, tenant: str = ""):
    """Open (or join) the thread's query profile.

    The OUTERMOST caller owns the profile: nested entries (commands that
    re-enter ``_execute_query``, subqueries, the cluster runner inside a
    session query) accumulate into the active profile instead of
    fragmenting one query into many records.

    ``enabled=False`` yields a detached throwaway profile that is never
    recorded — used for fetches of already-profiled results (a command's
    LocalRelation output) so they don't pollute the flight recorder."""
    existing = current_profile()
    if existing is not None:
        yield existing
        return
    if not enabled:
        yield QueryProfile(query_id="", statement=statement,
                           start_time=time.time())
        return
    profile = QueryProfile(
        query_id=uuid.uuid4().hex[:16],
        statement=(statement or "")[:_STATEMENT_MAX],
        session=session, tenant=tenant, start_time=time.time())
    from . import tracing as tr
    # the statement's root span: child of spark_connect:execute_plan
    # when there is one; the profile keeps it and all beneath it
    with tr.span("query", {"query.id": profile.query_id,
                           "rss_mb_start": _rss_mb()},
                 sink=profile) as root:
        profile.trace_id = root.trace_id
        _local.profile = profile
        FLIGHT_RECORDER.start(profile)
        try:
            from . import events as _events
            _events.emit(_events.EventType.QUERY_START,
                         query_id=profile.query_id,
                         trace_id=profile.trace_id,
                         statement=profile.statement[:200],
                         session=profile.session, tenant=profile.tenant)
        except Exception:  # noqa: BLE001 — telemetry must never break queries
            pass
        try:
            yield profile
        except BaseException as e:
            profile.status = "failed"
            profile.error = f"{type(e).__name__}: {e}"[:512]
            raise
        else:
            profile.status = "succeeded"
        finally:
            _local.profile = None
            profile.end_time = time.time()
            threshold = _slow_threshold_ms(conf)
            profile.slow = bool(threshold > 0
                                and profile.total_ms >= threshold)
            FLIGHT_RECORDER.finish(profile)
            root.attributes["rss_mb_end"] = _rss_mb()
            with tr.span("finalize"):
                _finalize(profile, threshold, root)


def _rss_mb() -> float:
    """This process's resident set now (one read of /proc/self/status);
    0.0 where the platform has no such file."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return 0.0


def _finalize(profile: QueryProfile, threshold_ms: float, root) -> None:
    """Post-completion export: registry counter, slow-query log line,
    and the phase breakdown as attributes of the ``query`` span, which
    the OTLP exporter receives when the span ends. Must never raise
    into the query path."""
    try:
        _record_metric("execution.query_count", 1,
                       session=profile.session or "default")
    except Exception:  # noqa: BLE001 — telemetry must never break queries
        pass
    try:
        # live SLO source: one query.latency observation per phase the
        # query entered plus the end-to-end wall under phase=total —
        # the histograms the per-tenant p50/p95/p99 surfaces
        # (system.telemetry.tenant_slo, /metrics) are computed from
        tenant = profile.tenant or "default"
        for name, ms in profile.phase_items():
            _record_metric("query.latency", ms / 1000.0,
                           tenant=tenant, phase=name)
        _record_metric("query.latency", profile.total_ms / 1000.0,
                       tenant=tenant, phase="total")
    except Exception:  # noqa: BLE001
        pass
    try:
        from . import events as _events
        _events.emit(_events.EventType.QUERY_END,
                     query_id=profile.query_id,
                     trace_id=profile.trace_id, status=profile.status,
                     rows_out=profile.rows_out,
                     total_ms=round(profile.total_ms, 3),
                     fingerprint=profile.plan_fingerprint,
                     spill_bytes=profile.spill_bytes,
                     cache_status=profile.cache_status)
    except Exception:  # noqa: BLE001
        pass
    try:
        # classify AFTER the query_end emit: the classifier cuts the
        # event stream at the query_end record, so the evidence set it
        # sees is exactly what a durable-log replay sees (events
        # racing in from workers after the cut are excluded on BOTH
        # sides). It still observes the profile into its baseline only
        # after classifying — an outlier must not pollute the baseline
        # it was judged against. The query span below carries the
        # verdict.
        from .analysis import anomaly as _anomaly
        _anomaly.on_profile_complete(profile)
    except Exception:  # noqa: BLE001
        pass
    try:
        if profile.slow:
            logger.warning(
                "slow query %s: %.0fms (threshold %.0fms): %s",
                profile.query_id, profile.total_ms, threshold_ms,
                profile.statement[:200])
        from . import tracing as tr
        if tr._exporter() is not None:
            attrs = root.attributes
            attrs.update({
                "query.status": profile.status,
                "query.rows_out": profile.rows_out,
                "query.compile.cache_hits": profile.compile_cache_hits,
                "query.compile.cache_misses":
                    profile.compile_cache_misses,
                "query.transfer_bytes": profile.transfer_bytes,
                "query.spill_bytes": profile.spill_bytes,
                "query.runtime_filter.built": profile.rtf_built,
                "query.runtime_filter.rows_pruned":
                    profile.rtf_rows_pruned,
                "query.adaptive.coalesced": profile.adaptive_coalesced,
                "query.adaptive.split": profile.adaptive_split,
                "query.adaptive.broadcast": profile.adaptive_broadcast,
                "query.adaptive.reordered": profile.adaptive_reordered,
                "query.plan_fingerprint": profile.plan_fingerprint,
                "query.retrace_count": profile.retrace_count,
                "query.anomaly.verdict": profile.anomaly_verdict,
                "query.anomaly.excess_ms":
                    round(profile.anomaly_excess_ms, 3)})
            if profile.cache_status or profile.cache_fragments \
                    or profile.scan_share_attached:
                attrs["query.result_cache.status"] = \
                    profile.cache_status or "miss"
                attrs["query.result_cache.bytes_served"] = \
                    profile.cache_bytes_served
                attrs["query.result_cache.fragments"] = \
                    ",".join(profile.cache_fragments)
                attrs["query.scan_share.attached"] = \
                    profile.scan_share_attached
            for name, ms in profile.phase_items():
                attrs[f"query.phase.{name}_ms"] = round(ms, 3)
            if profile.critical_path:
                # the gating chain rides the query span so the OTLP
                # view and the event log cross-reference
                attrs["query.critical_path"] = json.dumps(
                    profile.critical_path, default=str)
    except Exception:  # noqa: BLE001
        pass


# ---------------------------------------------------------------------------
# recording helpers for the executors (cheap no-ops without a profile)
# ---------------------------------------------------------------------------

@contextmanager
def maybe_phase(name: str):
    """Time a phase on the current profile; transparent without one."""
    profile = current_profile()
    if profile is None:
        yield
        return
    with profile.phase(name):
        yield


def host_sync(site: str, tree):
    """THE blocking device->host fetch of the execute path:
    ``jax.device_get(tree)`` inside a ``sync`` span (attrs ``site``,
    ``bytes``), counted on the current profile (``host_syncs``,
    ``sync_wait_ms``). The sync-point lint (analysis/lints.py) holds
    callers of this to the same allowlist as ``device_get`` itself."""
    import jax
    from . import tracing as tr
    with tr.span("sync", {"site": site}) as sp:
        out = jax.device_get(tree)
        sp.attributes["bytes"] = sum(
            int(getattr(x, "nbytes", 0))
            for x in jax.tree_util.tree_leaves(out))
    profile = current_profile()
    if profile is not None:
        profile.note_host_sync(sp.ms)
    return out


def note_compile_cache(hit: bool) -> None:
    try:
        _record_metric("execution.compile.cache_hit_count" if hit
                       else "execution.compile.cache_miss_count", 1)
    except Exception:  # noqa: BLE001
        pass
    profile = current_profile()
    if profile is not None:
        profile.note_compile(hit)


def note_compile_time(seconds: float, key: str = "") -> None:
    try:
        _record_metric("execution.compile.compile_time", float(seconds))
    except Exception:  # noqa: BLE001
        pass
    try:
        from . import events as _events
        _events.emit(_events.EventType.COMPILE, key=key[:120],
                     ms=round(float(seconds) * 1000.0, 3),
                     source="trace")
    except Exception:  # noqa: BLE001
        pass
    profile = current_profile()
    if profile is not None:
        profile.note_compile_time(seconds, key)


def note_retrace(cause: str, seconds: float) -> None:
    """One attributed compile (exec/retrace.py) on the current query;
    transparent without a profile (the event/metric surfaces still
    record it)."""
    profile = current_profile()
    if profile is not None:
        profile.note_retrace(cause, seconds)


def note_admission_wait(waited_ms: float) -> None:
    """Admission-queue wall time the current query paid before running
    (exec/admission.py)."""
    profile = current_profile()
    if profile is not None:
        profile.note_admission_wait(waited_ms)


def note_plan_fingerprint(fp: str) -> None:
    """Stamp the plan fingerprint the baseline/anomaly plane keys on."""
    profile = current_profile()
    if profile is not None and fp:
        profile.plan_fingerprint = fp


def note_backend_routes(routes) -> None:
    """Per-stage backend routing decisions (exec/router.py) taken for
    the current query's plan."""
    profile = current_profile()
    if profile is not None:
        profile.note_backend_routes(routes)


def note_result_cache(status: str = "", fragment: Optional[str] = None,
                      nbytes: int = 0, attached: int = 0,
                      saved: int = 0) -> None:
    """Result/fragment cache activity on the current query (scan-path
    executors call this; transparent without a profile)."""
    profile = current_profile()
    if profile is not None:
        profile.note_result_cache(status, fragment=fragment,
                                  nbytes=nbytes, attached=attached,
                                  saved=saved)


def note_transfer_bytes(nbytes: int) -> None:
    profile = current_profile()
    if profile is not None:
        profile.note_transfer(nbytes)


def note_spill_bytes(nbytes: int) -> None:
    profile = current_profile()
    if profile is not None:
        profile.note_spill(nbytes)


def note_runtime_filter(built: int = 0, pushed: int = 0,
                        rows_pruned: int = 0,
                        build_ms: float = 0.0) -> None:
    profile = current_profile()
    if profile is not None:
        profile.note_rtf(built=built, pushed=pushed,
                         rows_pruned=rows_pruned, build_ms=build_ms)


def note_plan_validated(passes: int = 1) -> None:
    """One plan-invariant validator walk completed for this query."""
    profile = current_profile()
    if profile is not None:
        profile.note_validated(passes)


def note_fusion(stages: int = 0, fused_ops: int = 0,
                fallbacks: int = 0) -> None:
    """Whole-stage fusion accounting for the current query."""
    profile = current_profile()
    if profile is not None:
        profile.note_fusion(stages=stages, fused_ops=fused_ops,
                            fallbacks=fallbacks)


def last_profile() -> Optional[QueryProfile]:
    """Most recently completed profile (bench / tests convenience)."""
    profiles = FLIGHT_RECORDER.profiles()
    return profiles[0] if profiles else None
