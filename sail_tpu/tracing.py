"""Distributed tracing: spans, W3C traceparent propagation, OTLP export.

Reference role: sail-telemetry's fastrace spans with client/server tower
layers propagating trace context across RPCs and the OTLP pipeline
(crates/sail-telemetry/src/layers/{client,server}.rs, src/telemetry.rs:
47-120). The image ships only ``opentelemetry-api`` (no SDK, no exporter),
so this is a from-scratch implementation:

- ``span(name)``: thread-local span stack; ids follow the W3C trace
  context format. THE recorder of the repository: a finished span is
  kept by the ``QueryProfile`` whose ``query`` span it lies under
  (profiler.py), mirrored as a ``jax.profiler.TraceAnnotation``
  ``sail:<name>`` while a profiler session is active (so it lies on the
  xplane's host plane, on the device trace's clock), and exported to
  OTLP when an endpoint is configured. Times are the wall clock read
  once, when the tree's root opens, plus monotonic offsets.
- ``inject_context()`` / ``extract_context()``: ``traceparent`` metadata
  for gRPC calls — one cluster query yields ONE connected trace across
  driver and workers.
- ``OtlpHttpExporter``: background-batched POST of OTLP/HTTP **JSON**
  (``/v1/traces``) — the encoding every OTLP collector accepts alongside
  protobuf. Configured via ``telemetry.otlp_endpoint``.
"""

from __future__ import annotations

import json
import logging
import os
import secrets
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_local = threading.local()
_lock = threading.Lock()

#: spans finished before any profile adopted their parent (``rpc.decode``
#: under ``spark_connect:execute_plan``) wait on the parent's context
_ORPHANS_MAX = 8


@dataclass
class Span:
    trace_id: str          # 32 hex chars
    span_id: str           # 16 hex chars
    parent_id: Optional[str]
    name: str
    start_ns: int          # the wall clock (time.time_ns()) when the
    end_ns: int = 0        # tree's root opened + a monotonic offset
    attributes: Dict[str, object] = field(default_factory=dict)
    status_ok: bool = True
    thread_id: int = 0

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "trace_id": self.trace_id,
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "thread_id": self.thread_id, "ok": self.status_ok,
                "attributes": dict(self.attributes)}


@dataclass
class SpanContext:
    """What a child needs of its open parent: the ids that cross an RPC
    (``trace_id``, ``span_id``) and, in-process, the ``sink`` that keeps
    finished spans (a ``QueryProfile``), whether this span was admitted
    under the sink's bound, and the open ``span`` itself."""

    trace_id: str
    span_id: str
    sink: object = None
    recorded: bool = True
    span: Optional[Span] = None
    orphans: Optional[List[Span]] = None
    #: (time.time_ns(), time.perf_counter_ns()) read together when the
    #: tree's first in-process span opened: every span beneath it is that
    #: wall-clock instant plus a monotonic offset, so children lie inside
    #: their parents whatever the wall clock does meanwhile
    anchor: Optional[Tuple[int, int]] = None


def _current() -> Optional[SpanContext]:
    stack = getattr(_local, "span_stack", None)
    return stack[-1] if stack else None


def current_context() -> Optional[SpanContext]:
    """The thread's open span, to hand to another thread as ``parent``."""
    return _current()


def current_trace_id() -> Optional[str]:
    ctx = _current()
    return ctx.trace_id if ctx else None


def set_attribute(key: str, value) -> None:
    """Attach an attribute to the thread's open span, if there is one."""
    ctx = _current()
    if ctx is not None and ctx.span is not None:
        ctx.span.attributes[key] = value


def _annotation(name: str, sink, span_id: str):
    """The span on the xplane's host plane, on the device trace's own
    clock, while a ``jax.profiler`` session is active; a flag check
    otherwise."""
    from jax.profiler import TraceAnnotation
    if not TraceAnnotation.is_enabled():
        return None
    ann = TraceAnnotation("sail:" + name, span_id=span_id,
                          query_id=getattr(sink, "query_id", "") or "")
    ann.__enter__()
    return ann


@contextmanager
def span(name: str, attributes: Optional[Dict] = None,
         parent: Optional[SpanContext] = None, sink=None,
         backdate_ns: int = 0):
    """Open a span; nests under the thread's current span (or an explicit
    ``parent``: one extracted from RPC metadata, or another thread's
    ``current_context()``).

    The one recorder: a finished span is kept by its ``sink`` (the
    ``QueryProfile`` that opened the enclosing ``query`` span passes
    itself; descendants inherit it), lies on the xplane as a
    ``sail:<name>`` annotation while a profiler session is active, and
    goes to the OTLP exporter when an endpoint is configured. ``sink``
    also adopts the thread's open ancestors, so that the RPC span
    around a query lands in the query's profile when it ends.
    ``backdate_ns`` starts the span that long ago, for work known to
    have happened only once it is over (a jit call that compiled)."""
    stack = getattr(_local, "span_stack", None)
    if stack is None:
        stack = _local.span_stack = []
    if parent is None:
        parent = stack[-1] if stack else None
    recorded = True
    if sink is not None:
        for ctx in stack:
            if ctx.sink is None:
                ctx.sink = sink
                for orphan in ctx.orphans or ():
                    sink.add_span(orphan)
                ctx.orphans = None
    elif parent is not None and parent.sink is not None:
        sink = parent.sink
        recorded = sink.admit_span(parent.recorded)
    trace_id = parent.trace_id if parent else secrets.token_hex(16)
    now = time.perf_counter_ns()
    anchor = parent.anchor if parent is not None and parent.anchor \
        else (time.time_ns(), now)
    s = Span(trace_id=trace_id, span_id=secrets.token_hex(8),
             parent_id=parent.span_id if parent else None,
             name=name,
             start_ns=anchor[0] + (now - anchor[1]) - backdate_ns,
             attributes=dict(attributes or {}),
             thread_id=threading.get_ident())
    ctx = SpanContext(trace_id, s.span_id, sink, recorded, s,
                      anchor=anchor)
    annotation = _annotation(name, sink, s.span_id)
    stack.append(ctx)
    try:
        yield s
    except BaseException:
        s.status_ok = False
        raise
    finally:
        stack.pop()
        s.end_ns = anchor[0] + (time.perf_counter_ns() - anchor[1])
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if ctx.sink is not None:
            if recorded:
                ctx.sink.add_span(s)
        elif parent is not None and parent.span is not None:
            # no profile yet: the parent keeps it for the one that
            # adopts it
            if parent.orphans is None:
                parent.orphans = []
            if len(parent.orphans) < _ORPHANS_MAX:
                parent.orphans.append(s)
        exporter = _exporter()
        if exporter is not None:
            exporter.add(s)


# ---------------------------------------------------------------------------
# W3C trace context over gRPC metadata
# ---------------------------------------------------------------------------

def inject_context(parent: Optional[SpanContext] = None
                   ) -> List[Tuple[str, str]]:
    """Metadata to attach to an outgoing RPC (client layer). An
    explicit ``parent`` overrides the thread-local span — RPCs issued
    from threads that never opened a span (the driver actor thread, a
    fetch pool worker) still propagate the owning query's context."""
    ctx = parent if parent is not None else _current()
    if ctx is None:
        return []
    return [("traceparent", f"00-{ctx.trace_id}-{ctx.span_id}-01")]


def extract_context(metadata) -> Optional[SpanContext]:
    """Parse ``traceparent`` from incoming RPC metadata (server layer)."""
    if metadata is None:
        return None
    for key, value in metadata:
        if key.lower() == "traceparent":
            parts = value.split("-")
            if len(parts) == 4 and len(parts[1]) == 32 and len(parts[2]) == 16:
                return SpanContext(parts[1], parts[2])
    return None


# ---------------------------------------------------------------------------
# OTLP/HTTP JSON export
# ---------------------------------------------------------------------------

@dataclass
class LogEvent:
    time_ns: int
    severity_number: int
    severity_text: str
    body: str
    attributes: Dict[str, object] = field(default_factory=dict)
    trace_id: Optional[str] = None
    span_id: Optional[str] = None


class OtlpHttpExporter:
    """Batched OTLP/HTTP JSON exporter: spans to ``/v1/traces`` and log
    records to ``/v1/logs`` (the reference's log-export pipeline,
    sail-telemetry src/telemetry.rs)."""

    #: signals that already warned about buffer overflow — CLASS level,
    #: so the warning dedupes per signal per PROCESS lifetime (a flappy
    #: collector must not re-warn per exporter instance or per outage
    #: burst; the dropped_count metric carries the ongoing tally)
    _warned_signals: "set[str]" = set()

    def __init__(self, endpoint: str, service_name: str = "sail-tpu",
                 flush_interval_s: float = 1.0, max_batch: int = 512):
        self.endpoint = endpoint.rstrip("/")
        self.service_name = service_name
        self.max_batch = max_batch
        self._buf: List[Span] = []
        self._log_buf: List[LogEvent] = []
        self._buf_lock = threading.Lock()
        self.dropped = {"spans": 0, "logs": 0}
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, args=(flush_interval_s,), daemon=True)
        self._thread.start()

    @classmethod
    def reset_drop_warnings(cls):
        """Forget which signals already warned (tests only)."""
        cls._warned_signals.clear()

    def _note_dropped(self, signal: str, count: int):
        """Account buffer-overflow drops: registry counter + ONE
        warning per signal per process lifetime (called outside the
        buffer lock — the warning itself re-enters add_log through the
        stdlib bridge, and a repeat warning per burst would flood the
        very pipeline that is already dropping)."""
        try:
            from .metrics import record as _record_metric
            _record_metric("telemetry.export.dropped_count", count,
                           signal=signal)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            pass
        if signal not in OtlpHttpExporter._warned_signals:
            OtlpHttpExporter._warned_signals.add(signal)
            logging.getLogger("sail_tpu.tracing").warning(
                "OTLP export buffer overflow: dropped %d %s "
                "(collector unreachable or slow); further %s drops "
                "count in telemetry.export.dropped_count without "
                "re-warning", count, signal, signal)

    def add(self, s: Span):
        """Enqueue only — span exit must never do network I/O on the hot
        path; the background flush thread posts. Bounded buffer drops the
        oldest spans under sustained collector outage."""
        with self._buf_lock:
            self._buf.append(s)
            dropped = 0
            if len(self._buf) > 16 * self.max_batch:
                dropped = 8 * self.max_batch
                del self._buf[:dropped]
                self.dropped["spans"] += dropped
        if dropped:
            self._note_dropped("spans", dropped)

    def add_log(self, ev: LogEvent):
        with self._buf_lock:
            self._log_buf.append(ev)
            dropped = 0
            if len(self._log_buf) > 16 * self.max_batch:
                dropped = 8 * self.max_batch
                del self._log_buf[:dropped]
                self.dropped["logs"] += dropped
        if dropped:
            self._note_dropped("logs", dropped)

    def _loop(self, interval: float):
        while not self._stop.wait(interval):
            self.flush()

    def flush(self):
        with self._buf_lock:
            batch, self._buf = self._buf, []
            logs, self._log_buf = self._log_buf, []
        if batch:
            self._post(batch)
        if logs:
            self._post_logs(logs)
        from .metrics import REGISTRY
        if REGISTRY.take_dirty():
            self._send("/v1/metrics",
                       REGISTRY.otlp_payload(self.service_name))

    def shutdown(self):
        self._stop.set()
        self.flush()

    @staticmethod
    def _attr(k: str, v) -> dict:
        if isinstance(v, bool):
            value = {"boolValue": v}
        elif isinstance(v, int):
            value = {"intValue": str(v)}
        elif isinstance(v, float):
            value = {"doubleValue": v}
        else:
            value = {"stringValue": str(v)}
        return {"key": k, "value": value}

    def _send(self, suffix: str, payload: dict):
        import urllib.request

        req = urllib.request.Request(
            self.endpoint + suffix,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            urllib.request.urlopen(req, timeout=10).read()
        except Exception:  # noqa: BLE001 — telemetry must never break queries
            pass

    def _post(self, batch: List[Span]):
        payload = {
            "resourceSpans": [{
                "resource": {"attributes": [
                    self._attr("service.name", self.service_name)]},
                "scopeSpans": [{
                    "scope": {"name": "sail_tpu"},
                    "spans": [{
                        "traceId": s.trace_id,
                        "spanId": s.span_id,
                        **({"parentSpanId": s.parent_id}
                           if s.parent_id else {}),
                        "name": s.name,
                        "kind": 1,
                        "startTimeUnixNano": str(s.start_ns),
                        "endTimeUnixNano": str(s.end_ns),
                        "attributes": [self._attr(k, v)
                                       for k, v in s.attributes.items()],
                        "status": {"code": 1 if s.status_ok else 2},
                    } for s in batch],
                }],
            }],
        }
        self._send("/v1/traces", payload)

    def _post_logs(self, logs: List[LogEvent]):
        payload = {
            "resourceLogs": [{
                "resource": {"attributes": [
                    self._attr("service.name", self.service_name)]},
                "scopeLogs": [{
                    "scope": {"name": "sail_tpu"},
                    "logRecords": [{
                        "timeUnixNano": str(ev.time_ns),
                        "severityNumber": ev.severity_number,
                        "severityText": ev.severity_text,
                        "body": {"stringValue": ev.body},
                        "attributes": [self._attr(k, v)
                                       for k, v in ev.attributes.items()],
                        **({"traceId": ev.trace_id} if ev.trace_id else {}),
                        **({"spanId": ev.span_id} if ev.span_id else {}),
                    } for ev in logs],
                }],
            }],
        }
        self._send("/v1/logs", payload)


# severityNumber per the OTLP spec
_SEVERITY = {"DEBUG": 5, "INFO": 9, "WARNING": 13, "WARN": 13,
             "ERROR": 17, "CRITICAL": 21, "FATAL": 21}


def log_event(severity: str, body: str, **attributes):
    """Emit one log record to the OTLP pipeline (no-op when no exporter
    is configured). Records correlate with the active span."""
    exporter = _exporter()
    if exporter is None:
        return
    ctx = _current()
    exporter.add_log(LogEvent(
        time_ns=time.time_ns(),
        severity_number=_SEVERITY.get(severity.upper(), 9),
        severity_text=severity.upper(), body=body,
        attributes=attributes,
        trace_id=ctx.trace_id if ctx else None,
        span_id=ctx.span_id if ctx else None))


class OtlpLogHandler(logging.Handler):
    """stdlib ``logging`` bridge: attach to a logger and every record
    flows into the OTLP log export."""

    def emit(self, record):
        try:
            log_event(record.levelname, record.getMessage(),
                      logger=record.name)
        except Exception:  # noqa: BLE001 — telemetry must never raise
            pass


def install_log_handler(logger_name: str = "sail_tpu"):
    """Route the engine's stdlib logger into the OTLP pipeline."""
    logger = logging.getLogger(logger_name)
    if logger.level == logging.NOTSET:
        # without an explicit level the logger inherits root's WARNING
        # and INFO/DEBUG records would never reach the handler
        logger.setLevel(logging.DEBUG)
    for h in logger.handlers:
        if isinstance(h, OtlpLogHandler):
            return h
    h = OtlpLogHandler()
    logger.addHandler(h)
    return h


_EXPORTER: Optional[OtlpHttpExporter] = None
_EXPORTER_INIT = False


def _exporter() -> Optional[OtlpHttpExporter]:
    global _EXPORTER, _EXPORTER_INIT
    if not _EXPORTER_INIT:
        with _lock:
            if not _EXPORTER_INIT:
                from .config import get as config_get
                endpoint = os.environ.get("SAIL_TELEMETRY__OTLP_ENDPOINT") \
                    or str(config_get("telemetry.otlp_endpoint", "") or "")
                if endpoint:
                    _EXPORTER = OtlpHttpExporter(endpoint)
                    install_log_handler()
                _EXPORTER_INIT = True
    return _EXPORTER


def configure_exporter(endpoint: Optional[str]):
    """Explicit (re)configuration — used by tests and the CLI."""
    global _EXPORTER, _EXPORTER_INIT
    with _lock:
        if _EXPORTER is not None:
            _EXPORTER.shutdown()
        _EXPORTER = OtlpHttpExporter(endpoint) if endpoint else None
        if _EXPORTER is not None:
            install_log_handler()
        _EXPORTER_INIT = True


def flush():
    if _EXPORTER is not None:
        _EXPORTER.flush()
