"""OTLP exporter failure modes: an unreachable collector must never
block or slow the query path, the bounded buffer drops with accounting,
and a failed operator's ``op.<name>`` span ends not ok."""

import json
import socket
import time

import pytest

from sail_tpu import metrics as gm
from sail_tpu import tracing as tr


def _unreachable_endpoint() -> str:
    # grab a port nobody is listening on
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


@pytest.fixture(autouse=True)
def clean_registry():
    gm.REGISTRY.reset()
    yield
    gm.REGISTRY.reset()


def test_span_exit_nonblocking_with_unreachable_collector():
    tr.configure_exporter(_unreachable_endpoint())
    try:
        t0 = time.perf_counter()
        for _ in range(50):
            with tr.span("hot-path"):
                pass
        elapsed = time.perf_counter() - t0
        # span exit only appends to the in-memory buffer; 50 spans must
        # complete orders of magnitude under any network timeout
        assert elapsed < 1.0, elapsed
    finally:
        tr.configure_exporter(None)


def test_flush_swallows_connection_errors():
    tr.configure_exporter(_unreachable_endpoint())
    try:
        with tr.span("doomed"):
            pass
        tr.log_event("INFO", "doomed log")
        gm.record("query.latency", 0.1, tenant="t", phase="total")
        tr.flush()  # must not raise despite the dead collector —
        # including the histogram-datapoint metrics payload
    finally:
        tr.configure_exporter(None)


def test_histogram_payload_shape_survives_serialization():
    """The histogram OTLP datapoint shape (bucketCounts + explicit
    bounds + sum + count) must serialize to JSON exactly as the
    /v1/metrics endpoint expects — the failure path posts this same
    payload, so a malformed shape would silently drop under outage."""
    gm.record("query.latency", 0.03, tenant="t", phase="total")
    gm.record("execution.spill_count", 1, kind="join")
    payload = gm.REGISTRY.otlp_payload()
    body = json.loads(json.dumps(payload))  # round-trippable
    metrics = {m["name"]: m
               for m in body["resourceMetrics"][0]
               ["scopeMetrics"][0]["metrics"]}
    h = metrics["query.latency"]["histogram"]
    assert h["aggregationTemporality"] == 2
    dp = h["dataPoints"][0]
    assert dp["count"] == "1" and abs(dp["sum"] - 0.03) < 1e-12
    assert len(dp["bucketCounts"]) == len(dp["explicitBounds"]) + 1
    assert all(isinstance(c, str) for c in dp["bucketCounts"])
    assert "sum" in metrics["execution.spill_count"]  # counters intact


def test_shutdown_terminates_promptly():
    exp = tr.OtlpHttpExporter(_unreachable_endpoint(),
                              flush_interval_s=3600.0)
    exp.add(tr.Span("0" * 32, "1" * 16, None, "s",
                    time.time_ns(), time.time_ns()))
    t0 = time.perf_counter()
    exp.shutdown()
    assert time.perf_counter() - t0 < 5.0
    assert exp._stop.is_set()


def test_bounded_buffer_counts_drops():
    # flush_interval 3600: the background thread never drains the buffer
    # during the test, so the overflow path is deterministic
    exp = tr.OtlpHttpExporter(_unreachable_endpoint(),
                              flush_interval_s=3600.0, max_batch=2)
    cap = 16 * exp.max_batch
    try:
        for i in range(cap + 1):
            exp.add(tr.Span("0" * 32, "1" * 16, None, f"s{i}",
                            time.time_ns(), time.time_ns()))
        assert exp.dropped["spans"] == 8 * exp.max_batch
        assert len(exp._buf) <= cap
        for i in range(cap + 1):
            exp.add_log(tr.LogEvent(time.time_ns(), 9, "INFO", f"l{i}"))
        assert exp.dropped["logs"] == 8 * exp.max_batch
        snap = {(r["name"], r["attributes"]): r["value"]
                for r in gm.REGISTRY.snapshot()}
        assert snap[("telemetry.export.dropped_count",
                     json.dumps({"signal": "spans"}))] == 16
        assert snap[("telemetry.export.dropped_count",
                     json.dumps({"signal": "logs"}))] == 16
    finally:
        exp.shutdown()


def _overflow(exp, signal: str, times: int = 1):
    for _ in range(times):
        for i in range(16 * exp.max_batch + 1):
            if signal == "spans":
                exp.add(tr.Span("0" * 32, "1" * 16, None, f"s{i}",
                                time.time_ns(), time.time_ns()))
            else:
                exp.add_log(tr.LogEvent(time.time_ns(), 9, "INFO",
                                        f"l{i}"))


def test_drop_warning_once_per_signal_per_process(caplog):
    """The overflow warning dedupes per SIGNAL per process lifetime:
    repeat bursts of the same signal never re-warn (the dropped_count
    metric carries the tally), each signal warns independently, and a
    fresh exporter instance in the same process stays silent."""
    import logging
    tr.OtlpHttpExporter.reset_drop_warnings()
    exp = tr.OtlpHttpExporter(_unreachable_endpoint(),
                              flush_interval_s=3600.0, max_batch=2)
    try:
        with caplog.at_level(logging.WARNING, logger="sail_tpu.tracing"):
            _overflow(exp, "spans", times=3)  # three bursts, one warning
        warns = [r for r in caplog.records
                 if "buffer overflow" in r.getMessage()]
        assert len(warns) == 1
        assert "spans" in warns[0].getMessage()
        # the OTHER signal still gets its own one warning
        with caplog.at_level(logging.WARNING, logger="sail_tpu.tracing"):
            _overflow(exp, "logs", times=2)
        warns = [r for r in caplog.records
                 if "buffer overflow" in r.getMessage()]
        assert len(warns) == 2
        assert "logs" in warns[1].getMessage()
    finally:
        exp.shutdown()
    # a NEW exporter instance in the same process must not re-warn for
    # either signal — the dedupe is per process lifetime, not per
    # instance
    exp2 = tr.OtlpHttpExporter(_unreachable_endpoint(),
                               flush_interval_s=3600.0, max_batch=2)
    try:
        with caplog.at_level(logging.WARNING, logger="sail_tpu.tracing"):
            _overflow(exp2, "spans")
            _overflow(exp2, "logs")
        warns = [r for r in caplog.records
                 if "buffer overflow" in r.getMessage()]
        assert len(warns) == 2  # unchanged
        # drops still COUNT even though the warning deduped
        assert exp2.dropped["spans"] > 0 and exp2.dropped["logs"] > 0
    finally:
        exp2.shutdown()


class _Sink:
    """Keeps the finished spans of the tree it roots, as a
    ``QueryProfile`` does."""

    def __init__(self):
        self.spans = []

    def admit_span(self, parent_recorded):
        return parent_recorded

    def add_span(self, span):
        self.spans.append(span)


def _op_span(sink, name):
    (span,) = [s for s in sink.spans if s.name == "op." + name]
    return span


def test_operator_span_fails_with_the_exception():
    from sail_tpu import telemetry as tel

    sink = _Sink()
    with pytest.raises(ValueError, match="boom"):
        with tr.span("query", sink=sink):
            with tel.collect_metrics() as collected:
                with tel.operator_span("Exploding"):
                    raise ValueError("boom")
    assert _op_span(sink, "Exploding").status_ok is False
    assert collected == []  # an aborted operator records no metrics


def test_operator_span_success_ends_ok_and_collects():
    from sail_tpu import telemetry as tel

    sink = _Sink()
    with tr.span("query", sink=sink):
        with tel.collect_metrics() as collected:
            with tel.operator_span("Fine") as m:
                m.output_rows = 1
    assert _op_span(sink, "Fine").status_ok is True
    assert len(collected) == 1 and collected[0].output_rows == 1
