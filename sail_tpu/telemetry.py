"""Telemetry: spans, per-operator execution metrics, EXPLAIN ANALYZE.

Reference role: sail-telemetry — fastrace spans around actors/RPC plus
DataFusion operator metrics harvested into OTel gauges per {job, stage,
partition, operator} (SURVEY.md §5). Here the executor wraps every operator
in an ``op.<name>`` span (tracing.py, the one recorder) and, under EXPLAIN
ANALYZE, a metrics recorder (rows out, batch capacity, wall time).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from .metrics import record as _record_metric


@dataclass
class OperatorMetrics:
    operator: str
    detail: str = ""
    output_rows: int = 0
    capacity: int = 0
    elapsed_ms: float = 0.0
    children: List["OperatorMetrics"] = field(default_factory=list)
    # free-form key=value counters (e.g. prefetch overlap stats); rendered
    # after the standard fields so EXPLAIN ANALYZE surfaces them
    extra: Dict[str, object] = field(default_factory=dict)

    def render(self, indent: int = 0) -> str:
        pad = "  " * indent
        more = "".join(f" {k}={v}" for k, v in self.extra.items())
        line = (f"{pad}{self.operator}{' ' + self.detail if self.detail else ''}"
                f"  [rows={self.output_rows} cap={self.capacity} "
                f"time={self.elapsed_ms:.1f}ms{more}]")
        return "\n".join([line] + [c.render(indent + 1) for c in self.children])

    def to_dict(self) -> dict:
        """JSON-safe shape — the wire format for cluster task metrics and
        the EXPLAIN ANALYZE FORMAT JSON operator tree."""
        out = {"operator": self.operator, "output_rows": self.output_rows,
               "capacity": self.capacity,
               "elapsed_ms": round(self.elapsed_ms, 3)}
        if self.detail:
            out["detail"] = self.detail
        if self.extra:
            out["extra"] = {k: (v if isinstance(v, (int, float, bool,
                                                    str, type(None)))
                                else str(v))
                            for k, v in self.extra.items()}
        if self.children:
            out["children"] = [c.to_dict() for c in self.children]
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "OperatorMetrics":
        m = cls(str(d.get("operator", "?")), str(d.get("detail", "")))
        m.output_rows = int(d.get("output_rows", 0))
        m.capacity = int(d.get("capacity", 0))
        m.elapsed_ms = float(d.get("elapsed_ms", 0.0))
        m.extra = dict(d.get("extra") or {})
        m.children = [cls.from_dict(c) for c in d.get("children") or ()]
        return m


_local = threading.local()


def current_collector() -> Optional[List]:
    return getattr(_local, "collector", None)


@contextmanager
def collect_metrics():
    """Enable metrics collection on this thread for one query."""
    prev = getattr(_local, "collector", None)
    _local.collector = []
    try:
        yield _local.collector
    finally:
        _local.collector = prev


def note(operator: str, detail: str = "", **extra) -> None:
    """Attach a zero-duration informational entry (e.g. prefetch overlap
    counters) at the current nesting level; no-op without a collector."""
    collector = current_collector()
    if collector is None:
        return
    m = OperatorMetrics(operator, detail)
    m.extra = dict(extra)
    collector.append(m)


@contextmanager
def operator_span(name: str, detail: str = ""):
    """Wrap one operator execution: always an ``op.<name>`` span in the
    statement's span tree (tracing.py); under EXPLAIN ANALYZE, where the
    thread has a collector, also an ``OperatorMetrics`` that the caller
    fills and that nests into the collector. Yields that, or None."""
    from . import tracing as tr
    with tr.span("op." + name):
        collector = current_collector()
        if collector is None:
            yield None
            return
        m = OperatorMetrics(name, detail)
        # children recorded during this span land in a fresh list
        parent = collector
        own: List[OperatorMetrics] = []
        _local.collector = own
        t0 = time.perf_counter()
        try:
            yield m
        except BaseException:
            # an aborted operator (a fused attempt that fell back) records
            # no metrics; its op.<name> span ends with status_ok False
            _local.collector = parent
            raise
        else:
            m.elapsed_ms = (time.perf_counter() - t0) * 1000
            m.children = own
            parent.append(m)
            _local.collector = parent
            _record_metric("execution.output_row_count", m.output_rows,
                           operator=name)
            _record_metric("execution.elapsed_compute_time",
                           m.elapsed_ms / 1000.0, operator=name)
