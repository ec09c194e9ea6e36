"""Pull-based ops endpoint: Prometheus /metrics, health, JSON debug.

Reference role: the operational surface Tailwind (arXiv:2604.28079)
assumes of a serving fleet — SLOs are only real if they are
continuously MEASURED and scrapeable. The OTLP exporter (tracing.py)
pushes; this module is the pull side: a stdlib ``http.server`` on a
daemon thread (no new dependencies), gated by
``telemetry.http.{enabled,port}``:

- ``GET /metrics``   Prometheus text exposition (v0.0.4) of the FLEET
  metric view: every sample carries a ``worker`` label (``driver`` =
  this process; remote workers from heartbeat-shipped deltas).
  Counters render with the ``_total`` convention, histograms as
  ``_bucket``/``_sum``/``_count`` over the declared exponential
  bounds.
- ``GET /healthz``   liveness: the process is serving.
- ``GET /readyz``    readiness: 200 only when every registered cluster
  driver reports all workers heartbeating, no evicted worker pending
  readmission, and no wedged admission queue; 503 otherwise (body says
  why). A process with no cluster is ready by definition.
- ``GET /debug/queries | /debug/workers | /debug/admission |
  /debug/autoscaler | /debug/compile_cache | /debug/slo |
  /debug/events?n=N``  JSON introspection of the flight recorder,
  worker pool, admission state, the autoscaler (policy config, pool
  occupancy, draining set with handoff progress, newest decisions),
  the persistent compiled-program cache (entry count, bytes, hit
  ratio, top entries by compile time saved), the tenant SLO burn-rate
  view (evaluating the monitor is the tick; also refreshed on every
  /metrics scrape), and the newest N ring events.

The surface is auth-free and bound to ``telemetry.http.host``
(default loopback); it exposes statements and runtime state but never
serializes configuration or the environment, so credentials cannot
leak through it (locked by a test).
"""

from __future__ import annotations

import json
import threading
import time
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional
from urllib.parse import parse_qs, urlparse

from . import metrics as _metrics

_START_TS = time.time()


# ---------------------------------------------------------------------------
# cluster registration: drivers expose readiness/debug state to the
# process's ops endpoint without the HTTP layer importing the scheduler
# ---------------------------------------------------------------------------

_CLUSTERS: "weakref.WeakSet" = weakref.WeakSet()


def register_cluster(driver) -> None:
    """A cluster driver in this process joins the ops surface (weakly:
    a stopped/collected driver drops out on its own)."""
    _CLUSTERS.add(driver)


def unregister_cluster(driver) -> None:
    _CLUSTERS.discard(driver)


def _drivers() -> List:
    return [d for d in list(_CLUSTERS)]


# ---------------------------------------------------------------------------
# readiness
# ---------------------------------------------------------------------------

def readiness() -> dict:
    """Aggregate readiness: ready iff every registered driver is ready.
    Driver state is read cross-thread; every probe is defensive — a
    half-updated pool entry must degrade to 'not ready', never raise."""
    checks = []
    ready = True
    for d in _drivers():
        try:
            c = d.readiness()
        except Exception as e:  # noqa: BLE001 — degraded, not broken
            c = {"ready": False, "error": f"{type(e).__name__}: {e}"}
        checks.append(c)
        ready = ready and bool(c.get("ready"))
    return {"ready": ready, "clusters": checks,
            "uptime_s": round(time.time() - _START_TS, 3)}


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def _escape_label(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _labels(attrs: Dict[str, str], worker: str,
            extra: Optional[Dict[str, str]] = None) -> str:
    pairs = dict(attrs)
    pairs["worker"] = worker
    if extra:
        pairs.update(extra)
    body = ",".join(f'{k}="{_escape_label(v)}"'
                    for k, v in sorted(pairs.items()))
    return "{" + body + "}"


def _fmt(value: float) -> str:
    f = float(value)
    return str(int(f)) if f == int(f) else repr(f)


def render_prometheus() -> str:
    """The fleet metric view in Prometheus text format. Series group
    per metric name under one # HELP / # TYPE header; a scrape of the
    driver therefore reads the whole fleet."""
    series = _metrics.FLEET.series()
    by_name: Dict[str, List] = {}
    for name, attrs, worker, value in series:
        by_name.setdefault(name, []).append((attrs, worker, value))
    lines: List[str] = []
    for name in sorted(by_name):
        d = _metrics.REGISTRY.definition(name)
        if d is None:
            continue
        prom = _metrics.prometheus_name(name, d.type)
        ptype = {"counter": "counter", "gauge": "gauge",
                 "histogram": "histogram"}[d.type]
        help_text = " ".join(d.description.split()) or name
        lines.append(f"# HELP {prom} {help_text}")
        lines.append(f"# TYPE {prom} {ptype}")
        for attrs, worker, value in sorted(
                by_name[name], key=lambda e: (e[1], sorted(e[0].items()))):
            if isinstance(value, _metrics.HistogramState):
                cum = 0
                for bound, count in zip(value.bounds, value.counts):
                    cum += count
                    lines.append(
                        f"{prom}_bucket"
                        f"{_labels(attrs, worker, {'le': _fmt(bound)})}"
                        f" {cum}")
                cum += value.counts[-1]
                lines.append(
                    f"{prom}_bucket"
                    f"{_labels(attrs, worker, {'le': '+Inf'})} {cum}")
                lines.append(f"{prom}_sum{_labels(attrs, worker)} "
                             f"{repr(float(value.sum))}")
                lines.append(f"{prom}_count{_labels(attrs, worker)} "
                             f"{value.count}")
            else:
                lines.append(
                    f"{prom}{_labels(attrs, worker)} {_fmt(value)}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# JSON debug views
# ---------------------------------------------------------------------------

def _debug_queries() -> dict:
    from .profiler import FLIGHT_RECORDER

    def brief(p, active: bool) -> dict:
        return {"query_id": p.query_id,
                "statement": (p.statement or "")[:200],
                "session": p.session, "tenant": p.tenant,
                "status": "running" if active else p.status,
                "phase": p.current_phase() if active else "",
                "total_ms": round(p.total_ms, 3),
                "rows_out": p.rows_out, "slow": p.slow}

    return {"active": [brief(p, True)
                       for p in FLIGHT_RECORDER.active()],
            "recent": [brief(p, False)
                       for p in FLIGHT_RECORDER.profiles()[:64]]}


def _debug_workers() -> dict:
    now = time.time()
    clusters = []
    for d in _drivers():
        try:
            workers = {}
            for wid, w in dict(d.workers).items():
                workers[wid] = {
                    "addr": w.get("addr", ""),
                    "slots": w.get("slots", 0),
                    "running_tasks": len(w.get("tasks", ())),
                    "heartbeat_age_s": round(
                        now - w.get("last_seen", now), 3),
                }
            clusters.append({
                "driver_id": getattr(d, "driver_id", ""),
                "workers": workers,
                "quarantined": sorted(dict(d.quarantined)),
                "pending_readmission": sorted(dict(d._readmit_info)),
            })
        except Exception as e:  # noqa: BLE001 — snapshot best-effort
            clusters.append({"error": f"{type(e).__name__}: {e}"})
    from .catalog.system import SYSTEM
    with SYSTEM._lock:
        known = {wid: dict(w) for wid, w in SYSTEM.workers.items()}
    return {"clusters": clusters, "registry": known}


def _debug_admission() -> dict:
    from .exec import admission as _adm
    gate = _adm.session_gate()
    out = {"session_gate": gate.debug_snapshot(), "clusters": []}
    for d in _drivers():
        try:
            out["clusters"].append(d.admission.debug_snapshot())
        except Exception as e:  # noqa: BLE001
            out["clusters"].append(
                {"error": f"{type(e).__name__}: {e}"})
    return out


def _debug_events(n: int) -> dict:
    from . import events as ev
    records = ev.events()
    return {"count": len(records), "events": records[-max(1, n):]}


def _debug_slo() -> dict:
    """Tenant SLO burn-rate view: evaluates the monitor (taking a
    fresh snapshot and refreshing the cluster.slo.burn_rate gauges)
    and returns the per-tenant/per-window rows alongside the newest
    anomaly verdicts. Pull-based: hitting this endpoint IS the
    evaluation tick."""
    from .analysis import anomaly as _anomaly
    try:
        rows = _anomaly.SLO_MONITOR.evaluate()
    except Exception as e:  # noqa: BLE001 — snapshot best-effort
        return {"error": f"{type(e).__name__}: {e}"}
    return {"slo": rows,
            "anomalies": _anomaly.anomalies()[-32:],
            "baselines": _anomaly.BASELINES.snapshot()[:64]}


def _debug_autoscaler() -> dict:
    """Autoscaler view per registered driver: effective policy config,
    the worker pool (occupancy/idle), the draining set with handoff
    progress, and the newest policy decisions (each carries the
    replayable canonical detail via /debug/events)."""
    now = time.time()
    clusters = []
    for d in _drivers():
        try:
            pool = {}
            draining = dict(getattr(d, "draining", {}))
            for wid, w in dict(d.workers).items():
                idle = w.get("idle_since")
                pool[wid] = {
                    "addr": w.get("addr", ""),
                    "slots": w.get("slots", 0),
                    "running_tasks": len(w.get("tasks", ())),
                    "idle_s": round(now - idle, 3)
                    if idle and not w.get("tasks") else 0.0,
                    "draining": wid in draining,
                }
            clusters.append({
                "driver_id": getattr(d, "driver_id", ""),
                "config": d.autoscaler_cfg.to_dict(),
                "state": {
                    "up_streak": d.autoscaler_state.up_streak,
                    "down_streak": d.autoscaler_state.down_streak,
                    "cooldown_left": d.autoscaler_state.cooldown_left,
                },
                "pool": pool,
                "draining": {
                    wid: {"reason": st.get("reason", ""),
                          "age_s": round(now - st.get("started", now),
                                         3),
                          "channels_moved": st.get("channels", 0),
                          "bytes_moved": st.get("bytes", 0)}
                    for wid, st in draining.items()},
                "decisions": list(d.autoscaler_log)[-32:],
            })
        except Exception as e:  # noqa: BLE001 — snapshot best-effort
            clusters.append({"error": f"{type(e).__name__}: {e}"})
    return {"clusters": clusters}


def _debug_compile_cache() -> dict:
    """Compile caches as this process holds them: the directory jax's
    persistent compilation cache is in, how many stage programs the
    in-memory operator cache holds, and the pinned capacity buckets
    (why capacities, and with them compiled shapes, are stable).
    Serializes cache state only — never configuration or environment
    values."""
    from .exec import capacity, pcache
    from .exec.local import _OP_CACHE
    return {"jax_cache_dir": pcache.place_jax_cache(),
            "op_cache_entries": len(_OP_CACHE.entries),
            "capacity": capacity.snapshot()}


# ---------------------------------------------------------------------------
# the HTTP server
# ---------------------------------------------------------------------------

class _Handler(BaseHTTPRequestHandler):
    server_version = "sail-obs/1"

    def log_message(self, *args):  # silence per-request stderr lines
        pass

    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _json(self, payload: dict, code: int = 200) -> None:
        self._send(code, json.dumps(payload, default=str,
                                    indent=1).encode("utf-8"),
                   "application/json")

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        try:
            url = urlparse(self.path)
            path = url.path.rstrip("/") or "/"
            if path == "/metrics":
                # refresh the SLO burn-rate gauges so a scrape reads
                # window math current as of the scrape, not of the
                # last /debug/slo hit
                try:
                    from .analysis import anomaly as _anomaly
                    _anomaly.SLO_MONITOR.evaluate()
                except Exception:  # noqa: BLE001 — scrape still serves
                    pass
                self._send(200, render_prometheus().encode("utf-8"),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                self._json({"status": "ok",
                            "uptime_s": round(
                                time.time() - _START_TS, 3)})
            elif path == "/readyz":
                state = readiness()
                self._json(state, 200 if state["ready"] else 503)
            elif path == "/debug/queries":
                self._json(_debug_queries())
            elif path == "/debug/workers":
                self._json(_debug_workers())
            elif path == "/debug/admission":
                self._json(_debug_admission())
            elif path == "/debug/autoscaler":
                self._json(_debug_autoscaler())
            elif path == "/debug/compile_cache":
                self._json(_debug_compile_cache())
            elif path == "/debug/slo":
                self._json(_debug_slo())
            elif path == "/debug/events":
                q = parse_qs(url.query)
                try:
                    n = int(q.get("n", ["100"])[0])
                except ValueError:
                    n = 100
                self._json(_debug_events(n))
            else:
                self._json({"error": "not found", "paths": [
                    "/metrics", "/healthz", "/readyz",
                    "/debug/queries", "/debug/workers",
                    "/debug/admission", "/debug/autoscaler",
                    "/debug/compile_cache",
                    "/debug/slo", "/debug/events?n="]}, 404)
        except BrokenPipeError:  # client went away mid-write
            pass
        except Exception as e:  # noqa: BLE001 — ops surface never dies
            try:
                self._json({"error": f"{type(e).__name__}: {e}"}, 500)
            except Exception:  # noqa: BLE001
                pass


class ObsServer:
    """One process-wide ops HTTP server on a daemon thread."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.2},
            daemon=True, name="sail-obs-server")
        self._thread.start()

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def close(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:  # noqa: BLE001
            pass


_SERVER: Optional[ObsServer] = None
_SERVER_LOCK = threading.Lock()
_STARTED = False


def server() -> Optional[ObsServer]:
    return _SERVER


def start(host: Optional[str] = None,
          port: Optional[int] = None) -> ObsServer:
    """Start (or return) the process ops server, regardless of the
    config gate — tests and the bench call this explicitly."""
    global _SERVER, _STARTED
    with _SERVER_LOCK:
        if _SERVER is None:
            from .config import get as config_get
            if host is None:
                host = str(config_get("telemetry.http.host",
                                      "127.0.0.1") or "127.0.0.1")
            if port is None:
                try:
                    port = int(config_get("telemetry.http.port", 0))
                except (TypeError, ValueError):
                    port = 0
            _SERVER = ObsServer(host, port)
        _STARTED = True
        return _SERVER


def ensure_started() -> Optional[ObsServer]:
    """Config-gated start (``telemetry.http.enabled``, default off) —
    called from session and cluster construction; one check per
    process, one server per process."""
    global _STARTED
    if _STARTED:
        return _SERVER
    with _SERVER_LOCK:
        if _STARTED:
            return _SERVER
        _STARTED = True
    try:
        from .config import truthy
        enabled = truthy("telemetry.http.enabled", default="false")
    except Exception:  # noqa: BLE001 — ops surface must not break startup
        enabled = False
    if not enabled:
        return None
    try:
        return start()
    except OSError as e:
        # a bind failure (port taken by another process) degrades to no
        # ops endpoint — it must never fail session/cluster startup
        import logging
        logging.getLogger("sail_tpu.obs_server").warning(
            "ops endpoint disabled: cannot bind (%s)", e)
        return None


def stop() -> None:
    """Shut the server down and re-arm the config gate (tests)."""
    global _SERVER, _STARTED
    with _SERVER_LOCK:
        if _SERVER is not None:
            _SERVER.close()
            _SERVER = None
        _STARTED = False
