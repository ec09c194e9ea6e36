"""Tail-latency forensics (analysis/anomaly.py + exec/retrace.py).

Four planes:

- retrace cause taxonomy: every cause in events.RETRACE_CAUSES is
  provoked deliberately through the REAL compile decision site
  (``_compile_timed`` + ``_OpCache``);
- baselines + verdicts: per-fingerprint baseline convergence, the
  outlier gates, evidence ranking, and every verdict category;
- SLO burn windows: fast/slow burn-rate math checked against exact
  sample fractions with an injectable clock, plus objective layering
  and the ``/debug/slo`` ops endpoint;
- durable-log replay: ``replay_verdicts`` (and the offline
  ``sail_timeline.py --anomalies`` entry point, i.e. a genuine process
  restart) reproduces the live anomaly ring bit-identically, chaos
  faults included.
"""

import json
import os
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import pyarrow as pa
import pytest

from sail_tpu import SparkSession, events, faults, obs_server
from sail_tpu import metrics as gm
from sail_tpu.analysis import anomaly
from sail_tpu.events import EventType
from sail_tpu.exec import local as xl
from sail_tpu.exec import retrace
from sail_tpu.exec.local import clear_caches

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMELINE = os.path.join(REPO_ROOT, "scripts", "sail_timeline.py")


@pytest.fixture(autouse=True)
def _reset():
    anomaly.reset()
    retrace.clear()
    yield
    anomaly.reset()
    retrace.clear()
    clear_caches()
    faults.reset()
    events.reload()


def _sig_args(rows, cols):
    return jnp.zeros((rows, cols))


# ---------------------------------------------------------------------------
# retrace cause taxonomy — through the real compile sites
# ---------------------------------------------------------------------------

def test_first_ever_then_capacity_bucket_then_new_aval():
    f = xl._compile_timed(jax.jit(lambda x: x * 2), ("op", "taxonomy"))
    f(_sig_args(8, 4))
    assert retrace.LEDGER.totals() == {"first-ever": 1}
    # leading (padded capacity) dim changed, trailing shape identical:
    # the round_capacity churn cause
    f(_sig_args(16, 4))
    assert retrace.LEDGER.totals()["capacity-bucket"] == 1
    # trailing dim changed too: a genuinely new aval signature
    f(_sig_args(16, 5))
    assert retrace.LEDGER.totals()["new-aval-signature"] == 1
    # repeat signature: bound executable, no compile, no attribution
    f(_sig_args(16, 4))
    assert sum(retrace.LEDGER.totals().values()) == 3


def test_op_cache_eviction_recompile_reads_as_eviction():
    cache = xl._OpCache(max_entries=1)

    def mk(key):
        return xl._compile_timed(jax.jit(lambda x: x + 1), key)

    f1 = cache.get(("op", "k1"), (), lambda: mk(("op", "k1")))
    f1(_sig_args(4, 2))
    f2 = cache.get(("op", "k2"), (), lambda: mk(("op", "k2")))
    f2(_sig_args(4, 2))   # evicts k1 from the op cache
    f1b = cache.get(("op", "k1"), (), lambda: mk(("op", "k1")))
    f1b(_sig_args(4, 2))  # same key, same signature → eviction retrace
    totals = retrace.LEDGER.totals()
    assert totals == {"first-ever": 2, "eviction": 1}
    rows = retrace.LEDGER.snapshot()
    evicted = [r for r in rows if r["cause"] == "eviction"]
    assert evicted and evicted[0]["count"] == 1
    assert evicted[0]["evictions"] >= 1


# ---------------------------------------------------------------------------
# baselines + the classifier
# ---------------------------------------------------------------------------

def _inputs(qid="q1", total_ms=100.0, fp="f" * 16, spill=0, cache=""):
    return {"query_id": qid, "trace_id": "t" * 32, "fingerprint": fp,
            "total_ms": total_ms, "spill_bytes": spill,
            "cache_status": cache}


_CONF = {"enabled": True, "min_samples": 5, "outlier_factor": 2.0,
         "min_excess_ms": 20.0, "min_evidence_ms": 5.0,
         "ring_capacity": 256, "baseline_capacity": 512}


def test_baseline_converges_within_bucket_error():
    store = anomaly.BaselineStore()
    for i in range(20):
        store.observe(_inputs(qid=f"q{i}", cache="hit"), [])
    snap = store.snapshot_for("f" * 16)
    assert snap["count"] == 20
    # exponential buckets with 1.25 growth: p50 within 12.5% of truth
    assert abs(snap["p50_ms"] - 100.0) / 100.0 <= 0.125
    assert snap["hit_ratio"] == 1.0
    assert store.snapshot_for("unknown") is None


def test_classifier_outlier_gates():
    store = anomaly.BaselineStore()
    for i in range(4):
        store.observe(_inputs(qid=f"q{i}"), [])
    base = store.snapshot_for("f" * 16)
    # below min_samples: never classify
    assert anomaly.classify(_inputs(total_ms=900.0), [], base,
                            _CONF) is None
    store.observe(_inputs(qid="q4"), [])
    base = store.snapshot_for("f" * 16)
    # within outlier_factor × p50: not an outlier
    assert anomaly.classify(_inputs(total_ms=150.0), [], base,
                            _CONF) is None
    # outlier with no evidence at all: unexplained
    rec = anomaly.classify(_inputs(total_ms=900.0), [], base, _CONF)
    assert rec is not None and rec["verdict"] == "unexplained"
    assert rec["excess_ms"] == pytest.approx(
        900.0 - rec["baseline_p50_ms"], abs=1e-6)
    # no baseline at all: silent
    assert anomaly.classify(_inputs(total_ms=900.0), [], None,
                            _CONF) is None


def _warm(store, n=6):
    for i in range(n):
        store.observe(_inputs(qid=f"w{i}"), [])
    return store.snapshot_for("f" * 16)


def test_retrace_verdict_excludes_first_ever_and_names_causes():
    base = _warm(anomaly.BaselineStore())
    evs = [
        {"type": "retrace", "cause": "first-ever", "ms": 500.0},
        {"type": "retrace", "cause": "capacity-bucket", "ms": 120.0},
        {"type": "retrace", "cause": "eviction", "ms": 40.0},
    ]
    rec = anomaly.classify(_inputs(total_ms=600.0), evs, base, _CONF)
    assert rec["verdict"] == "retrace"
    top = rec["evidence"][0]
    assert top["category"] == "retrace"
    assert top["ms"] == pytest.approx(160.0)  # first-ever excluded
    assert top["causes"] == {"capacity-bucket": 1, "eviction": 1}


def test_evidence_ranked_by_wall_time():
    base = _warm(anomaly.BaselineStore())
    evs = [
        {"type": "retrace", "cause": "eviction", "ms": 30.0},
        {"type": "backpressure", "stall_ms": 80.0},
        {"type": "admission_admit", "waited_ms": 10.0},
        {"type": "task_finish", "fetch_wait_ms": 5.0},
    ]
    rec = anomaly.classify(_inputs(total_ms=600.0), evs, base, _CONF)
    assert rec["verdict"] == "credit-stall"
    cats = [e["category"] for e in rec["evidence"]]
    assert cats == ["credit-stall", "retrace", "admission-queue-wait",
                    "fetch-wait"]


def test_flag_verdicts_spill_and_cache_invalidation():
    base = _warm(anomaly.BaselineStore())
    rec = anomaly.classify(_inputs(total_ms=600.0, spill=4096), [],
                           base, _CONF)
    assert rec["verdict"] == "spill"
    assert rec["evidence"][0]["bytes"] == 4096
    # this fingerprint usually serves from cache; an outlier run that
    # missed points at an invalidation
    store = anomaly.BaselineStore()
    for i in range(6):
        store.observe(_inputs(qid=f"h{i}", cache="hit"), [])
    base = store.snapshot_for("f" * 16)
    rec = anomaly.classify(_inputs(total_ms=600.0, cache="miss"), [],
                           base, _CONF)
    assert rec["verdict"] == "cache-invalidation"


def test_sub_threshold_evidence_stays_unexplained():
    base = _warm(anomaly.BaselineStore())
    evs = [{"type": "retrace", "cause": "eviction", "ms": 2.0}]
    rec = anomaly.classify(_inputs(total_ms=600.0), evs, base, _CONF)
    assert rec["verdict"] == "unexplained"
    # the sub-threshold evidence is still reported, just not blamed
    assert rec["evidence"][0]["category"] == "retrace"


# ---------------------------------------------------------------------------
# SLO burn-rate windows
# ---------------------------------------------------------------------------

def test_burn_rate_windows_match_exact_fractions():
    gm.REGISTRY.reset()
    mon = anomaly.SloMonitor()
    mon.set_objective("acme", target_ms=1000.0, objective=0.9)
    t0 = 50_000.0
    # history before the fast window: 10 fast queries
    for _ in range(10):
        gm.record("query.latency", 0.1, tenant="acme", phase="total")
    mon.evaluate(now=t0)
    # inside the fast window: 4 fast + 1 slow (4.0 s ≫ 1 s target;
    # no sample lands in the threshold's own bucket, so
    # fraction_above is EXACT, not interpolated)
    for _ in range(4):
        gm.record("query.latency", 0.1, tenant="acme", phase="total")
    gm.record("query.latency", 4.0, tenant="acme", phase="total")
    rows = {(r["tenant"], r["window"]): r
            for r in mon.evaluate(now=t0 + 301.0)}
    fast = rows[("acme", "fast")]
    assert fast["queries"] == 5
    assert fast["fraction_above"] == pytest.approx(1 / 5)
    assert fast["burn_rate"] == pytest.approx((1 / 5) / 0.1)
    # slow window (3600 s) has no anchor yet: full history counts
    slow = rows[("acme", "slow")]
    assert slow["queries"] == 15
    assert slow["fraction_above"] == pytest.approx(1 / 15, abs=1e-6)
    assert slow["burn_rate"] == pytest.approx((1 / 15) / 0.1, abs=1e-5)
    # burn gauges recorded per tenant × window
    names = {(row["name"], row["attributes"])
             for row in gm.REGISTRY.snapshot()}
    assert any(n == "cluster.slo.burn_rate" and "fast" in a
               for n, a in names)


def test_objective_layering(monkeypatch):
    monkeypatch.setenv("SAIL_SLO__TENANTS__ACME__TARGET_MS", "500")
    mon = anomaly.SloMonitor()
    assert mon.objective_for("acme")[0] == 500.0
    assert mon.objective_for("other")[0] == 1000.0
    # explicit session override (spark.sail.slo.targetMs) wins
    mon.set_objective("acme", target_ms=250.0, objective=0.95)
    target, objective = mon.objective_for("acme")
    assert (target, objective) == (250.0, 0.95)


def test_session_conf_sets_tenant_objective():
    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.tenant": "slo-tenant"})
    try:
        spark.sql("SET spark.sail.slo.targetMs=750")
        spark.sql("SET spark.sail.slo.objective=0.95")
        target, objective = anomaly.SLO_MONITOR.objective_for(
            "slo-tenant")
        assert (target, objective) == (750.0, 0.95)
    finally:
        spark.stop()


def _get(url: str):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.read().decode()


def test_debug_slo_endpoint_and_prometheus_gauge():
    gm.REGISTRY.reset()
    gm.record("query.latency", 2.0, tenant="acme", phase="total")
    srv = obs_server.start()
    status, body = _get(srv.url + "/debug/slo")
    assert status == 200
    doc = json.loads(body)
    burn = {(r["tenant"], r["window"]): r for r in doc["slo"]}
    assert ("acme", "fast") in burn and ("acme", "slow") in burn
    assert burn[("acme", "fast")]["burn_rate"] > 1.0  # 100% > target
    status, body = _get(srv.url + "/metrics")
    assert status == 200
    assert "cluster_slo_burn_rate" in body


# ---------------------------------------------------------------------------
# durable-log replay — verdicts from the log alone
# ---------------------------------------------------------------------------

def _emit_query(qid, total_ms, retraces=(), tenant="t0",
                fp="a" * 16, cache="miss"):
    events.emit(EventType.QUERY_START, query_id=qid,
                trace_id=qid * 8, statement="select …", session="s",
                tenant=tenant)
    for cause, ms in retraces:
        events.emit(EventType.RETRACE, query_id=qid, trace_id=qid * 8,
                    key="k", fp=fp, cause=cause, ms=ms, site="memory")
    events.emit(EventType.QUERY_END, query_id=qid, trace_id=qid * 8,
                status="succeeded", rows_out=1, total_ms=total_ms,
                fingerprint=fp, spill_bytes=0, cache_status=cache)


def test_replay_verdicts_and_offline_timeline_restart(
        tmp_path, monkeypatch):
    monkeypatch.setenv("SAIL_TELEMETRY__EVENT_LOG__ENABLED", "1")
    monkeypatch.setenv("SAIL_TELEMETRY__EVENT_LOG__DIR", str(tmp_path))
    events.reload()
    for i in range(5):
        _emit_query(f"q{i:04d}", 100.0)
    _emit_query("q-out", 400.0,
                retraces=(("first-ever", 50.0),
                          ("capacity-bucket", 120.0)))
    path = events.EVENT_LOG.path
    assert path and os.path.exists(path)
    events.EVENT_LOG.close()
    recs = events.load_event_log(path)
    verdicts = anomaly.replay_verdicts(recs)
    assert len(verdicts) == 1
    v = verdicts[0]
    assert v["query_id"] == "q-out"
    assert v["verdict"] == "retrace"
    assert v["evidence"][0]["causes"] == {"capacity-bucket": 1}
    assert v["total_ms"] == 400.0
    # replay is deterministic: a second walk is bit-identical
    assert json.dumps(anomaly.replay_verdicts(recs), sort_keys=True) \
        == json.dumps(verdicts, sort_keys=True)
    # a genuine restart: the offline script (fresh process, no shared
    # state) re-derives the SAME verdict list from the log alone
    proc = subprocess.run(
        [sys.executable, TIMELINE, path, "--anomalies", "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    offline = json.loads(proc.stdout)["anomalies"]
    assert json.dumps(offline, sort_keys=True) == \
        json.dumps(verdicts, sort_keys=True)
    # --query filters to one query (by id or trace id)
    proc = subprocess.run(
        [sys.executable, TIMELINE, path, "--anomalies", "--json",
         "--query", "q-out"],
        capture_output=True, text=True, timeout=120)
    assert json.loads(proc.stdout)["anomalies"] == offline


def _force_anomaly_env(monkeypatch, tmp_path):
    monkeypatch.setenv("SAIL_TELEMETRY__EVENT_LOG__ENABLED", "1")
    monkeypatch.setenv("SAIL_TELEMETRY__EVENT_LOG__DIR", str(tmp_path))
    # every query past the 2nd classifies (no outlier gate) so the
    # live-vs-replay comparison always has verdicts to compare
    monkeypatch.setenv("SAIL_TELEMETRY__ANOMALY__MIN_SAMPLES", "2")
    monkeypatch.setenv("SAIL_TELEMETRY__ANOMALY__OUTLIER_FACTOR", "0")
    monkeypatch.setenv("SAIL_TELEMETRY__ANOMALY__MIN_EXCESS_MS",
                       "-1000000")
    events.reload()


def test_live_ring_equals_replay_end_to_end(tmp_path, monkeypatch):
    _force_anomaly_env(monkeypatch, tmp_path)
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    t = pa.table({"a": list(range(300)),
                  "b": [float(i) * 0.25 for i in range(300)]})
    spark.createDataFrame(t).createOrReplaceTempView("t")
    q = ("SELECT a % 7 AS g, sum(b) AS s, count(*) AS n FROM t "
         "WHERE a > 10 GROUP BY a % 7 ORDER BY g")
    for _ in range(5):
        spark.sql(q).collect()
    spark.stop()
    live = anomaly.anomalies()
    assert len(live) >= 3  # queries 3..5 classify
    path = events.EVENT_LOG.path
    events.EVENT_LOG.close()
    replayed = anomaly.replay_verdicts(events.load_event_log(path))
    assert json.dumps(replayed, sort_keys=True) == \
        json.dumps(live, sort_keys=True)


@pytest.mark.parametrize("seed", [3, 11])
def test_chaos_verdicts_deterministic_and_replayable(
        tmp_path, monkeypatch, seed):
    _force_anomaly_env(monkeypatch, tmp_path)
    faults.configure("io.read=delay(0.02)@0.5", seed=seed)
    spark = SparkSession({"spark.sail.execution.mesh": "off"})
    t = pa.table({"a": list(range(250)),
                  "b": [float(i) for i in range(250)]})
    spark.createDataFrame(t).createOrReplaceTempView("t")
    q = "SELECT a % 5 AS g, max(b) AS m FROM t GROUP BY a % 5 ORDER BY g"
    for _ in range(4):
        spark.sql(q).collect()
    spark.stop()
    live = anomaly.anomalies()
    assert live  # classification forced past min_samples
    path = events.EVENT_LOG.path
    events.EVENT_LOG.close()
    recs = events.load_event_log(path)
    r1 = anomaly.replay_verdicts(recs)
    r2 = anomaly.replay_verdicts(recs)
    # replay is a pure function of the log: deterministic per fault
    # seed, and bit-identical to what the live ring held
    assert json.dumps(r1, sort_keys=True) == \
        json.dumps(r2, sort_keys=True)
    assert json.dumps(r1, sort_keys=True) == \
        json.dumps(live, sort_keys=True)
