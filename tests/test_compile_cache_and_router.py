"""Where JAX's persistent compilation cache goes (exec/pcache.py
``place_jax_cache``), the per-stage backend router (exec/router.py) and
the ``/debug/compile_cache`` ops endpoint.

- the cache directory: the environment's when set, else one fixed
  directory in the checkout (what a second process finds there is held
  by tests/test_program_names.py);
- router: force overrides, deterministic per-fingerprint decisions,
  plan-level mesh gate, EXPLAIN / FORMAT JSON / event surfaces;
- ``/debug/compile_cache`` shape + no-secret contract.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pyarrow as pa
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.exec import pcache, router
from sail_tpu.exec.local import clear_caches

pytestmark = []


@pytest.fixture(autouse=True)
def _reset_after():
    yield
    clear_caches()
    router.clear_observations()


def _session(**conf):
    base = {"spark.sail.execution.mesh": "off"}
    base.update(conf)
    return SparkSession(base)


Q = ("SELECT a % 5 AS g, sum(b) AS s, count(*) AS n "
     "FROM t WHERE a > 3 GROUP BY a % 5 ORDER BY g")


def _make_t(spark, n=500):
    t = pa.table({"a": list(range(n)),
                  "b": [float(i) * 0.5 for i in range(n)]})
    spark.createDataFrame(t).createOrReplaceTempView("t")


_JAX_CACHE_SCRIPT = r"""
import os
import jax, jax.numpy as jnp
from sail_tpu import SparkSession
from sail_tpu.exec import pcache
before = jax.config.jax_compilation_cache_dir
SparkSession({})      # a session's start places the cache ...
placed = pcache.place_jax_cache()      # ... once: this only reports it
jax.jit(lambda x: jnp.tanh(x) * 3.0)(jnp.arange(7.0)).block_until_ready()
print("PLACED", placed)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("UNTOUCHED", before == jax.config.jax_compilation_cache_dir)
print("ENTRIES", len(os.listdir(placed)))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_jax_cache_goes_where_the_environment_says(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: the program never overrides it.
    Unset: one fixed directory in the checkout. Either way it is a
    session's start that places it."""
    env = dict(os.environ)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    r = subprocess.run([sys.executable, "-c", _JAX_CACHE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.split(" ", 1)[0] in
               ("PLACED", "CONFIG", "UNTOUCHED", "ENTRIES"))
    want = str(tmp_path / "placed") if from_env else pcache.JAX_CACHE_DIR
    assert out["PLACED"] == want and out["CONFIG"] == want
    assert out["UNTOUCHED"] == str(from_env)
    assert int(out["ENTRIES"]) >= 1
    assert pcache.JAX_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


# ---------------------------------------------------------------------------
# backend router
# ---------------------------------------------------------------------------

def test_force_xla_disables_native():
    from sail_tpu import native as _native
    if not _native.native_active():
        pytest.skip("native toolchain unavailable")
    spark_native = _session()
    _make_t(spark_native)
    expected = spark_native.sql(Q).toArrow()
    spark_xla = _session(
        **{"spark.sail.execution.backend.force": "xla"})
    _make_t(spark_xla)
    out = spark_xla.sql(Q).toArrow()
    assert out.equals(expected)
    routes = profiler.last_profile().backend_routes
    agg = [r for r in routes if r["kind"] == "aggregate"]
    assert agg and all(r["backend"] == "xla"
                       and r["reason"] == "forced" for r in agg)


def test_default_route_is_deterministic():
    """The chosen BACKEND is a pure function of fingerprint + config;
    the reason may refine as the observation table fills (cost-model →
    compile-bound after a compile-dominated first run) — decisions are
    deterministic per fingerprint AND observed history, and recorded."""
    spark = _session()
    _make_t(spark)
    spark.sql(Q).toArrow()
    first = profiler.last_profile().backend_routes
    clear_caches()
    spark.sql(Q).toArrow()
    second = profiler.last_profile().backend_routes
    assert [(r["stage"], r["kind"], r["backend"]) for r in second] == \
        [(r["stage"], r["kind"], r["backend"]) for r in first]
    assert all(r["reason"] in ("cost-model", "compile-bound", "default",
                               "unsupported") for r in second)
    # with the observation table cleared, the decision repeats exactly
    router.clear_observations()
    clear_caches()
    spark.sql(Q).toArrow()
    assert profiler.last_profile().backend_routes == first


def test_explain_renders_backend_line():
    spark = _session()
    _make_t(spark)
    text = spark.sql("EXPLAIN " + Q).toArrow().column(0)[0].as_py()
    assert "backend: " in text
    assert "s0=" in text
    payload = json.loads(spark.sql(
        "EXPLAIN FORMAT JSON " + Q).toArrow().column(0)[0].as_py())
    assert payload["backends"]
    assert {"stage", "kind", "backend", "reason"} <= set(
        payload["backends"][0])


def test_backend_route_events_recorded():
    from sail_tpu import events as ev
    spark = _session()
    _make_t(spark)
    spark.sql(Q).toArrow()
    routed = [e for e in ev.events()
              if e.get("type") == "backend_route"]
    assert routed
    assert {e["backend"] for e in routed} <= {"native", "xla", "mesh"}


def test_plan_gate_dispatch_bound_vs_force():
    import sail_tpu.plan.nodes as pn
    from sail_tpu.spec import data_type as dt
    # a KNOWN-small source (cost model sees 16 rows, far under the
    # mesh_min_rows floor) → the SPMD program is not worth dispatching
    small = pa.table({"a": list(range(16))})
    scan = pn.ScanExec(out_schema=(pn.Field("a", dt.LongType()),),
                       format="memory", source=small)
    d = router.decide_plan(scan, nparts=8, force="", mode="auto")
    assert (d.backend, d.reason) == ("xla", "dispatch-bound")
    d = router.decide_plan(scan, nparts=8, force="", mode="force")
    assert d.backend == "mesh"
    d = router.decide_plan(scan, nparts=8, force="xla", mode="auto")
    assert (d.backend, d.reason) == ("xla", "forced")
    d = router.decide_plan(scan, nparts=1, force="", mode="auto")
    assert (d.backend, d.reason) == ("xla", "unavailable")


def test_compile_bound_observation_reason():
    class Stage:
        sid = 0
        kind = "aggregate"
    import sail_tpu.plan.nodes as pn
    from sail_tpu.plan import stages as pst
    from sail_tpu.spec import data_type as dt
    scan = pn.ScanExec(out_schema=(pn.Field("a", dt.LongType()),),
                       format="memory")
    agg = pn.AggregateExec(scan, (0,), (), ("a",))
    stage = pst.FusedStage(0, agg, (agg, scan), "aggregate", False)
    # the SAME key the executor records under: compute ops, no leaves
    key = router.stage_obs_key(stage)
    assert key == router.obs_key((pst.node_fingerprint(agg),))
    router.note_stage(key, compile_s=1.0, exec_s=0.2)
    d = router.decide_stage(stage, native_ok=True)
    assert (d.backend, d.reason) == ("native", "compile-bound")
    router.clear_observations()
    d = router.decide_stage(stage, native_ok=True)
    assert (d.backend, d.reason) == ("native", "cost-model")
    d = router.decide_stage(stage, native_ok=False)
    assert d.backend == "xla"


# ---------------------------------------------------------------------------
# ops endpoint
# ---------------------------------------------------------------------------

def test_debug_compile_cache_endpoint(monkeypatch):
    from sail_tpu import obs_server
    monkeypatch.setenv("SAIL_TEST_SECRET_TOKEN", "hunter2-do-not-print")
    spark = _session()
    _make_t(spark)
    spark.sql(Q).toArrow()
    srv = obs_server.start()
    try:
        body = urllib.request.urlopen(
            srv.url + "/debug/compile_cache", timeout=10).read().decode()
        payload = json.loads(body)
        assert set(payload) == {"jax_cache_dir", "op_cache_entries",
                                "capacity"}
        assert payload["jax_cache_dir"] == pcache.place_jax_cache()
        assert payload["op_cache_entries"] >= 1
        assert {"entries", "pinned_count", "grow_count",
                "buckets"} <= set(payload["capacity"])
        # no-secret contract: cache state only, never config/env dumps
        for needle in ("SAIL_", "AWS_", "TOKEN", "SECRET", "hunter2"):
            assert needle not in body.replace(
                payload["jax_cache_dir"], "")
    finally:
        obs_server.stop()
