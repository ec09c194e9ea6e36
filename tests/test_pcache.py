"""Persistent compiled-program cache (exec/pcache.py) + per-stage
backend router (exec/router.py).

- cross-"process" store/load round trip (fresh in-memory caches load
  stored AOT executables; results bit-identical);
- chaos: truncated entries, header/version skew, injected ``io.cache``
  faults, concurrent multi-process writers — every failure falls back
  to JIT with correct results and counted load errors;
- compile-time-weighted eviction under ``compile_cache.max_mb``;
- cache on/off bit-identical TPC-H subset + ClickBench;
- router: force overrides, deterministic per-fingerprint decisions,
  plan-level mesh gate, EXPLAIN / FORMAT JSON / event surfaces;
- ``/debug/compile_cache`` ops endpoint shape + no-secret contract.
"""

import glob
import json
import os
import subprocess
import sys
import urllib.request

import pyarrow as pa
import pytest

from sail_tpu import SparkSession, faults, profiler
from sail_tpu import metrics as gm
from sail_tpu.exec import pcache, router
from sail_tpu.exec.local import clear_caches

pytestmark = []


@pytest.fixture(autouse=True)
def _reset_after():
    yield
    clear_caches()
    router.clear_observations()
    faults.reset()
    pcache.reload()


@pytest.fixture
def store(tmp_path, monkeypatch):
    d = str(tmp_path / "pc")
    monkeypatch.setenv("SAIL_COMPILE_CACHE__DIR", d)
    monkeypatch.setenv("SAIL_COMPILE_CACHE__ENABLED", "1")
    monkeypatch.delenv("SAIL_COMPILE_CACHE__MAX_MB", raising=False)
    pcache.reload()
    clear_caches()
    return d


def _session(**conf):
    base = {"spark.sail.execution.mesh": "off"}
    base.update(conf)
    return SparkSession(base)


def _counter(name: str) -> float:
    for row in gm.REGISTRY.snapshot():
        if row["name"] == name and row["attributes"] == "{}":
            return float(row["value"])
    return 0.0


Q = ("SELECT a % 5 AS g, sum(b) AS s, count(*) AS n "
     "FROM t WHERE a > 3 GROUP BY a % 5 ORDER BY g")


def _make_t(spark, n=500):
    t = pa.table({"a": list(range(n)),
                  "b": [float(i) * 0.5 for i in range(n)]})
    spark.createDataFrame(t).createOrReplaceTempView("t")


def _canon(table: pa.Table) -> pa.Table:
    order = [(n, "ascending") for n in table.column_names]
    return table.sort_by(order)


# ---------------------------------------------------------------------------
# store/load round trip
# ---------------------------------------------------------------------------

def test_store_then_load_bit_identical(store):
    spark = _session()
    _make_t(spark)
    first = spark.sql(Q).toArrow()
    entries = glob.glob(os.path.join(store, "*.sailpc"))
    assert entries, "no AOT entries were stored"
    # simulate a fresh process: wipe the in-memory operator caches so
    # every program re-binds — the persistent store must serve it
    clear_caches()
    second = spark.sql(Q).toArrow()
    prof = profiler.last_profile()
    assert prof.persistent_hits > 0
    assert prof.persistent_misses == 0
    assert first.equals(second)


def test_compile_events_distinguish_sources(store):
    spark = _session()
    _make_t(spark)
    spark.sql(Q).toArrow()
    assert all(e["source"] == "trace"
               for e in profiler.last_profile().compile_events)
    assert profiler.last_profile().compiled_programs > 0
    clear_caches()
    spark.sql(Q).toArrow()
    sources = {e["source"]
               for e in profiler.last_profile().compile_events}
    assert sources == {"persistent"}
    # nothing traced: the misses= figure is a direct trace count, not
    # a key-minus-signature subtraction
    assert profiler.last_profile().compiled_programs == 0
    assert "misses=0" in profiler.last_profile().render()
    # the EXPLAIN ANALYZE compile: line reports the cache ladder
    text = profiler.last_profile().render()
    assert "compile: memory_hits=" in text
    assert "persistent_hits=" in text


def test_disabled_without_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("SAIL_COMPILE_CACHE__DIR", raising=False)
    monkeypatch.setenv("SAIL_COMPILE_CACHE__ENABLED", "1")
    pcache.reload()
    assert not pcache.enabled()


def test_session_conf_opt_out(store):
    spark = _session(**{"spark.sail.compileCache.enabled": "false"})
    _make_t(spark)
    spark.sql(Q).toArrow()
    assert not glob.glob(os.path.join(store, "*.sailpc"))


# ---------------------------------------------------------------------------
# chaos: corruption, skew, faults, concurrency
# ---------------------------------------------------------------------------

def test_truncated_entry_falls_back_to_jit(store):
    spark = _session()
    _make_t(spark)
    expected = spark.sql(Q).toArrow()
    for path in glob.glob(os.path.join(store, "*.sailpc")):
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:max(16, len(blob) // 2)])
    errors0 = _counter("execution.compile.persistent_load_error_count")
    clear_caches()
    out = spark.sql(Q).toArrow()
    assert out.equals(expected)
    prof = profiler.last_profile()
    assert prof.persistent_hits == 0
    assert _counter(
        "execution.compile.persistent_load_error_count") > errors0


def test_version_skew_reads_as_miss(store, monkeypatch):
    spark = _session()
    _make_t(spark)
    expected = spark.sql(Q).toArrow()
    assert glob.glob(os.path.join(store, "*.sailpc"))
    real = pcache.env_fingerprint()
    monkeypatch.setattr(pcache, "env_fingerprint",
                        lambda: real[:1] + ("jax-from-the-future",)
                        + real[2:])
    clear_caches()
    out = spark.sql(Q).toArrow()
    prof = profiler.last_profile()
    assert prof.persistent_hits == 0       # skewed keys never match
    assert prof.persistent_misses > 0
    assert out.equals(expected)


def test_header_skew_counts_load_error(store, monkeypatch):
    spark = _session()
    _make_t(spark)
    expected = spark.sql(Q).toArrow()
    # same digest, incompatible on-disk format version in the header:
    # the load must reject the entry, count it, and recompile
    monkeypatch.setattr(pcache, "FORMAT_VERSION", pcache.FORMAT_VERSION)
    for path in glob.glob(os.path.join(store, "*.sailpc")):
        blob = open(path, "rb").read()
        nl = blob.index(b"\n", len(b"SAILPC1\n"))
        header = json.loads(blob[len(b"SAILPC1\n"):nl + 1])
        header["v"] = 99
        with open(path, "wb") as f:
            f.write(b"SAILPC1\n")
            f.write(json.dumps(header).encode() + b"\n")
            f.write(blob[nl + 1:])
    errors0 = _counter("execution.compile.persistent_load_error_count")
    clear_caches()
    out = spark.sql(Q).toArrow()
    assert out.equals(expected)
    assert _counter(
        "execution.compile.persistent_load_error_count") > errors0


def test_io_cache_fault_injection_falls_back(store):
    spark = _session()
    _make_t(spark)
    expected = spark.sql(Q).toArrow()
    faults.configure("io.cache:load*=error")
    clear_caches()
    out = spark.sql(Q).toArrow()
    assert out.equals(expected)
    prof = profiler.last_profile()
    assert prof.persistent_hits == 0
    assert faults.injection_counts().get("io.cache", 0) > 0


def test_concurrent_multiprocess_writers(store):
    """N processes racing stores on the SAME digests: every surviving
    entry must be complete and loadable (tmp + atomic rename)."""
    script = r"""
import os, sys, time
import jax, jax.numpy as jnp
from sail_tpu.exec import pcache
idx = int(sys.argv[1])

def fn(x):
    return jnp.sin(x) * (1.0 + jnp.cos(x))

x = jnp.arange(256, dtype=jnp.float32)
sig = pcache.signature((x,))
digest = pcache.entry_digest("shared-key", "d0", sig)
mine = pcache.entry_digest(f"key-{idx}", "d0", sig)
compiled = jax.jit(fn).lower(x).compile()
for _ in range(10):
    pcache.store(digest, compiled, 0.5, site="test")
    pcache.store(mine, compiled, 0.1, site="test")
print("WROTE", digest, mine)
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SAIL_COMPILE_CACHE__DIR"] = store
    env["SAIL_COMPILE_CACHE__ENABLED"] = "1"
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(i)],
        env=env, stdout=subprocess.PIPE, text=True)
        for i in range(3)]
    digests = set()
    for p in procs:
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0
        for line in out.splitlines():
            if line.startswith("WROTE "):
                digests.update(line.split()[1:])
    assert len(digests) == 4  # 1 shared + 3 private
    for digest in digests:
        assert pcache.load(digest, site="test") is not None


_DEVICE_BOUND_SCRIPT = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from sail_tpu.exec import pcache

assert len(jax.devices()) == 8
mesh = Mesh(np.array(jax.devices()), ("data",))
x1 = jnp.arange(64, dtype=jnp.float32)
x8 = jax.device_put(jnp.arange(64, dtype=jnp.float32),
                    NamedSharding(mesh, P("data")))
single = lambda x: x * 2.0 + 1.0
spmd = jax.shard_map(lambda x: jax.lax.psum(jnp.sum(x), "data"),
                     mesh=mesh, in_specs=P("data"), out_specs=P())
d1 = pcache.entry_digest("one-device", "d0", pcache.signature((x1,)))
d8 = pcache.entry_digest("eight-devices", "d0", pcache.signature((x8,)))
if sys.argv[1] == "store":
    assert pcache.store(d1, jax.jit(single).lower(x1).compile(), 0.1)
    assert pcache.store(d8, jax.jit(spmd).lower(x8).compile(), 0.1)
else:
    f1, f8 = pcache.load(d1), pcache.load(d8)
    assert f1 is not None and f8 is not None
    np.testing.assert_array_equal(np.asarray(f1(x1)), np.arange(64) * 2.0 + 1)
    assert float(f8(x8)) == float(np.arange(64).sum())
print("OK", sys.argv[1])
"""


def test_single_device_and_mesh_programs_load_in_fresh_interpreter(store):
    """jax 0.9 binds a deserialized executable to EVERY device of the
    backend unless told otherwise; the store records the devices each
    program was compiled for, so a one-device program and an
    eight-device program both load in a new process and run."""
    env = dict(os.environ)
    env["SAIL_COMPILE_CACHE__DIR"] = store
    env["SAIL_COMPILE_CACHE__ENABLED"] = "1"
    for mode in ("store", "load"):
        r = subprocess.run(
            [sys.executable, "-c", _DEVICE_BOUND_SCRIPT, mode], env=env,
            capture_output=True, text=True, timeout=180)
        assert r.returncode == 0, r.stderr[-2000:]
        assert f"OK {mode}" in r.stdout


def test_entry_for_absent_devices_is_a_miss_not_a_crash(store):
    import jax
    import jax.numpy as jnp
    x = jnp.arange(16, dtype=jnp.float32)
    digest = pcache.entry_digest("k", "d0", pcache.signature((x,)))
    compiled = jax.jit(lambda v: v + 1).lower(x).compile()
    assert pcache.store(digest, compiled, 0.1)
    path = os.path.join(store, digest + ".sailpc")
    blob = open(path, "rb").read()
    assert b'"devices":[0]' in blob
    with open(path, "wb") as f:
        f.write(blob.replace(b'"devices":[0]', b'"devices":[4096]'))
    fn, reason = pcache._load(digest)
    assert fn is None and reason == "skew"
    assert not os.path.exists(path + ".bad")


_JAX_CACHE_SCRIPT = r"""
import os
import jax, jax.numpy as jnp
from sail_tpu.exec import pcache
before = jax.config.jax_compilation_cache_dir
placed = pcache.place_jax_cache()
pcache.enabled()      # the executors' first consult: must not re-place
jax.jit(lambda x: jnp.tanh(x) * 3.0)(jnp.arange(7.0)).block_until_ready()
print("PLACED", placed)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("UNTOUCHED", before == jax.config.jax_compilation_cache_dir)
print("ENTRIES", len(os.listdir(placed)))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_jax_cache_goes_where_the_environment_says(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: the program never overrides it.
    Unset: one fixed directory in the checkout, whatever
    compile_cache.dir says."""
    env = dict(os.environ)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    env["SAIL_COMPILE_CACHE__DIR"] = str(tmp_path / "aot")
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
    assert not os.path.exists(tmp_path / "aot" / "xla")
    assert pcache.JAX_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def test_eviction_cheapest_compile_first(store, monkeypatch):
    monkeypatch.setenv("SAIL_COMPILE_CACHE__MAX_MB", "1")
    pcache.reload()
    payload = os.urandom(300 * 1024)
    # five ~300KB entries with ascending compile cost; 1MB budget keeps
    # only the most expensive ones
    for i in range(5):
        digest = pcache.entry_digest(f"evict-{i}", "d0", ("sig",))
        header = {"v": pcache.FORMAT_VERSION, "digest": digest,
                  "env": list(pcache.env_fingerprint()),
                  "compile_s": float(i), "site": "test", "created": 0}
        path = os.path.join(store, digest + ".sailpc")
        os.makedirs(store, exist_ok=True)
        with open(path, "wb") as f:
            f.write(b"SAILPC1\n")
            f.write(json.dumps(header).encode() + b"\n")
            f.write(payload)
    evicted0 = _counter("execution.compile.persistent_evict_count")
    pcache._evict_to_budget()
    left = sorted(glob.glob(os.path.join(store, "*.sailpc")))
    total = sum(os.path.getsize(p) for p in left)
    assert total <= 1 << 20
    assert _counter(
        "execution.compile.persistent_evict_count") > evicted0
    survivors = {json.loads(
        open(p, "rb").read().split(b"\n", 1)[1]
        .split(b"\n", 1)[0])["compile_s"] for p in left}
    # the cheap-to-recompile entries (lowest compile_s) died first,
    # and eviction stopped as soon as the store fit the budget
    assert survivors == {2.0, 3.0, 4.0}


def test_undeserializable_entry_poisoned_once(store, monkeypatch):
    """An INTACT entry whose executable cannot load in a fresh process
    (jaxlib 'Symbols not found' class) is poison-marked: later loads
    are fast misses without repeated load errors, and the digest is
    never re-stored."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        return x * 2
    x = jnp.arange(8)
    compiled = jax.jit(fn).lower(x).compile()
    digest = pcache.entry_digest("poison-key", "d0",
                                 pcache.signature((x,)))
    assert pcache.store(digest, compiled, 0.3, site="test")
    from jax.experimental import serialize_executable as se
    monkeypatch.setattr(se, "deserialize_and_load",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("Symbols not found")))
    errors0 = _counter("execution.compile.persistent_load_error_count")
    assert pcache.load(digest, site="test") is None
    assert _counter(
        "execution.compile.persistent_load_error_count") == errors0 + 1
    assert os.path.exists(os.path.join(store, digest + ".bad"))
    monkeypatch.undo()
    # poisoned: no further load attempt (no new error), store refused
    assert pcache.load(digest, site="test") is None
    assert _counter(
        "execution.compile.persistent_load_error_count") == errors0 + 1
    assert pcache.store(digest, compiled, 0.3, site="test") is False


def test_stale_writer_tmp_reaped(store):
    """A writer killed mid-store leaves .tmp-* garbage; the next store
    scan reaps anything past the reap age (fresh tmps are spared — a
    live writer may still own them)."""
    os.makedirs(store, exist_ok=True)
    stale = os.path.join(store, ".tmp-999-1-deadbeef")
    fresh = os.path.join(store, ".tmp-999-2-cafebabe")
    for p in (stale, fresh):
        with open(p, "wb") as f:
            f.write(b"partial write")
    old = __import__("time").time() - 2 * pcache._TMP_REAP_S
    os.utime(stale, (old, old))
    pcache._scan_entries()
    assert not os.path.exists(stale)
    assert os.path.exists(fresh)


def test_corrupt_entry_deleted_for_repair(store):
    """Garbage bytes under a digest are removed on the failed load, so
    the next compile re-stores a good entry."""
    digest = pcache.entry_digest("repair-key", "d0", ("sig",))
    os.makedirs(store, exist_ok=True)
    path = os.path.join(store, digest + ".sailpc")
    with open(path, "wb") as f:
        f.write(b"not an entry at all")
    assert pcache.load(digest, site="test") is None
    assert not os.path.exists(path)
    assert not os.path.exists(os.path.join(store, digest + ".bad"))


def test_unpersistable_identity_key(store):
    class Opaque:
        pass
    assert pcache.entry_digest(repr(("k", Opaque())), "d0",
                               ("sig",)) is None


# ---------------------------------------------------------------------------
# cache on/off equivalence: TPC-H subset + ClickBench
# ---------------------------------------------------------------------------

def _tpch_results(spark, queries, sf=0.01):
    from sail_tpu.benchmarks.tpch_data import register_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    register_tpch(spark, sf=sf)
    return {q: _canon(spark.sql(QUERIES[q]).toArrow()) for q in queries}


def test_tpch_subset_bit_identical_on_vs_off(store, monkeypatch):
    queries = (1, 5, 18)
    spark = _session()
    baseline_store = _tpch_results(spark, queries)   # populates
    clear_caches()
    loaded = _tpch_results(spark, queries)           # persistent hits
    assert profiler.last_profile().persistent_hits > 0
    monkeypatch.setenv("SAIL_COMPILE_CACHE__ENABLED", "0")
    pcache.reload()
    clear_caches()
    plain = _tpch_results(spark, queries)
    for q in queries:
        assert baseline_store[q].equals(plain[q]), f"q{q} drifted"
        assert loaded[q].equals(plain[q]), f"q{q} drifted on load"


def test_clickbench_subset_bit_identical_on_vs_off(store, monkeypatch):
    from sail_tpu.benchmarks.clickbench import load_queries, register_hits
    spark = _session()
    register_hits(spark, n_rows=2000)
    queries = list(load_queries())[:10]
    with_store = [_canon(spark.sql(q).toArrow()) for q in queries]
    clear_caches()
    loaded = [_canon(spark.sql(q).toArrow()) for q in queries]
    monkeypatch.setenv("SAIL_COMPILE_CACHE__ENABLED", "0")
    pcache.reload()
    clear_caches()
    plain = [_canon(spark.sql(q).toArrow()) for q in queries]
    for i, (a, b, c) in enumerate(zip(with_store, loaded, plain)):
        assert a.equals(c), f"clickbench q{i + 1} drifted"
        assert b.equals(c), f"clickbench q{i + 1} drifted on load"


@pytest.mark.slow
def test_clickbench_full_bit_identical_on_vs_off(store, monkeypatch):
    from sail_tpu.benchmarks.clickbench import load_queries, register_hits
    spark = _session()
    register_hits(spark, n_rows=2000)
    queries = list(load_queries())
    with_store = [_canon(spark.sql(q).toArrow()) for q in queries]
    monkeypatch.setenv("SAIL_COMPILE_CACHE__ENABLED", "0")
    pcache.reload()
    clear_caches()
    plain = [_canon(spark.sql(q).toArrow()) for q in queries]
    for i, (a, c) in enumerate(zip(with_store, plain)):
        assert a.equals(c), f"clickbench q{i + 1} drifted"


# ---------------------------------------------------------------------------
# backend router
# ---------------------------------------------------------------------------

def test_force_xla_disables_native(store):
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


def test_default_route_is_deterministic(store):
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


def test_explain_renders_backend_line(store):
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


def test_backend_route_events_recorded(store):
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

def test_debug_compile_cache_endpoint(store):
    from sail_tpu import obs_server
    spark = _session()
    _make_t(spark)
    spark.sql(Q).toArrow()
    clear_caches()
    spark.sql(Q).toArrow()   # persistent hits for the tally
    srv = obs_server.start()
    try:
        body = urllib.request.urlopen(
            srv.url + "/debug/compile_cache", timeout=10).read().decode()
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert payload["entries"] >= 1
        assert payload["bytes"] > 0
        assert payload["counters"]["hit"] >= 1
        assert payload["hit_ratio"] is not None
        assert payload["top_by_saved"], "hit tally missing"
        top = payload["top_by_saved"][0]
        assert {"digest", "hits", "compile_s", "saved_s",
                "site"} <= set(top)
        # no-secret contract: cache state only, never config/env dumps
        for needle in ("SAIL_", "AWS_", "TOKEN", "SECRET"):
            assert needle not in body.replace(store, "")
    finally:
        obs_server.stop()
