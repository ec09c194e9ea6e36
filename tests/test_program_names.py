"""Stage programs are named ``sail_<site>_<digest>`` from their
structural key alone (PR 26): the XLA module, the ``dispatch`` span and
the device trace all carry the name, and JAX's persistent cache keys it,
so it must not move with the data's seed, the process, or ``id()``."""

import json
import os
import subprocess
import sys

import pyarrow as pa
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu.exec import pcache
from sail_tpu.exec.local import clear_caches

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, os, sys
import jax.monitoring

counts = {"requests": 0, "hits": 0}


def _on_event(event, **_kw):
    # the two events benchmark/run.py's CompileCounter reads
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        counts["hits"] += 1


jax.monitoring.register_event_listener(_on_event)

import numpy as np
import pyarrow as pa
from sail_tpu import SparkSession, profiler

seed, route = int(sys.argv[1]), sys.argv[2]
rng = np.random.default_rng(seed)
n = 3000
flags = np.array(["A", "N", "R"])
# the dictionary's order is that of first appearance: pin it, as the
# benchmark's generator pins its pools
flag = np.concatenate([flags, flags[rng.integers(0, 3, n - 3)]])
lineitem = pa.table({
    "l_returnflag": pa.array(flag),
    "l_quantity": pa.array(rng.integers(1, 51, n).astype("float64")),
    "l_extendedprice": pa.array(rng.uniform(900, 105000, n)),
    "l_orderkey": pa.array(rng.permutation(n).astype("int64") // 4),
})
orders = pa.table({
    "o_orderkey": pa.array(np.arange(n // 4, dtype="int64")),
    "o_custkey": pa.array(rng.integers(0, 97, n // 4).astype("int64")),
})
conf = {"spark.sail.cache.result.enabled": "false"}
if route == "mesh":
    conf["spark.sail.execution.mesh"] = "force"
else:
    conf.update({"spark.sail.execution.mesh": "off",
                 "spark.sail.execution.backend.force": "xla"})
spark = SparkSession(conf)
spark.createDataFrame(lineitem).createOrReplaceTempView("lineitem")
spark.createDataFrame(orders).createOrReplaceTempView("orders")
names, answers, compile_spans = set(), [], 0
for sql in (
    "SELECT l_returnflag, sum(l_quantity) q, avg(l_extendedprice) p "
    "FROM lineitem WHERE l_quantity < 40 GROUP BY l_returnflag "
    "ORDER BY l_returnflag",
    "SELECT o_custkey, sum(l_extendedprice) s FROM lineitem JOIN orders "
    "ON l_orderkey = o_orderkey GROUP BY o_custkey ORDER BY s DESC LIMIT 5",
):
    answers.append(spark.sql(sql).toArrow().to_pylist())
    p = profiler.last_profile()
    names |= {s.attributes["program"] for s in p.spans
              if s.name == "dispatch"}
    compile_spans += p.span_count("compile")
mesh = getattr(spark, "_last_mesh_executor", None)
print("RESULT " + json.dumps({
    "names": sorted(names), "answers": answers,
    "compile_spans": compile_spans, **counts,
    "mesh_exchanges": mesh.last_exchanges if mesh is not None else 0,
    "entries": sorted(os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"]))}))
"""


def _in_a_fresh_interpreter(seed, cache_dir, hashseed, route="local"):
    """The two statements in a new process whose programs go to JAX's
    persistent cache in ``cache_dir`` (tests/conftest.py keeps that
    cache off for every other test)."""
    env = dict(os.environ)
    env.update({"JAX_ENABLE_COMPILATION_CACHE": "true",
                "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
                "PYTHONHASHSEED": str(hashseed),
                "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", "")})
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(seed), route],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    out["stderr"] = r.stderr
    return out


def _stage_entries(run):
    """The cache entries of stage programs: JAX names an entry after
    its module, ``jit_sail_<site>_<digest>-<sha>-cache``."""
    return [e for e in run["entries"] if e.startswith("jit_sail_")]


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """Other data, other hash seed, one cache directory (the directory
    is part of JAX's key, so only a shared one can be compared)."""
    cache = tmp_path_factory.mktemp("jax_cache")
    first = _in_a_fresh_interpreter(7, cache, hashseed=1)
    second = _in_a_fresh_interpreter(2**31 + 11, cache, hashseed=2)
    return first, second


def test_names_and_entry_digests_hold_across_seeds_and_processes(
        two_processes):
    a, b = two_processes
    assert a["names"] == b["names"]
    assert len(a["names"]) >= 4
    for name in a["names"]:
        site, _, digest = name[len("sail_"):].rpartition("_")
        assert name.startswith("sail_") and site
        assert len(digest) == 8 and int(digest, 16) >= 0
    sites = {n[len("sail_"):].rpartition("_")[0] for n in a["names"]}
    assert {"agg", "join_phase"} <= sites
    # JAX's cache keys the module, name included: every named program
    # has its entry, and the second process's are the same files
    assert {e.split("-")[0] for e in _stage_entries(a)} == \
        {"jit_" + n for n in a["names"]}
    assert _stage_entries(a) == _stage_entries(b)


def _second_was_answered_from_the_cache(a, b):
    """``b`` ran after ``a`` on one cache directory: it added no stage
    program's entry and its compile requests were cache hits. Small
    eager programs may miss (57 of 59 requests hit on the chip) and are
    not held."""
    assert a["hits"] == 0 and a["requests"] >= len(_stage_entries(a))
    assert _stage_entries(b) == _stage_entries(a)
    assert b["hits"] >= len(_stage_entries(a))
    assert b["requests"] - b["hits"] <= 2
    assert "Error reading persistent compilation cache" not in b["stderr"]


def test_a_second_process_compiles_no_stage_program(two_processes):
    """What every cell's warm ``setup_s`` rests on: a process that
    finds the first one's cache directory is answered from it."""
    a, b = two_processes
    assert len(_stage_entries(a)) >= 4
    _second_was_answered_from_the_cache(a, b)


def test_a_second_process_compiles_no_mesh_program(tmp_path):
    """The same for the whole-graph SPMD program on eight virtual
    devices (``mesh=force``)."""
    a = _in_a_fresh_interpreter(7, tmp_path, hashseed=1, route="mesh")
    b = _in_a_fresh_interpreter(2**31 + 11, tmp_path, hashseed=2,
                                route="mesh")
    assert a["mesh_exchanges"] >= 1 and b["mesh_exchanges"] >= 1
    mesh_entries = [e for e in _stage_entries(a)
                    if e.startswith("jit_sail_mesh_")]
    assert len(mesh_entries) == 2          # one program a statement
    _second_was_answered_from_the_cache(a, b)


def test_a_truncated_cache_entry_costs_a_compile_not_the_statement(
        tmp_path):
    """JAX reads a damaged entry as a miss (it warns, compiles, and
    leaves the file as it found it): the statement pays the compile
    and answers the same."""
    a = _in_a_fresh_interpreter(7, tmp_path, hashseed=1)
    damaged = _stage_entries(a)
    assert len(damaged) >= 4
    for entry in damaged:
        path = os.path.join(tmp_path, entry)
        os.truncate(path, os.path.getsize(path) // 2)
    b = _in_a_fresh_interpreter(7, tmp_path, hashseed=1)
    assert b["answers"] == a["answers"]
    assert b["compile_spans"] == a["compile_spans"] >= len(damaged)
    assert b["requests"] == a["requests"]
    assert b["hits"] <= b["requests"] - len(damaged)
    for entry in damaged:
        name = entry.split("-")[0]
        assert f"cache entry for '{name}'" in b["stderr"]


@pytest.fixture()
def spark():
    clear_caches()
    s = SparkSession({"spark.sail.execution.mesh": "off",
                      "spark.sail.cache.result.enabled": "false",
                      "spark.sail.execution.backend.force": "xla"})
    s.createDataFrame(pa.table({
        "k": pa.array([i % 5 for i in range(500)], pa.int64()),
        "v": pa.array([float(i) for i in range(500)]),
    })).createOrReplaceTempView("t")
    yield s
    clear_caches()


def _programs(profile):
    return [s.attributes["program"] for s in profile.spans
            if s.name == "dispatch"]


def test_structurally_different_stages_get_different_names(spark):
    spark.sql("SELECT k, sum(v) FROM t WHERE v < 100 GROUP BY k").toArrow()
    one = set(_programs(profiler.last_profile()))
    spark.sql("SELECT k, sum(v) FROM t WHERE v < 200 GROUP BY k").toArrow()
    two = set(_programs(profiler.last_profile()))
    assert one and two and one != two
    # the same structure again: the same names
    spark.sql("SELECT k, sum(v) FROM t WHERE v < 100 GROUP BY k").toArrow()
    assert set(_programs(profiler.last_profile())) == one


def test_a_second_execution_of_a_named_program_does_not_retrace(spark):
    sql = "SELECT k, sum(v) s FROM t GROUP BY k ORDER BY k"
    spark.sql(sql).toArrow()
    first = profiler.last_profile()
    assert first.compiled_programs >= 1
    spark.sql(sql).toArrow()
    second = profiler.last_profile()
    assert second.compiled_programs == 0
    assert second.span_count("compile") == 0
    assert _programs(second) == _programs(first)


def test_the_name_ignores_addresses_and_hash_order():
    class Opaque:
        pass

    a, b = Opaque(), Opaque()
    assert repr(a) != repr(b)
    assert pcache.program_name(("filter", a, 3)) == \
        pcache.program_name(("filter", b, 3))
    assert pcache.program_name(("filter", 1)) != \
        pcache.program_name(("filter", 2))
    assert pcache.program_name(("agg", 1)) != \
        pcache.program_name(("agg2", 1))
    assert pcache.program_name(("agg", 1)).startswith("sail_agg_")
    assert pcache.program_name(None).startswith("sail_op_")
    assert pcache.program_name((("nested",), 1)).startswith("sail_op_")


def test_the_jitted_module_carries_the_name():
    import jax
    import jax.numpy as jnp

    def builder():
        def fn(x):
            return x + 1
        return fn

    name = pcache.program_name(("project", "x+1"))
    text = jax.jit(pcache.named(builder(), name)).lower(
        jnp.ones(3)).as_text()
    assert f"module @jit_{name} " in text
    import functools
    wrapped = pcache.named(functools.partial(builder(), ), name)
    assert wrapped.__name__ == name


def test_the_mesh_program_is_named_from_its_structural_key():
    import re

    import numpy as np

    from sail_tpu.parallel.mesh import make_mesh
    from sail_tpu.parallel.mesh_exec import MeshExecutor

    spark = SparkSession({"spark.sail.cache.result.enabled": "false"})
    names = []
    for seed in (0, 1):                 # same plan, other data
        rng = np.random.default_rng(seed)
        spark.createDataFrame(pa.table({
            "k": rng.integers(0, 37, 4000),
            "v": rng.normal(size=4000)})).createOrReplaceTempView("m")
        node = spark._resolve(
            spark.sql("SELECT k, SUM(v) AS s FROM m GROUP BY k")._plan)
        conf = dict(spark.conf.items())
        conf["spark.sail.mesh.captureHlo"] = "true"
        ex = MeshExecutor(mesh=make_mesh(8), config=conf)
        assert ex.execute(node) is not None
        names.append(re.search(r"module @jit_(sail_mesh_[0-9a-f]{8}) ",
                               ex.last_hlo).group(1))
    assert names[0] == names[1]


def test_generated_names_do_not_depend_on_who_else_is_resolving(spark):
    """Two sessions resolving at once (the benchmark's first calls) used
    to draw generated column names from one module-global counter: the
    op keys, and with them the program names, came out different."""
    import sys
    import threading

    from sail_tpu.plan.stages import plan_fingerprint_hash

    sql = ("SELECT k, sum(v) AS s, avg(v) AS a FROM t WHERE v < 300 "
           "GROUP BY k ORDER BY s DESC")
    alone = plan_fingerprint_hash(spark._resolve(spark.sql(sql)._plan))
    seen, errors = set(), []
    start = threading.Barrier(4)

    def work():
        try:
            start.wait(timeout=30)
            for _ in range(40):
                seen.add(plan_fingerprint_hash(
                    spark._resolve(spark.sql(sql)._plan)))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)     # interleave the resolves for certain
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert seen == {alone}
