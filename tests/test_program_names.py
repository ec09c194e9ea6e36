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
import numpy as np
import pyarrow as pa
from sail_tpu import SparkSession, profiler

seed = int(sys.argv[1])
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
spark = SparkSession({"spark.sail.execution.mesh": "off",
                      "spark.sail.cache.result.enabled": "false",
                      "spark.sail.execution.backend.force": "xla"})
spark.createDataFrame(lineitem).createOrReplaceTempView("lineitem")
spark.createDataFrame(orders).createOrReplaceTempView("orders")
names = set()
for sql in (
    "SELECT l_returnflag, sum(l_quantity) q, avg(l_extendedprice) p "
    "FROM lineitem WHERE l_quantity < 40 GROUP BY l_returnflag "
    "ORDER BY l_returnflag",
    "SELECT o_custkey, sum(l_extendedprice) s FROM lineitem JOIN orders "
    "ON l_orderkey = o_orderkey GROUP BY o_custkey ORDER BY s DESC LIMIT 5",
):
    spark.sql(sql).toArrow()
    p = profiler.last_profile()
    names |= {s.attributes["program"] for s in p.spans
              if s.name == "dispatch"}
store = os.environ["SAIL_COMPILE_CACHE__DIR"]
print("RESULT " + json.dumps({
    "names": sorted(names),
    "digests": sorted(f for f in os.listdir(store)
                      if f.endswith(".sailpc"))}))
"""


def _in_a_fresh_interpreter(seed, store, hashseed):
    env = dict(os.environ)
    env.update({"SAIL_COMPILE_CACHE__DIR": str(store),
                "SAIL_COMPILE_CACHE__ENABLED": "1",
                "PYTHONHASHSEED": str(hashseed),
                "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", "")})
    r = subprocess.run([sys.executable, "-c", _SCRIPT, str(seed)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_names_and_entry_digests_hold_across_seeds_and_processes(tmp_path):
    a = _in_a_fresh_interpreter(7, tmp_path / "a", hashseed=1)
    b = _in_a_fresh_interpreter(2**31 + 11, tmp_path / "b", hashseed=2)
    assert a["names"] == b["names"]
    assert len(a["names"]) >= 4
    for name in a["names"]:
        site, _, digest = name[len("sail_"):].rpartition("_")
        assert name.startswith("sail_") and site
        assert len(digest) == 8 and int(digest, 16) >= 0
    sites = {n[len("sail_"):].rpartition("_")[0] for n in a["names"]}
    assert {"agg", "join_phase"} <= sites
    # the persistent store's entries are named from the same structure
    # (key repr + dictionary CONTENT + signature): the same files
    assert a["digests"] and a["digests"] == b["digests"]


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
