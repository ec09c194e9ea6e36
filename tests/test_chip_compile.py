"""Ask the v5e compiler, without a chip, whether it takes the programs of
the served path: a compile for a DESCRIBED ``v5e:2x2`` topology raises
what the chip's compiler would raise (a 64-bit operator the TPU backend
lacks, a program that does not fit 16 GB). Nothing runs, so a pass here
is a compile that passed — never a chip run.

Everything that touches libtpu happens inside fixtures and tests of
THIS file (only one process may load the TPU library; under xdist the
worker that is given this file is that process). Sizes: the q1 stage
compiles at its real SF1 capacity; the sort-based programs compile at
4096 rows, because with libtpu 0.0.34 every large sort costs 30–150 s
of compile time (measured in PR 23, see PERF.md) and these tests are
meant to take seconds. The 64-bit lowering they guard is the same at
either size.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from sail_tpu.columnar.batch import Column, DeviceBatch
from sail_tpu.ops import aggregate as aggk
from sail_tpu.ops import join as joink
from sail_tpu.ops import sort as sortk
from sail_tpu.spec import data_type as dt

#: capacity the engine gives SF1's 5,995,559-row lineitem
SF1_LINEITEM_CAPACITY = 6_291_456
HBM_BYTES = 16 * (1 << 30)
SORT_ROWS = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices[:4]), ("data",))


@pytest.fixture
def masked_branch(monkeypatch):
    """Take the chip-only [G, n] compare-and-select branch of
    ops/aggregate._seg_reduce, as ``jax.default_backend() == "tpu"``
    would (the code asks the attached backend, which is the CPU here)."""
    monkeypatch.setattr(aggk, "_masked_max_segments",
                        lambda: aggk._MASKED_SEGMENTS_MAX)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, mem
    return compiled, mem


def _shaped(tree, sharding, rows=None):
    """Arrays of a call → ShapeDtypeStructs on the described device,
    with the row dimension stretched to ``rows``."""
    def one(x):
        shape = x.shape if rows is None or not x.shape \
            else (rows,) + tuple(x.shape[1:])
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)
    return jax.tree_util.tree_map(one, tree)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def test_q1_fused_stage_masked_branch_at_sf1_capacity(
        one_chip, masked_branch, monkeypatch):
    """The real fused q1 stage (filter → decimal arithmetic → direct-
    binned group-by → eight aggregates), captured from the executor on a
    small table and compiled at SF1's lineitem capacity. The [G, n]
    compare-and-select must fuse into the reductions: a materialised
    one would be G x n x 8 bytes for every aggregate."""
    from sail_tpu import SparkSession
    from sail_tpu.benchmarks.tpch_data import generate_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    from sail_tpu.exec.local import LocalExecutor

    captured = {}
    real_jitted = LocalExecutor._jitted

    def spy(self, key, dict_objs, builder, fused=False):
        def capturing_builder():
            fn, aux = builder()
            if key is not None and key[0] == "agg":
                captured["fn"] = fn
            return fn, aux
        fn, aux = real_jitted(self, key, dict_objs, capturing_builder,
                              fused)
        if "fn" in captured and "args" not in captured:
            def recording(*args):
                captured["args"] = args
                return fn(*args)
            return recording, aux
        return fn, aux

    monkeypatch.setattr(LocalExecutor, "_jitted", spy)
    spark = SparkSession({"spark.sail.execution.mesh": "off",
                          "spark.sail.execution.backend.force": "xla"})
    lineitem = generate_tpch(sf=0.001, seed=1)["lineitem"]
    spark.createDataFrame(lineitem).createOrReplaceTempView("lineitem")
    assert spark.sql(QUERIES[1]).toArrow().num_rows == 4
    assert "args" in captured, "q1 did not go through the fused agg stage"

    shapes = _shaped(captured["args"], one_chip, SF1_LINEITEM_CAPACITY)
    _compiled, mem = _compile(captured["fn"], *shapes)
    groups = (3 + 1) * (2 + 1) + 1      # returnflag x linestatus + trash
    one_materialised = groups * SF1_LINEITEM_CAPACITY * 8
    assert mem.temp_size_in_bytes < one_materialised, mem


def test_masked_segment_sum_int64_and_float64(one_chip, masked_branch):
    n = SF1_LINEITEM_CAPACITY

    def fn(ints, floats, seg):
        return (aggk._seg_sum(ints, seg, 13), aggk._seg_sum(floats, seg, 13),
                aggk._seg_reduce(ints, seg, 13, "min", 1 << 62))

    _compiled, mem = _compile(
        fn, jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.float64, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    assert mem.temp_size_in_bytes < 13 * n * 8, mem


def test_sort_group_by_on_packed_uint64_key(one_chip):
    """ops/aggregate.group_aggregate's sorted route over two int32 keys:
    one stable sort carrying a nullable key and the values, prefix sums
    (int64) and segmented scans (float64 sum, int64 min, first non-null)
    in key order, the compacting sort; count(*)."""
    n = SORT_ROWS

    def fn(k1, v1, k2, dec, dbl, sel):
        keys = [Column(k1, v1, dt.IntegerType()),
                Column(k2, None, dt.IntegerType())]
        g = aggk.group_aggregate(
            keys, sel, n,
            [aggk.AggCall("sum", Column(dec, None, dt.LongType()),
                          dt.LongType()),
             aggk.AggCall("sum", Column(dbl, None, dt.DoubleType()),
                          dt.DoubleType()),
             aggk.AggCall("count"),
             aggk.AggCall("min", Column(dec, v1, dt.LongType())),
             aggk.AggCall("first", Column(dbl, v1, dt.DoubleType()))])
        return ([c.data for c in g.keys], [c.data for c in g.aggs], g.sel)

    def s(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    _compile(fn, s(jnp.int32), s(jnp.bool_), s(jnp.int32), s(jnp.int64),
             s(jnp.float64), s(jnp.bool_))


def test_sorted_build_merge_probe_join(one_chip):
    """ops/join: the build side ordered by ONE sort on (dead flag, int64
    key) that carries the row number, so sorted keys and permutation come
    out of the sort and nothing is gathered through an index; probe keys
    merged into it by one more sort and two scans (no loop of gathers),
    the expanding materialisation, the unique fast path."""
    bn, pn, out_cap = SORT_ROWS, SORT_ROWS, 2 * SORT_ROWS

    def fn(bkey, bpay, bsel, pkey, ppay, psel):
        bcols = [Column(bkey, None, dt.LongType())]
        pcols = [Column(pkey, None, dt.LongType())]
        bt = joink.build_side(bcols, bsel)
        ranges = joink.probe_ranges(bt, pcols, psel)
        probe = DeviceBatch({"k": pcols[0],
                             "p": Column(ppay, None, dt.DoubleType())}, psel)
        build = DeviceBatch({"b": Column(bpay, None, dt.LongType())}, bsel)
        uniq = joink.join_unique(bt, ranges, probe, build, "inner", ["b"])
        exp = joink.join_expand(bt, ranges, probe, build, "inner", ["b"],
                                out_cap)
        return (uniq.columns["b"].data, uniq.sel,
                exp.batch.columns["b"].data, exp.batch.sel,
                joink.join_output_count(ranges, psel, "inner"),
                joink.has_duplicate_build_keys(bt))

    def s(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    _compile(fn, s(bn, jnp.int64), s(bn, jnp.int64), s(bn, jnp.bool_),
             s(pn, jnp.int64), s(pn, jnp.float64), s(pn, jnp.bool_))


def test_multi_key_sort(one_chip):
    """ops/sort.lexsort_perm: decimal desc, date asc, a nullable double
    both ways (doubles sort by value: the compiler has no f64→u64
    bitcast for order bits)."""
    n = SORT_ROWS

    def fn(dec, date, dbl, dbl_valid, sel):
        return sortk.lexsort_perm(
            [(dec, None, dt.LongType(), False, None),
             (date, None, dt.DateType(), True, None),
             (dbl, dbl_valid, dt.DoubleType(), True, None),
             (dbl, dbl_valid, dt.DoubleType(), False, None)], sel)

    def s(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    _compile(fn, s(jnp.int64), s(jnp.int32), s(jnp.float64), s(jnp.bool_),
             s(jnp.bool_))


def test_double_as_group_or_join_key_is_still_refused(one_chip):
    """Known gap, kept visible: a DOUBLE column as a group-by, join or
    shuffle key goes through ops/hash._to_bits, whose float64→uint64
    bitcast the TPU compiler refuses. It fails loudly at compile time;
    when this test starts failing, the gap is closed — update PERF.md."""
    from sail_tpu.ops.hash import hash64

    with pytest.raises(Exception, match="X64"):
        jax.jit(lambda x: hash64([x], [dt.DoubleType()])).lower(
            jax.ShapeDtypeStruct((SORT_ROWS,), jnp.float64,
                                 sharding=one_chip)).compile()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _collectives(compiled) -> str:
    text = compiled.as_text()
    return " ".join(op for op in ("all-to-all", "all-gather", "all-reduce")
                    if op in text)


def test_hash_shuffle_all_to_all_on_four_chips(four_chips):
    """parallel/exchange.make_shuffle: local bucket sort + all_to_all."""
    from sail_tpu.parallel.exchange import make_shuffle
    n, bucket_cap = SORT_ROWS, SORT_ROWS // 2
    sharded = NamedSharding(four_chips, P("data"))

    def s(dtype):
        return jax.ShapeDtypeStruct((4, n), dtype, sharding=sharded)

    shuffle = make_shuffle(four_chips, 2, [False, True], bucket_cap)
    compiled, _mem = _compile(
        shuffle, (s(jnp.int64), s(jnp.float64)), (None, s(jnp.bool_)),
        s(jnp.bool_), s(jnp.int32))
    assert "all-to-all" in _collectives(compiled)


def test_distributed_aggregate_on_four_chips(four_chips):
    """parallel/dist_ops.make_distributed_agg: partial aggregate →
    hash all_to_all of the partial rows → final aggregate, one SPMD
    program over the four-device mesh."""
    from sail_tpu.parallel.dist_ops import make_distributed_agg
    n = SORT_ROWS
    sharded = NamedSharding(four_chips, P("data"))

    def s(dtype):
        return jax.ShapeDtypeStruct((4, n), dtype, sharding=sharded)

    agg = make_distributed_agg(four_chips, dt.LongType(), 2,
                               local_groups=1024, bucket_cap=512)
    compiled = agg.lower(s(jnp.int64), (s(jnp.float64), s(jnp.float64)),
                         s(jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES
    assert "all-to-all" in _collectives(compiled)
