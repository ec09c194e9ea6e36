"""Distributed relational operators over a device mesh.

Reference role: the distributed execution of stages — partial aggregation,
hash-shuffled final aggregation, broadcast joins — that the reference runs
as tasks exchanging Arrow Flight streams (SURVEY.md §2.5). Here a whole
multi-stage pipeline is ONE jitted SPMD program: per-shard relational
kernels (the same sort/segment primitives as the local engine) composed
with `all_to_all` / `all_gather` collectives inside `jax.shard_map`.

Used by the multichip dry-run and (in later rounds) the distributed
executor's stage compiler.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.batch import Column
from ..ops import aggregate as aggk
from ..ops import join as joink
from ..ops.hash import hash64
from ..spec import data_type as dt
from .mesh import DATA_AXIS
from .exchange import bucket_by_partition


def partition_arrays(arrays: Sequence[np.ndarray], n: int, num_partitions: int,
                     cap_per_part: Optional[int] = None):
    """Host-side: split n rows contiguously into [P, cap] (shared layout
    helper: parallel.mesh.partition_rows)."""
    from .mesh import partition_rows
    per = -(-n // num_partitions)
    cap = cap_per_part or max(8, per)
    out = [partition_rows(a, num_partitions, cap) for a in arrays]
    sel = partition_rows(np.ones(n, dtype=bool), num_partitions, cap)
    return out, sel


def _local_partial_agg(key_data, key_type: dt.DataType, vals, sel, max_groups):
    """Per-shard partial aggregation: returns (group key, partial sums,
    partial counts, group sel)."""
    calls = [aggk.AggCall("sum", Column(v, None, dt.DoubleType()),
                          dt.DoubleType()) for v in vals]
    g = aggk.group_aggregate([Column(key_data, None, key_type)], sel,
                             max_groups, calls + [aggk.AggCall("count")])
    return (g.keys[0].data, [c.data for c in g.aggs[:-1]], g.aggs[-1].data,
            g.sel, g.overflow)


def make_distributed_agg(mesh: Mesh, key_type: dt.DataType, n_vals: int,
                         local_groups: int, bucket_cap: int):
    """Two-phase distributed GROUP BY SUM/COUNT as one SPMD program:

      local partial agg → hash all_to_all of partial rows → final agg

    Inputs (sharded [P, n]): key, vals..., sel.
    Outputs (sharded [P, local_groups]): key, sums..., count, group_sel,
    plus a per-shard overflow count [P] covering BOTH loss modes: partial
    groups dropped because a target bucket exceeded ``bucket_cap``, and
    group-table truncation when a shard saw more than ``local_groups``
    distinct keys (locally or after the exchange). Callers MUST host-check
    ``overflow.max() == 0`` and re-run with larger capacities otherwise
    (same detect-and-rerun contract as ``make_shuffle``).
    """
    nparts = mesh.shape[DATA_AXIS]
    spec = P(DATA_AXIS)

    def step(key, vals, sel):
        k, v, s = key[0], [x[0] for x in vals], sel[0]
        gkey, sums, cnt, gsel, l_over = _local_partial_agg(
            k, key_type, v, s, local_groups)
        # shuffle partial groups by key hash so equal keys co-locate
        pid = (hash64([gkey], [key_type]) % jnp.uint64(nparts)).astype(jnp.int32)
        arrays = [gkey] + sums + [cnt]
        perm, valid, overflow = bucket_by_partition(pid, gsel, nparts,
                                                    bucket_cap)
        bufs = [a[perm].reshape(nparts, bucket_cap) for a in arrays]
        valid2 = valid.reshape(nparts, bucket_cap)
        exch = [jax.lax.all_to_all(b, DATA_AXIS, 0, 0, tiled=True) for b in bufs]
        vex = jax.lax.all_to_all(valid2, DATA_AXIS, 0, 0, tiled=True)
        rkey = exch[0].reshape(-1)
        rsums = [e.reshape(-1) for e in exch[1: 1 + n_vals]]
        rcnt = exch[1 + n_vals].reshape(-1)
        rsel = vex.reshape(-1)
        # final aggregation of partials
        calls = [aggk.AggCall("sum", Column(x, None, dt.DoubleType()),
                              dt.DoubleType()) for x in rsums]
        calls.append(aggk.AggCall("sum", Column(rcnt, None, dt.LongType()),
                                  dt.LongType()))
        g = aggk.group_aggregate([Column(rkey, None, key_type)], rsel,
                                 local_groups, calls)
        fkey, fsel = g.keys[0].data, g.sel
        fsums = [c.data for c in g.aggs[:-1]]
        fcnt = g.aggs[-1].data
        total_overflow = (overflow.astype(jnp.int32)
                          + l_over.astype(jnp.int32)
                          + g.overflow.astype(jnp.int32))
        return (fkey[None], tuple(f[None] for f in fsums), fcnt[None],
                fsel[None], total_overflow[None])

    wrapped = jax.shard_map(step, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=(spec, spec, spec, spec, spec))
    return jax.jit(wrapped)


def make_broadcast_join(mesh: Mesh, probe_key_type: dt.DataType,
                        n_payload: int):
    """Broadcast hash join as one SPMD program: the (small) build side is
    all_gathered to every shard; each shard sort-probes locally.

    Inputs: probe key + payload cols [P, n] sharded, probe sel;
            build key + payload [P, m] sharded, build sel.
    Output: probe cols ++ gathered build payload (validity = match), and an
            output sel — all sharded, inner-join semantics, unique build.
    """
    spec = P(DATA_AXIS)

    def step(pkey, ppayload, psel, bkey, bpayload, bsel):
        pk, ps = pkey[0], psel[0]
        bk = jax.lax.all_gather(bkey[0], DATA_AXIS, tiled=True)
        bs = jax.lax.all_gather(bsel[0], DATA_AXIS, tiled=True)
        bp = [jax.lax.all_gather(x[0], DATA_AXIS, tiled=True) for x in bpayload]
        bt = joink.build_side([Column(bk, None, probe_key_type)], bs)
        ranges = joink.probe_ranges(bt, [Column(pk, None, probe_key_type)], ps)
        matched = ranges.cnt > 0
        cap = bk.shape[0]
        bidx = bt.perm[jnp.clip(ranges.lo, 0, cap - 1)]
        out_payload = tuple(x[bidx][None] for x in bp)
        out_sel = (ps & matched)[None]
        return (pk[None], tuple(x[0][None] for x in ppayload), out_payload,
                out_sel)

    wrapped = jax.shard_map(step, mesh=mesh,
                            in_specs=(spec, spec, spec, spec, spec, spec),
                            out_specs=(spec, spec, spec, spec))
    return jax.jit(wrapped)
