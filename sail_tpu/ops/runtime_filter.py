"""Runtime join-filter kernels: what a join's source side says about its
keys, for pruning the OTHER side's scans.

Sideways information passing for equi-joins: after one side of a join
materializes, the executor derives value conjuncts from its keys — per
column min/max bounds and, for small sources, the exact key list — and
pushes them into the other side's annotated scans (parquet row-group
skipping, host-side Arrow filtering, cluster task predicates). Fewer
rows are decoded and uploaded, and every later kernel runs at the
capacity the pruned scan leaves. This module computes what that push is
decided and built from: the source's usable-row and distinct-key counts
(``key_stats``), each key column's bounds (``column_bounds``) and its
values on the usable rows, compacted into a bucket small enough to
fetch (``key_bucket``).

There is no device-side membership mask: inside a join's static-shape
program a selection mask shortens no sort and removes only rows the join
would reject anyway (the spill path keeps its own exact host mask,
``exec/local.py _spill_probe_mask``).

Key derivation is shared with the join kernels (``ops/join._join_keys``):
multi-column keys pack losslessly into one uint64 when they fit,
otherwise the same seed-0 ``hash64`` the join uses; Spark key semantics
(-0.0 ≡ 0.0, NaN ≡ NaN) ride the shared ``_to_bits`` normalization, so
the distinct count is over JOIN-equal keys.

Reference role: DataFusion's dynamic filter pushdown / Spark's runtime
filter join rewrite, reduced to what a scan can use.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from .join import _KEY_MAX, _join_keys


class KeyStats(NamedTuple):
    n_build: jnp.ndarray  # int32 scalar: usable source rows
    ndv: jnp.ndarray     # int32 scalar: distinct keys among usable rows


def key_stats(key_cols: Sequence, sel) -> KeyStats:
    """Usable-row and distinct-key counts of a join's source side.

    Dead/null-key rows are excluded: an equi-join key with any NULL part
    never matches, so such rows say nothing about the other side.
    """
    keys, usable, _ = _join_keys(key_cols, sel)
    n = keys.shape[0]
    n_build = jnp.sum(usable.astype(jnp.int32))
    # distinct count over the usable prefix of the sorted keys
    skeys = jnp.sort(jnp.where(usable, keys, _KEY_MAX))
    pos = jnp.arange(n, dtype=jnp.int32)
    first = (pos == 0) | (skeys != jnp.concatenate(
        [skeys[:1], skeys[:-1]]))
    ndv = jnp.sum((first & (pos < n_build)).astype(jnp.int32))
    return KeyStats(n_build, ndv)


def column_bounds(data: jnp.ndarray, usable: jnp.ndarray):
    """(min, max) of one key column over usable rows, in the column's
    physical dtype. With zero usable rows min > max (callers detect the
    empty build via n_build and may prune the whole probe side)."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        lo, hi = jnp.array(-jnp.inf, data.dtype), jnp.array(jnp.inf,
                                                            data.dtype)
    elif data.dtype == jnp.bool_:
        lo, hi = jnp.array(False), jnp.array(True)
    else:
        info = jnp.iinfo(data.dtype)
        lo, hi = jnp.array(info.min, data.dtype), jnp.array(info.max,
                                                            data.dtype)
    cmin = jnp.min(jnp.where(usable, data, hi))
    cmax = jnp.max(jnp.where(usable, data, lo))
    return cmin, cmax


def key_bucket(key_cols: Sequence, usable: jnp.ndarray, size: int):
    """Each key column's values on the usable rows, in row order, at the
    front of a ``size``-row bucket (static), where there are at most
    ``size`` usable rows; zeros where there are more, since no list is
    made from part of the keys. What a key list is made from leaves the
    device in this bucket, whatever the source's capacity.

    The j-th usable row is the first whose running count of usable rows
    reaches j + 1: one cumulative sum and ``size`` binary searches, no
    scatter over the source's rows, and neither where the bucket cannot
    hold them (``lax.cond``)."""
    empty = tuple(jnp.zeros(size, c.data.dtype) for c in key_cols)
    if size == 0:
        return empty
    n = usable.shape[0]

    def compact():
        count = jnp.cumsum(usable.astype(jnp.int32))
        rows = jnp.searchsorted(count, jnp.arange(1, size + 1,
                                                  dtype=jnp.int32))
        rows = jnp.minimum(rows, n - 1)
        return tuple(c.data[rows] for c in key_cols)

    return jax.lax.cond(jnp.sum(usable.astype(jnp.int32)) <= size,
                        compact, lambda: empty)
