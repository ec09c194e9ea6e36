"""Grouped aggregation kernels (sort + segmented reduction).

TPU-first design for GROUP BY: instead of a scatter-probe hash table (the
DataFusion approach — SURVEY.md §2.4; serializes on TPU), rows are sorted
by their group key and reduced with ``jax.ops.segment_*`` primitives, which
XLA lowers to parallel scans. The number of output group slots is a static
capacity; the live group count is dynamic and exported via the output
selection mask.

NULL semantics follow Spark: null group keys form their own group; null
values are skipped by aggregates; COUNT(*) counts rows, COUNT(x) counts
non-null x; SUM over an all-null group is NULL; MIN/MAX ignore nulls.

Planner-level rewrites decompose compound aggregates before reaching this
kernel: AVG → SUM/COUNT, VAR/STD → SUM/SUM2/COUNT, COUNT(DISTINCT) →
two-level group-by.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import Column, DeviceBatch
from ..spec import data_type as dt
from .hash import can_pack, pack_keys
from .sort import sort_pass


#: the grouping routes, as the executor names them on an aggregate's
#: span: no key (one group), direct binning of small known domains
#: (``group_rows_direct``), and sort + segmented reduction (``group_rows``)
GLOBAL, DIRECT, SORTED = "global", "direct", "sorted"


def _group_sort_perm(key_cols: Sequence[Column], sel) -> jnp.ndarray:
    """Sort permutation grouping equal keys together, dead rows last."""
    n = sel.shape[0]
    types = [c.dtype for c in key_cols]
    if can_pack(types, reserve_bits=len(key_cols) + 1):
        # Fast path: one argsort over a packed key with null flags folded in.
        datas = []
        for c in key_cols:
            datas.append(jnp.where(c.validity, c.data, jnp.zeros_like(c.data))
                         if c.validity is not None else c.data)
        packed = pack_keys(datas, types)
        shift = 64 - (len(key_cols) + 1)
        packed = packed & jnp.uint64((1 << shift) - 1)
        for i, c in enumerate(key_cols):
            if c.validity is not None:
                packed = packed | (jnp.where(c.validity, jnp.uint64(0), jnp.uint64(1))
                                   << jnp.uint64(shift + i))
        packed = jnp.where(sel, packed, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        return jnp.argsort(packed, stable=True).astype(jnp.int32)
    perm = jnp.arange(n, dtype=jnp.int32)
    for c in reversed(list(key_cols)):
        perm = sort_pass(perm, c.data, c.dtype)
        if c.validity is not None:
            perm = perm[jnp.argsort(c.validity[perm].astype(jnp.uint8), stable=True)]
    dead = (~sel).astype(jnp.uint8)
    return perm[jnp.argsort(dead[perm], stable=True)].astype(jnp.int32)


def _keys_equal_adjacent(sorted_keys: Sequence[Column]) -> jnp.ndarray:
    """eq[i] = row i has the same group key as row i-1 (eq[0] = False)."""
    n = sorted_keys[0].data.shape[0]
    eq = jnp.ones(n, dtype=jnp.bool_)
    for c in sorted_keys:
        prev = jnp.roll(c.data, 1)
        same_val = c.data == prev
        if jnp.issubdtype(c.data.dtype, jnp.floating):
            # Spark groups all NaNs together (and -0.0 with 0.0; == covers it)
            same_val = same_val | (jnp.isnan(c.data) & jnp.isnan(prev))
        if c.validity is not None:
            prev_v = jnp.roll(c.validity, 1)
            same = (same_val & c.validity & prev_v) | (~c.validity & ~prev_v)
        else:
            same = same_val
        eq = eq & same
    return eq.at[0].set(False)


class GroupContext:
    """Per-row segment ids + masks, shared by all aggregate columns.

    Two construction modes:
    - sort-based (group_rows): rows sorted by key, dense segment ids,
      groups front-compacted;
    - direct-binned (group_rows_direct): segment id = packed dictionary
      code, no sort — bins may be sparse, ``group_mask`` marks live ones,
      and ``perm`` is None (identity): large gathers are pathologically
      slow on TPU, so the direct path must touch values in place.
    """

    def __init__(self, perm, seg_ids, alive_sorted, num_groups, max_groups,
                 group_mask=None):
        self.perm = perm  # int32[n] sort permutation, or None = identity
        self.seg_ids = seg_ids            # int32[n], dead rows → max_groups
        self.alive_sorted = alive_sorted  # bool[n]
        self.num_groups = num_groups      # dynamic scalar
        self.max_groups = max_groups      # static
        self.group_mask = group_mask      # bool[max_groups] (direct mode)


def group_rows(key_cols: Sequence[Column], sel, max_groups: int) -> Tuple[GroupContext, List[Column]]:
    """Group rows by key; returns (context, key columns in ORIGINAL order).

    The sort is used only to derive dense segment ids (adjacent-equal
    detection needs key order); the ids are then scattered back to the
    original row order so every aggregate reduces values IN PLACE. This
    trades the former per-column permutation gathers — pathologically slow
    on TPU — for one int32 scatter, and keeps within-group row order equal
    to input order (first/last semantics)."""
    if not key_cols:
        n = sel.shape[0]
        seg = jnp.where(sel, 0, max_groups).astype(jnp.int32)
        return GroupContext(None, seg, sel, jnp.int32(1), max_groups), []
    perm = _group_sort_perm(key_cols, sel)
    sorted_keys = [Column(c.data[perm],
                          None if c.validity is None else c.validity[perm],
                          c.dtype) for c in key_cols]
    alive = sel[perm]
    eq = _keys_equal_adjacent(sorted_keys)
    new_group = alive & ~eq
    seg_sorted = jnp.cumsum(new_group.astype(jnp.int32)) - 1
    seg_sorted = jnp.where(alive, jnp.clip(seg_sorted, 0, max_groups),
                           max_groups).astype(jnp.int32)
    n = sel.shape[0]
    seg = jnp.zeros(n, dtype=jnp.int32).at[perm].set(seg_sorted)
    num_groups = jnp.sum(new_group.astype(jnp.int32))
    return GroupContext(None, seg, sel, num_groups, max_groups), \
        list(key_cols)


def group_key_output(ctx: GroupContext, sorted_keys: Sequence[Column]) -> List[Column]:
    """Representative key values per group (first row of each segment)."""
    n = ctx.seg_ids.shape[0]
    first_idx = _seg_reduce(jnp.arange(n, dtype=jnp.int32), ctx.seg_ids,
                            ctx.max_groups + 1, "min", n)[: ctx.max_groups]
    first_idx = jnp.clip(first_idx, 0, n - 1)
    out = []
    for c in sorted_keys:
        data = c.data[first_idx]
        validity = None if c.validity is None else c.validity[first_idx]
        out.append(Column(data, validity, c.dtype))
    return out


def group_rows_direct(key_cols: Sequence[Column], domains: Sequence[int],
                      sel) -> Tuple[GroupContext, List[Column]]:
    """Sort-free grouping for low-cardinality keys with known domains
    (dictionary codes, booleans): segment id = packed code. The dominant
    TPC-H aggregations (Q1's returnflag×linestatus, Q12's shipmode, …) hit
    this path, turning an O(n log n) sort into O(n) segment reductions.

    Each key gets domain_i + 1 slots (the extra one encodes NULL).
    """
    n = sel.shape[0]
    gid = jnp.zeros(n, dtype=jnp.int32)
    g_total = 1
    for c, dom in zip(key_cols, domains):
        slots = dom + 1
        code = jnp.clip(c.data.astype(jnp.int32), 0, dom - 1)
        if c.validity is not None:
            code = jnp.where(c.validity, code, dom)
        gid = gid * slots + code
        g_total *= slots
    seg = jnp.where(sel, gid, g_total).astype(jnp.int32)
    counts = _seg_sum(sel.astype(jnp.int32), seg, g_total + 1)[:g_total]
    mask = counts > 0
    ctx = GroupContext(None, seg, sel, jnp.int32(g_total), g_total, mask)
    return ctx, list(key_cols)


def group_sel(ctx: GroupContext) -> jnp.ndarray:
    if ctx.group_mask is not None:
        return ctx.group_mask
    return jnp.arange(ctx.max_groups, dtype=jnp.int32) < ctx.num_groups


def group_overflow(ctx: GroupContext) -> jnp.ndarray:
    """Device scalar: the input had more distinct groups than max_groups and
    the output is truncated. The executor must host-check this whenever it
    chose max_groups smaller than the input capacity, and re-run with a
    larger capacity."""
    return ctx.num_groups > ctx.max_groups



# TPU scatter pitfall: XLA lowers scatter-based segment reductions with
# unpredictable indices to a serialized per-row loop (~600 ms per 8M-row
# scatter-add measured on v5e). For bounded segment counts a masked
# broadcast-reduction runs as G vectorized passes that XLA fuses (the
# [G, n] compare/select fuses into the row reduction — nothing
# materializes), ~100x faster. Above the threshold the compute cost of
# G*n element ops exceeds the scatter cost and we fall back. On CPU the
# scatter lowering is already fast, and the masked form is a slowdown —
# so the masked path is TPU(-like)-only.
_MASKED_SEGMENTS_MAX = 128
_MASKED_BACKENDS = ("tpu",)


def _masked_max_segments() -> int:
    return _MASKED_SEGMENTS_MAX \
        if jax.default_backend() in _MASKED_BACKENDS else 0


def _seg_reduce(vals, seg_ids, num_segments: int, kind: str, identity):
    if num_segments <= _masked_max_segments():
        gids = jnp.arange(num_segments, dtype=seg_ids.dtype)[:, None]
        hit = seg_ids[None, :] == gids
        body = jnp.where(hit, vals[None, :],
                         jnp.asarray(identity, dtype=vals.dtype))
        if kind == "sum":
            return jnp.sum(body, axis=1)
        if kind == "min":
            return jnp.min(body, axis=1)
        return jnp.max(body, axis=1)
    fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
          "max": jax.ops.segment_max}[kind]
    return fn(vals, seg_ids, num_segments=num_segments)


def _seg_sum(vals, seg_ids, num_segments: int):
    return _seg_reduce(vals, seg_ids, num_segments, "sum", 0)


def _masked(vals, mask, fill):
    return jnp.where(mask, vals, jnp.full_like(vals, fill))


def _perm(ctx: GroupContext, arr):
    """Row permutation, skipped entirely for the identity (direct) mode —
    an explicit arange gather would lower to a full random gather on TPU."""
    return arr if ctx.perm is None else arr[ctx.perm]


def agg_count(ctx: GroupContext, value: Optional[Column]) -> Column:
    """COUNT(*) when value is None, else COUNT(value)."""
    mask = ctx.alive_sorted
    if value is not None and value.validity is not None:
        mask = mask & _perm(ctx, value.validity)
    ones = mask.astype(jnp.int64)
    out = _seg_sum(ones, ctx.seg_ids, ctx.max_groups + 1)
    return Column(out[: ctx.max_groups], None, dt.LongType())


def agg_sum(ctx: GroupContext, value: Column, out_type: dt.DataType) -> Column:
    vals = _perm(ctx, value.data)
    mask = ctx.alive_sorted
    if value.validity is not None:
        mask = mask & _perm(ctx, value.validity)
    odt = jnp.dtype(out_type.physical_dtype)
    vals = _masked(vals.astype(odt), mask, 0)
    out = _seg_sum(vals, ctx.seg_ids, ctx.max_groups + 1)
    cnt = _seg_sum(mask.astype(jnp.int32), ctx.seg_ids, ctx.max_groups + 1)
    return Column(out[: ctx.max_groups], cnt[: ctx.max_groups] > 0, out_type)


def _extreme_for(dtype_np, is_min: bool):
    if jnp.issubdtype(dtype_np, jnp.floating):
        return jnp.inf if is_min else -jnp.inf
    info = jnp.iinfo(dtype_np)
    return info.max if is_min else info.min


def agg_min_max(ctx: GroupContext, value: Column, is_min: bool) -> Column:
    vals = _perm(ctx, value.data)
    mask = ctx.alive_sorted
    if value.validity is not None:
        mask = mask & _perm(ctx, value.validity)
    if vals.dtype == jnp.bool_:
        vals = vals.astype(jnp.int8)
    fill = _extreme_for(vals.dtype, is_min)
    vals = _masked(vals, mask, fill)
    out = _seg_reduce(vals, ctx.seg_ids, ctx.max_groups + 1,
                      "min" if is_min else "max", fill)[: ctx.max_groups]
    cnt = _seg_sum(mask.astype(jnp.int32), ctx.seg_ids,
                   ctx.max_groups + 1)[: ctx.max_groups]
    if value.data.dtype == jnp.bool_:
        out = out.astype(jnp.bool_)
    return Column(out, cnt > 0, value.dtype)


def agg_first_last(ctx: GroupContext, value: Column, is_first: bool,
                   ignore_nulls: bool = True) -> Column:
    n = ctx.seg_ids.shape[0]
    mask = ctx.alive_sorted
    if ignore_nulls and value.validity is not None:
        mask = mask & _perm(ctx, value.validity)
    idx = jnp.arange(n, dtype=jnp.int32)
    sentinel = n if is_first else -1
    idx_m = _masked(idx, mask, sentinel)
    pos = _seg_reduce(idx_m, ctx.seg_ids, ctx.max_groups + 1,
                      "min" if is_first else "max",
                      sentinel)[: ctx.max_groups]
    has = (pos < n) if is_first else (pos >= 0)
    pos = jnp.clip(pos, 0, n - 1)
    vals = _perm(ctx, value.data)[pos]
    validity = has
    if value.validity is not None:
        validity = validity & _perm(ctx, value.validity)[pos]
    return Column(vals, validity, value.dtype)


def agg_bool(ctx: GroupContext, value: Column, is_any: bool) -> Column:
    vals = _perm(ctx, value.data).astype(jnp.int8)
    mask = ctx.alive_sorted
    if value.validity is not None:
        mask = mask & _perm(ctx, value.validity)
    fill = 0 if is_any else 1
    vals = _masked(vals, mask, fill)
    out = _seg_reduce(vals, ctx.seg_ids, ctx.max_groups + 1,
                      "max" if is_any else "min", fill)[: ctx.max_groups]
    cnt = _seg_sum(mask.astype(jnp.int32), ctx.seg_ids,
                   ctx.max_groups + 1)[: ctx.max_groups]
    return Column(out.astype(jnp.bool_), cnt > 0, dt.BooleanType())
