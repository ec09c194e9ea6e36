"""Grouped aggregation kernels.

One entry point, ``group_aggregate``, and three routes, which the executor
names on an aggregate's span (``path``):

- ``sorted``: rows are put in key order by ONE stable ``lax.sort`` that
  carries the key columns and every aggregate's input, and are reduced in
  that order: integer sums and counts as running sums differenced at the
  group boundaries, everything else as segmented scans that restart at
  each group's first row, so each group's result sits at its last row. A
  second sort, on the group number of those last rows, moves them to the
  front. Nothing is gathered or scattered through a permutation: on a v5e
  a gathered row costs several times a sorted one, and XLA lowers a
  scatter with unsorted indices to a serial loop over the rows.
- ``direct`` (``group_rows_direct``): keys with small known domains
  (dictionary codes, booleans) bin rows by their packed codes, no sort.
- ``global``: no key, one group.

The two binned routes reduce with ``jax.ops.segment_*`` (scatters), or on
the TPU, for at most ``_MASKED_SEGMENTS_MAX`` bins, with a masked
broadcast reduction. The number of output group slots is a static
capacity; the live group count is dynamic and exported via the output
selection mask.

NULL semantics follow Spark: null group keys form their own group; null
values are skipped by aggregates; COUNT(*) counts rows, COUNT(x) counts
non-null x; SUM over an all-null group is NULL; MIN/MAX ignore nulls.
Float keys group -0.0 with 0.0 and every NaN together.

Planner-level rewrites decompose compound aggregates before reaching this
kernel: AVG → SUM/COUNT, VAR/STD → SUM/SUM2/COUNT, COUNT(DISTINCT) →
two-level group-by.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import Column
from ..spec import data_type as dt
from .hash import _normalize_float


#: the grouping routes, as the executor names them on an aggregate's
#: span: no key (one group), direct binning of small known domains
#: (``group_rows_direct``), and sort + reduction in key order
GLOBAL, DIRECT, SORTED = "global", "direct", "sorted"

#: the aggregate functions the kernels compute
AGG_FNS = ("count", "sum", "min", "max", "first", "last", "bool_and",
           "bool_or")


class AggCall(NamedTuple):
    """One aggregate of a GROUP BY. ``value`` None is COUNT(*)."""
    fn: str                                  # one of AGG_FNS
    value: Optional[Column] = None
    out_type: Optional[dt.DataType] = None   # sum's result type
    ignore_nulls: bool = True                # first / last


class Groups(NamedTuple):
    keys: List[Column]       # one value per group slot
    aggs: List[Column]       # one per AggCall
    sel: jnp.ndarray         # bool[slots]: the live groups
    num_groups: jnp.ndarray  # distinct groups of the input (dynamic)
    overflow: jnp.ndarray    # more groups than slots: the output is cut
    sort_operands: int       # arrays the sorted route's first sort moves


def group_aggregate(key_cols: Sequence[Column], sel, max_groups: int,
                    aggs: Sequence[AggCall],
                    domains: Optional[Sequence[int]] = None) -> Groups:
    """GROUP BY ``key_cols`` over the rows ``sel`` keeps.

    ``domains`` (one per key) takes direct binning, with one slot a bin;
    otherwise keys are sorted into ``max_groups`` slots, the live groups
    first. ``overflow``: the input had more distinct groups than
    ``max_groups`` and only the first ``max_groups`` in key order are
    there; the executor must host-check it whenever it chose
    ``max_groups`` smaller than the input capacity, and re-run with a
    larger capacity."""
    if key_cols and domains is None:
        return _sorted_aggregate(key_cols, sel, max_groups, aggs)
    if key_cols:
        bins, keys = group_rows_direct(key_cols, domains, sel)
        num_groups = bins.count
    else:  # one group, in the first slot
        ids = jnp.where(sel, 0, max_groups).astype(jnp.int32)
        bins = Bins(ids, sel, max_groups,
                    jnp.arange(max_groups, dtype=jnp.int32) < 1)
        keys, num_groups = [], 1
    outs = [_BINNED[a.fn](bins, a) for a in aggs]
    # every group has its bin: nothing is cut
    return Groups(keys, outs, bins.live, jnp.int32(num_groups),
                  jnp.asarray(False), 0)


# ---------------------------------------------------------------------------
# sorted route
# ---------------------------------------------------------------------------

def _group_order(c: Column) -> jnp.ndarray:
    """A key column as the sort orders it: 0 where it is null, so that the
    keys after it order the null group; floats normalised (Spark groups
    -0.0 with 0.0 and every NaN together; the TPU compiler has no float64
    bitcast, so floats sort by value); booleans as uint8."""
    data = c.data
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = _normalize_float(data)
    elif data.dtype == jnp.bool_:
        data = data.astype(jnp.uint8)
    if c.validity is not None:
        data = jnp.where(c.validity, data, jnp.zeros((), data.dtype))
    return data


def _segmented_scan(op, start, *xs):
    """Inclusive scan of the associative ``op`` (tuple → tuple) over
    ``xs``, restarting wherever ``start`` is set."""
    def combine(p, q):
        merged = op(p[1:], q[1:])
        return (p[0] | q[0],) + tuple(jnp.where(q[0], b, m)
                                      for b, m in zip(q[1:], merged))
    return jax.lax.associative_scan(combine, (start,) + xs)[1:]


def _diff(run):
    """Group totals from a running total read at each group's last row,
    the groups in order."""
    return run - jnp.concatenate([jnp.zeros(1, run.dtype), run[:-1]])


def _fit(x, slots: int):
    """The first ``slots`` rows of ``x``, zero-padded where there are
    more slots than rows."""
    n = x.shape[0]
    return x[:slots] if n >= slots else jnp.pad(x, (0, slots - n))


def _reduce_in_order(a: AggCall, v, w, alive, start):
    """One aggregate over rows in key order: the arrays whose value at a
    group's last row makes its result, and how the result is made from
    them once they stand one to a slot (with the live groups' mask)."""
    skip_nulls = w is not None and (a.fn not in ("first", "last")
                                    or a.ignore_nulls)
    mask = alive & w if skip_nulls else alive
    if a.fn == "count":
        return [jnp.cumsum(mask.astype(jnp.int32))], \
            lambda r, live: Column(_diff(r[0]).astype(jnp.int64), None,
                                   dt.LongType())
    if a.fn in ("first", "last"):
        # stable sort: a group's rows keep input order, so the first or
        # last row that may be chosen is Spark's first / last
        xs = (mask, v) + (() if w is None else (w,))
        if a.fn == "first":
            pick = lambda p, q: tuple(jnp.where(p[0], x, y)  # noqa: E731
                                      for x, y in zip(p, q))
            xs = _segmented_scan(pick, start, *xs)
        elif skip_nulls:
            pick = lambda p, q: tuple(jnp.where(q[0], y, x)  # noqa: E731
                                      for x, y in zip(p, q))
            xs = _segmented_scan(pick, start, *xs)
        return list(xs), lambda r, live: Column(
            r[1], r[0] if w is None else r[0] & r[2], a.value.dtype)
    # the rest are NULL where a group has no value to reduce
    count = [jnp.cumsum(mask.astype(jnp.int32))] if skip_nulls else []

    def valid(r, live):
        return _diff(r[-1]) > 0 if skip_nulls else live

    if a.fn == "sum":
        odt = jnp.dtype(a.out_type.physical_dtype)
        x = jnp.where(mask, v.astype(odt), jnp.zeros((), odt))
        if not jnp.issubdtype(odt, jnp.floating):
            # exact modulo 2**64, as a per-group sum is
            return [jnp.cumsum(x)] + count, \
                lambda r, live: Column(_diff(r[0]), valid(r, live), a.out_type)
        # a float restarts at each group: a running sum over all rows
        # would cancel a small group against a large one before it
        (x,) = _segmented_scan(lambda p, q: (p[0] + q[0],), start, x)
        return [x] + count, \
            lambda r, live: Column(r[0], valid(r, live), a.out_type)
    if a.fn in ("min", "max"):
        is_min, out_type = a.fn == "min", a.value.dtype
    else:
        is_min, out_type = a.fn == "bool_and", dt.BooleanType()
    as_bool = v.dtype == jnp.bool_ or isinstance(out_type, dt.BooleanType)
    x = v.astype(jnp.int8) if as_bool else v
    x = jnp.where(mask, x, _extreme_for(x.dtype, is_min))
    op = jnp.minimum if is_min else jnp.maximum
    (x,) = _segmented_scan(lambda p, q: (op(p[0], q[0]),), start, x)
    return [x] + count, lambda r, live: Column(
        r[0].astype(jnp.bool_) if as_bool else r[0], valid(r, live), out_type)


def _sorted_aggregate(key_cols: Sequence[Column], sel, max_groups: int,
                      aggs: Sequence[AggCall]) -> Groups:
    n = sel.shape[0]
    # sort keys, most significant first: the dead flag (dead rows last),
    # then for each key its null flag, where it has one, and its value
    operands = [(~sel).astype(jnp.uint8)]
    key_at = []
    for c in key_cols:
        flag = None
        if c.validity is not None:
            flag = len(operands)
            operands.append((~c.validity).astype(jnp.uint8))
        key_at.append((flag, len(operands)))
        operands.append(_group_order(c))
    num_keys = len(operands)
    carried = {}

    def carry(x):
        # an array rides the sort once, however many aggregates read it
        if x is None:
            return None
        if id(x) not in carried:
            carried[id(x)] = len(operands)
            operands.append(x)
        return carried[id(x)]

    refs = [(None, None) if a.value is None else
            (carry(a.value.data), carry(a.value.validity)) for a in aggs]
    s = jax.lax.sort(tuple(operands), num_keys=num_keys, is_stable=True)

    alive = s[0] == 0
    same = jnp.ones(n - 1, dtype=jnp.bool_)
    for flag, k in key_at:
        x, prev = s[k][1:], s[k][:-1]
        eq = x == prev
        if jnp.issubdtype(x.dtype, jnp.floating):
            eq = eq | (jnp.isnan(x) & jnp.isnan(prev))
        if flag is not None:
            eq = eq & (s[flag][1:] == s[flag][:-1])
        same = same & eq
    start = alive & jnp.concatenate([jnp.ones(1, jnp.bool_), ~same])
    last = alive & jnp.concatenate([start[1:] | ~alive[1:],
                                    jnp.ones(1, jnp.bool_)])
    gid = jnp.cumsum(start.astype(jnp.int32)) - 1
    num_groups = jnp.sum(start.astype(jnp.int32))

    rides, finishes = [], []
    for flag, k in key_at:
        rides.append(s[k])
        if flag is not None:
            rides.append(s[flag])
    for a, (vi, wi) in zip(aggs, refs):
        r, finish = _reduce_in_order(a, None if vi is None else s[vi],
                                     None if wi is None else s[wi],
                                     alive, start)
        finishes.append((len(rides), len(r), finish))
        rides.extend(r)
    # each group's last row to slot gid; dead rows and groups past the
    # slots behind them
    slot = jnp.where(last & (gid < max_groups), gid, max_groups)
    out = jax.lax.sort((slot.astype(jnp.int32),) + tuple(rides),
                       num_keys=1, is_stable=False)[1:]
    out = [_fit(x, max_groups) for x in out]
    live = jnp.arange(max_groups, dtype=jnp.int32) < num_groups

    keys, i = [], 0
    for c, (flag, _) in zip(key_cols, key_at):
        validity = None
        if flag is not None:
            validity = out[i + 1] == 0
        keys.append(Column(out[i].astype(c.data.dtype), validity, c.dtype))
        i += 1 if flag is None else 2
    cols = [finish(out[j:j + m], live) for j, m, finish in finishes]
    return Groups(keys, cols, live, num_groups, num_groups > max_groups,
                  num_keys + len(carried))


# ---------------------------------------------------------------------------
# binned routes: direct and global
# ---------------------------------------------------------------------------

class Bins(NamedTuple):
    """Rows binned in place, shared by every aggregate of a binned route:
    rows stay where they are (large gathers are pathologically slow on
    TPU), and bins may be empty."""
    ids: jnp.ndarray    # int32[n]: each row's bin, dead rows → count
    alive: jnp.ndarray  # bool[n]
    count: int          # static: the bins
    live: jnp.ndarray   # bool[count]: the bins that hold a group


def group_rows_direct(key_cols: Sequence[Column], domains: Sequence[int],
                      sel) -> Tuple[Bins, List[Column]]:
    """Sort-free grouping for low-cardinality keys with known domains
    (dictionary codes, booleans): segment id = packed code. The dominant
    TPC-H aggregations (Q1's returnflag×linestatus, Q12's shipmode, …) hit
    this path, turning an O(n log n) sort into O(n) segment reductions.

    Each key gets domain_i + 1 slots (the extra one encodes NULL); a
    bin's key values are decoded from its number, so nothing is gathered.
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
    ids = jnp.where(sel, gid, g_total).astype(jnp.int32)
    counts = _seg_sum(sel.astype(jnp.int32), ids, g_total + 1)[:g_total]
    bin_no = jnp.arange(g_total, dtype=jnp.int32)
    keys, stride = [], g_total
    for c, dom in zip(key_cols, domains):
        stride //= dom + 1
        code = (bin_no // stride) % (dom + 1)
        keys.append(Column(code.astype(c.data.dtype),
                           None if c.validity is None else code < dom,
                           c.dtype))
    return Bins(ids, sel, g_total, counts > 0), keys


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


def _value_mask(bins: Bins, value: Optional[Column]):
    mask = bins.alive
    if value is not None and value.validity is not None:
        mask = mask & value.validity
    return mask


def _nonempty(bins: Bins, mask):
    return _seg_sum(mask.astype(jnp.int32), bins.ids,
                    bins.count + 1)[: bins.count] > 0


def _binned_count(bins: Bins, a: AggCall) -> Column:
    """COUNT(*) when value is None, else COUNT(value)."""
    ones = _value_mask(bins, a.value).astype(jnp.int64)
    out = _seg_sum(ones, bins.ids, bins.count + 1)
    return Column(out[: bins.count], None, dt.LongType())


def _binned_sum(bins: Bins, a: AggCall) -> Column:
    mask = _value_mask(bins, a.value)
    odt = jnp.dtype(a.out_type.physical_dtype)
    vals = _masked(a.value.data.astype(odt), mask, 0)
    out = _seg_sum(vals, bins.ids, bins.count + 1)
    return Column(out[: bins.count], _nonempty(bins, mask), a.out_type)


def _extreme_for(dtype_np, is_min: bool):
    if jnp.issubdtype(dtype_np, jnp.floating):
        return jnp.inf if is_min else -jnp.inf
    info = jnp.iinfo(dtype_np)
    return info.max if is_min else info.min


def _binned_min_max(bins: Bins, a: AggCall) -> Column:
    is_min = a.fn == "min"
    vals = a.value.data
    mask = _value_mask(bins, a.value)
    if vals.dtype == jnp.bool_:
        vals = vals.astype(jnp.int8)
    fill = _extreme_for(vals.dtype, is_min)
    vals = _masked(vals, mask, fill)
    out = _seg_reduce(vals, bins.ids, bins.count + 1,
                      "min" if is_min else "max", fill)[: bins.count]
    if a.value.data.dtype == jnp.bool_:
        out = out.astype(jnp.bool_)
    return Column(out, _nonempty(bins, mask), a.value.dtype)


def _binned_first_last(bins: Bins, a: AggCall) -> Column:
    is_first, value = a.fn == "first", a.value
    n = bins.ids.shape[0]
    mask = _value_mask(bins, value) if a.ignore_nulls else bins.alive
    idx = jnp.arange(n, dtype=jnp.int32)
    sentinel = n if is_first else -1
    idx_m = _masked(idx, mask, sentinel)
    pos = _seg_reduce(idx_m, bins.ids, bins.count + 1,
                      "min" if is_first else "max",
                      sentinel)[: bins.count]
    has = (pos < n) if is_first else (pos >= 0)
    pos = jnp.clip(pos, 0, n - 1)
    validity = has
    if value.validity is not None:
        validity = validity & value.validity[pos]
    return Column(value.data[pos], validity, value.dtype)


def _binned_bool(bins: Bins, a: AggCall) -> Column:
    is_any = a.fn == "bool_or"
    mask = _value_mask(bins, a.value)
    fill = 0 if is_any else 1
    vals = _masked(a.value.data.astype(jnp.int8), mask, fill)
    out = _seg_reduce(vals, bins.ids, bins.count + 1,
                      "max" if is_any else "min", fill)[: bins.count]
    return Column(out.astype(jnp.bool_), _nonempty(bins, mask),
                  dt.BooleanType())


_BINNED = {"count": _binned_count, "sum": _binned_sum,
           "min": _binned_min_max, "max": _binned_min_max,
           "first": _binned_first_last, "last": _binned_first_last,
           "bool_and": _binned_bool, "bool_or": _binned_bool}
