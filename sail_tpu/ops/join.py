"""Equi-join kernels (sorted build + merge probe).

TPU-first replacement for DataFusion's HashJoinExec (SURVEY.md §2.4): the
build side is sorted by key in one ``lax.sort`` that carries the dead
flag, the key and the row number, so the sorted keys and the permutation
come out of the sort itself and nothing is gathered through an index
(``build_side``); the probe keys are merged into it by one more sort,
and each probe row's match range falls out of two running scans over the
merged order (``_merge_ranges``) — no serialized scatter-probe hash
table, and no binary search per probe row, whose ``log2(build)``
dependent full-width gathers cost four times the sorts on a v5e. Dynamic
output size is handled in two phases:

  1. ``join_match``: static-shape match ranges per probe row, plus the total
     output row count as a device scalar — the *only* host sync point.
  2. ``join_expand``: given a static output capacity chosen by the host
     (bucketed, so shapes cache), materialize the joined batch.

A unique-build fast path (``join_unique``) skips the sync entirely: with at
most one build match per probe row, output capacity equals probe capacity.
Null join keys never match (SQL equi-join semantics).

Multi-column keys pack losslessly into uint64 when they fit; otherwise a
64-bit hash is used for the sort order and candidate ranges are verified
against the true key columns.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import Column, DeviceBatch
from ..spec import data_type as dt
from .hash import can_pack, hash64, pack_keys


def _join_keys(cols: Sequence[Column], sel, seed: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray, bool]:
    """(key_bits, usable_mask, exact). usable = alive and no null key part.
    Dead/null rows keep their key but are excluded via the mask."""
    types = [c.dtype for c in cols]
    usable = sel
    datas = []
    for c in cols:
        if c.validity is not None:
            usable = usable & c.validity
        datas.append(c.data)
    if can_pack(types, reserve_bits=0):
        return pack_keys(datas, types), usable, True
    return hash64(datas, types, seed=seed), usable, False


def _values_eq(a, b):
    """Key-value equality with Spark semantics (NaN == NaN; -0.0 == 0.0)."""
    eq = a == b
    if jnp.issubdtype(a.dtype, jnp.floating):
        eq = eq | (jnp.isnan(a) & jnp.isnan(b))
    return eq


def _verify_eq(build_cols, probe_cols, bidx, valid):
    """Exact key equality check for the hashed path."""
    ok = valid
    for bc, pc in zip(build_cols, probe_cols):
        ok = ok & _values_eq(bc.data[bidx], pc.data)
    return ok


class BuildTable(NamedTuple):
    """Sorted build side, shareable across probes (broadcast join reuse)."""

    perm: jnp.ndarray         # int32[bn]: usable rows first, in key order
    sorted_keys: jnp.ndarray  # uint64[bn]; positions >= num_valid hold KEY_MAX
    exact: bool
    num_valid: jnp.ndarray    # dynamic count of usable build rows
    seed: int = 0             # hash seed (hashed path; bumped on ambiguity)


_KEY_MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)


def build_side(build_key_cols: Sequence[Column], build_sel, seed: int = 0) -> BuildTable:
    keys, usable, exact = _join_keys(build_key_cols, build_sel, seed=seed)
    # One sort on (dead, key), stable, that carries the row number: usable
    # rows come first in key order, equal keys in row order, dead rows
    # behind them, and the sorted keys and the permutation arrive in that
    # order with no gather through an index (on a v5e a gather of random
    # rows costs five times what a sorted row does). The suffix is then
    # overwritten with KEY_MAX so the array stays globally sorted. A *real*
    # key equal to KEY_MAX lives in the prefix; probe ranges clip against
    # num_valid, so the sentinel suffix can never produce a match.
    dead = (~usable).astype(jnp.uint8)
    row = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, skeys, perm = jax.lax.sort((dead, keys, row), num_keys=2, is_stable=True)
    num_valid = jnp.sum(usable.astype(jnp.int32))
    sorted_keys = jnp.where(row < num_valid, skeys, _KEY_MAX)
    return BuildTable(perm, sorted_keys, exact, num_valid, seed)


def hash_ambiguous(bt: BuildTable, build_key_cols: Sequence[Column]) -> jnp.ndarray:
    """Device scalar: two adjacent usable build rows share a 64-bit hash but
    differ in true key — probing by hash ranges would be wrong. The executor
    re-builds with seed+1 until unambiguous (astronomically rare to recur).
    Only meaningful when ``bt.exact`` is False."""
    n = bt.sorted_keys.shape[0]
    pos = jnp.arange(n - 1, dtype=jnp.int32)
    both_valid = (pos + 1) < bt.num_valid
    same_hash = (bt.sorted_keys[1:] == bt.sorted_keys[:-1]) & both_valid
    diff_key = jnp.zeros(n - 1, dtype=jnp.bool_)
    a, b = bt.perm[:-1], bt.perm[1:]
    for c in build_key_cols:
        neq = ~_values_eq(c.data[a], c.data[b])
        if c.validity is not None:
            neq = neq | (c.validity[a] != c.validity[b])
        diff_key = diff_key | neq
    return jnp.any(same_hash & diff_key)


class MatchRanges(NamedTuple):
    lo: jnp.ndarray      # int32[pn] first matching sorted-build position
    cnt: jnp.ndarray     # int32[pn] number of matches (0 if none)
    usable: jnp.ndarray  # bool[pn] probe row alive with non-null key


def _merge_ranges(sorted_keys, num_valid, pkeys) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(lo, cnt) per probe key from one ordered pass over build and probe
    keys together: what ``searchsorted`` left and right (the latter
    clipped at ``num_valid``) would give, without a loop of dependent
    gathers per probe row.

    The build keys (already sorted, first) and the probe keys are sorted
    as one array, stably, so the build rows of a key precede its probe
    rows. In that order the running count of live build rows is, at a
    probe row, the number of build keys <= its key; the same count at
    the first row of the key's run is the number of build keys < it. A
    second sort on the carried row number brings both back to probe
    order."""
    bn, pn = sorted_keys.shape[0], pkeys.shape[0]
    n = bn + pn
    keys = jnp.concatenate([sorted_keys, pkeys])
    row = jnp.arange(n, dtype=jnp.int32)
    keys, row = jax.lax.sort((keys, row), num_keys=1, is_stable=True)
    # dead build rows (the KEY_MAX suffix) count for nothing, so a real
    # key equal to KEY_MAX matches only the live rows that hold it
    live = (row < num_valid).astype(jnp.int32)
    at_or_before = jnp.cumsum(live)
    run_start = jnp.concatenate(
        [jnp.ones(1, dtype=jnp.bool_), keys[1:] != keys[:-1]])
    before = jax.lax.cummax(jnp.where(run_start, at_or_before - live, 0))
    _, lo, cnt = jax.lax.sort((row, before, at_or_before - before),
                              num_keys=1, is_stable=False)
    return lo[bn:], cnt[bn:]


def probe_ranges(bt: BuildTable, probe_key_cols: Sequence[Column], probe_sel,
                 build_key_cols: Optional[Sequence[Column]] = None) -> MatchRanges:
    pkeys, pusable, _ = _join_keys(probe_key_cols, probe_sel, seed=bt.seed)
    lo, cnt = _merge_ranges(bt.sorted_keys, bt.num_valid, pkeys)
    cnt = jnp.where(pusable, cnt, 0)
    if not bt.exact:
        # Hashed path: given an ambiguity-free build (see hash_ambiguous),
        # each hash range holds exactly one distinct true key, so verifying
        # the first candidate decides the whole range exactly.
        assert build_key_cols is not None
        cap = bt.sorted_keys.shape[0]
        cand = bt.perm[jnp.clip(lo, 0, cap - 1)]
        ok = _verify_eq(build_key_cols, probe_key_cols, cand, cnt > 0)
        cnt = jnp.where(ok, cnt, 0)
    return MatchRanges(lo, cnt, pusable)


def join_unique(bt: BuildTable, ranges: MatchRanges, probe: DeviceBatch,
                build_payload: DeviceBatch, join_type: str,
                build_names: Sequence[str]) -> DeviceBatch:
    """Join assuming ≤1 build match per probe row (PK-FK). Output capacity =
    probe capacity. join_type ∈ {inner, left, semi, anti}."""
    cap = bt.sorted_keys.shape[0]
    matched = ranges.cnt > 0
    bidx = bt.perm[jnp.clip(ranges.lo, 0, cap - 1)]
    if join_type == "semi":
        return probe.with_sel(probe.sel & matched)
    if join_type == "anti":
        return probe.with_sel(probe.sel & ~matched)
    cols = dict(probe.columns)
    for name in build_names:
        c = build_payload.columns[name]
        data = c.data[bidx]
        validity = matched if c.validity is None else matched & c.validity[bidx]
        cols[name] = Column(data, validity, c.dtype)
    if join_type == "inner":
        sel = probe.sel & matched
    elif join_type == "left":
        sel = probe.sel
    else:
        raise ValueError(join_type)
    return DeviceBatch(cols, sel)


def join_output_count(ranges: MatchRanges, probe_sel, join_type: str) -> jnp.ndarray:
    """Total output rows for the expanding join (device scalar)."""
    cnt = ranges.cnt
    if join_type in ("left", "full"):
        cnt = jnp.where(probe_sel, jnp.maximum(cnt, 1), 0)
    else:
        cnt = jnp.where(probe_sel, cnt, 0)
    return jnp.sum(cnt.astype(jnp.int64))


class ExpandResult(NamedTuple):
    batch: DeviceBatch
    probe_index: jnp.ndarray  # int32[out_capacity] originating probe row
    is_match: jnp.ndarray     # bool[out_capacity] row is a key match
    build_index: jnp.ndarray  # int32[out_capacity] originating build row


def join_expand(bt: BuildTable, ranges: MatchRanges, probe: DeviceBatch,
                build_payload: DeviceBatch, join_type: str,
                build_names: Sequence[str], out_capacity: int) -> ExpandResult:
    """Materialize a many-to-many join into a batch of static capacity.

    join_type ∈ {inner, left}. (right/full are planned as swapped/left+anti
    unions by the physical layer.)
    """
    bn = bt.sorted_keys.shape[0]
    cnt = ranges.cnt
    if join_type == "left":
        eff = jnp.where(probe.sel, jnp.maximum(cnt, 1), 0)
    else:
        eff = jnp.where(probe.sel, cnt, 0)
    offsets = jnp.cumsum(eff) - eff  # exclusive prefix sum
    total = jnp.sum(eff)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    # probe row for output j: last i with offsets[i] <= j (among eff>0 rows)
    pi = jnp.searchsorted(offsets + eff, j, side="right").astype(jnp.int32)
    pi = jnp.clip(pi, 0, probe.capacity - 1)
    k = j - offsets[pi]
    is_match = k < cnt[pi]
    bpos = jnp.clip(ranges.lo[pi] + jnp.where(is_match, k, 0), 0, bn - 1)
    bidx = bt.perm[bpos]
    out_sel = j < total
    cols = {}
    for name, c in probe.columns.items():
        data = c.data[pi]
        validity = None if c.validity is None else c.validity[pi]
        cols[name] = Column(data, validity, c.dtype)
    for name in build_names:
        c = build_payload.columns[name]
        data = c.data[bidx]
        validity = is_match if c.validity is None else is_match & c.validity[bidx]
        cols[name] = Column(data, validity, c.dtype)
    return ExpandResult(DeviceBatch(cols, out_sel), pi, is_match, bidx)


def build_matched_mask(bt: BuildTable, ranges: MatchRanges, probe_sel) -> jnp.ndarray:
    """bool[build_capacity]: build rows matched by ≥1 probe row (for right/
    full outer). Computed as a range-increment difference array over sorted
    build positions, then mapped back through the sort permutation."""
    bn = bt.sorted_keys.shape[0]
    active = (ranges.cnt > 0) & probe_sel
    lo = jnp.where(active, ranges.lo, 0)
    hi = jnp.where(active, ranges.lo + ranges.cnt, 0)
    diff = jnp.zeros(bn + 1, dtype=jnp.int32)
    diff = diff.at[lo].add(active.astype(jnp.int32))
    diff = diff.at[hi].add(-active.astype(jnp.int32))
    covered_sorted = jnp.cumsum(diff[:bn]) > 0
    matched = jnp.zeros(bn, dtype=jnp.bool_).at[bt.perm].set(covered_sorted)
    return matched


def has_duplicate_build_keys(bt: BuildTable) -> jnp.ndarray:
    """Device scalar: any two usable build rows share a key (→ the unique
    fast path is invalid and the planner must expand)."""
    k = bt.sorted_keys
    pos = jnp.arange(k.shape[0] - 1, dtype=jnp.int32)
    dup = (k[1:] == k[:-1]) & ((pos + 1) < bt.num_valid)
    return jnp.any(dup)
