"""Sort / permutation kernels.

Sorting is the workhorse primitive of this engine: ORDER BY reduces to
argsort + gather, group-by (``ops/aggregate.py``) to one ``lax.sort`` that
carries keys and values, and joins (``ops/join.py``) order their build
side with one — unlike scatter-heavy hash tables, which serialize on TPU. Total order over null/dead rows is
obtained by mapping every key column to order-preserving uint64 bits
(IEEE-754 trick for floats, sign-bias for ints) with null and selection
flags folded in, so one stable argsort per key column suffices.

Reference role: SortExec / sort-merge machinery in DataFusion (SURVEY.md
§2.4-2.5), re-designed for XLA static shapes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar.batch import Column, DeviceBatch
from ..spec import data_type as dt


def _order_bits(data, d: dt.DataType) -> jnp.ndarray:
    """Map values to uint64 whose unsigned order equals the value order."""
    pd = d.physical_dtype
    if pd == "bool":
        return data.astype(jnp.uint64)
    if pd in ("int8", "int16", "int32", "int64"):
        x = data.astype(jnp.int64)
        return (x.astype(jnp.uint64)) ^ jnp.uint64(1 << 63)
    if pd == "float32":
        from .hash import _normalize_float
        b = jax.lax.bitcast_convert_type(_normalize_float(data.astype(jnp.float32)),
                                         jnp.uint32).astype(jnp.uint64)
        neg = (b >> jnp.uint64(31)) != 0
        return jnp.where(neg, ~b & jnp.uint64(0xFFFFFFFF), b | jnp.uint64(0x80000000))
    if pd == "float64":
        from .hash import _normalize_float
        b = jax.lax.bitcast_convert_type(_normalize_float(data.astype(jnp.float64)), jnp.uint64)
        neg = (b >> jnp.uint64(63)) != 0
        return jnp.where(neg, ~b, b | jnp.uint64(1 << 63))
    raise TypeError(pd)


def order_bits(data, d: dt.DataType, ascending: bool = True) -> jnp.ndarray:
    """Full-width uint64 order key (exact: distinct values stay distinct).
    Null placement is handled by a separate stable pass in lexsort_perm."""
    bits = _order_bits(data, d)
    return bits if ascending else ~bits


def sort_pass(perm, data, d: dt.DataType, ascending: bool = True):
    """One stable pass of a lexicographic sort: ``perm`` reordered by
    ``data``. Doubles sort by VALUE, not by order bits: the TPU compiler
    has no float64→uint64 bitcast (UNIMPLEMENTED in its 64-bit
    rewriting), and jax's sort comparator is already the total order
    wanted — -0.0 equals 0.0, NaNs together at the end. Spark's NaN is
    the LARGEST value, so a descending pass moves the NaNs to the
    front with a second stable pass."""
    if d.physical_dtype != "float64":
        bits = order_bits(data, d, ascending)
        return perm[jnp.argsort(bits[perm], stable=True)]
    vals = data[perm]
    if ascending:
        return perm[jnp.argsort(vals, stable=True)]
    perm = perm[jnp.argsort(-vals, stable=True)]
    not_nan = (~jnp.isnan(data[perm])).astype(jnp.uint8)
    return perm[jnp.argsort(not_nan, stable=True)]


def lexsort_perm(keys, sel=None) -> jnp.ndarray:
    """Stable lexicographic sort permutation.

    ``keys``: sequence of (data, validity, dtype, ascending, nulls_first),
    most significant first. Spark null ordering (default nulls first when
    ascending, last when descending). Dead rows (sel == False) always sort
    last. Returns int32 permutation of row indices.
    """
    n = keys[0][0].shape[0] if keys else sel.shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for data, validity, d, asc, nf in reversed(list(keys)):
        perm = sort_pass(perm, data, d, asc)
        if validity is not None:
            nulls_first = asc if nf is None else nf
            null_rank = (validity if nulls_first else ~validity).astype(jnp.uint8)
            perm = perm[jnp.argsort(null_rank[perm], stable=True)]
    if sel is not None:
        dead = (~sel).astype(jnp.uint8)
        perm = perm[jnp.argsort(dead[perm], stable=True)]
    return perm


def take_column(col: Column, perm) -> Column:
    data = col.data[perm]
    validity = None if col.validity is None else col.validity[perm]
    return Column(data, validity, col.dtype)


def take_batch(batch: DeviceBatch, perm) -> DeviceBatch:
    cols = {n: take_column(c, perm) for n, c in batch.columns.items()}
    return DeviceBatch(cols, batch.sel[perm])


def compact_perm(sel) -> jnp.ndarray:
    """Permutation moving live rows to the front, preserving order."""
    dead = (~sel).astype(jnp.uint8)
    return jnp.argsort(dead, stable=True).astype(jnp.int32)


def compact(batch: DeviceBatch) -> DeviceBatch:
    return take_batch(batch, compact_perm(batch.sel))


def limit(batch: DeviceBatch, n: int, offset: int = 0) -> DeviceBatch:
    """LIMIT/OFFSET over live rows (compacts first)."""
    out = compact(batch)
    idx = jnp.arange(out.capacity, dtype=jnp.int32)
    count = out.num_rows()
    new_sel = (idx >= offset) & (idx < jnp.minimum(count, offset + n))
    return out.with_sel(new_sel)
