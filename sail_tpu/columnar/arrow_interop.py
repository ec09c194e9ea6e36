"""Arrow ⇄ device-columnar conversion.

Host boundary of the engine: pyarrow Tables (from Parquet/CSV/JSON scans or
client LocalRelations) become padded DeviceBatches, and query results come
back as Arrow for the protocol layer. Mirrors the role of the reference's
use of arrow-rs as the in-memory format (SURVEY.md §2.1 sail-common /
§2.6 sail-data-source), re-shaped for HBM residency:

- fixed-width types upload as padded device arrays
- decimal128(p≤18) uploads as the *unscaled* int64 (exact arithmetic on
  device; the low 64 bits of the two's-complement decimal128 value equal
  the int64 value whenever it fits)
- strings/binary dictionary-encode; codes upload, dictionary stays host-side.
  A dictionary of at most ``INTERN_MAX_VALUES`` values is put in sorted
  order and interned by content (``DICTIONARIES``), so tables that hold the
  same value set share one dictionary object whatever order their rows
  came in
"""

from __future__ import annotations

import datetime
import decimal
import hashlib
import threading
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..spec import data_type as dt
from .batch import (Column, DeviceBatch, HostBatch, bucket_capacity,
                    make_batch)


def arrow_type_to_spec(t: pa.DataType) -> dt.DataType:
    if pa.types.is_boolean(t):
        return dt.BooleanType()
    if pa.types.is_int8(t):
        return dt.ByteType()
    if pa.types.is_int16(t):
        return dt.ShortType()
    if pa.types.is_int32(t):
        return dt.IntegerType()
    if pa.types.is_int64(t):
        return dt.LongType()
    if pa.types.is_uint8(t):
        return dt.ShortType()
    if pa.types.is_uint16(t):
        return dt.IntegerType()
    if pa.types.is_uint32(t) or pa.types.is_uint64(t):
        return dt.LongType()
    if pa.types.is_float32(t):
        return dt.FloatType()
    if pa.types.is_float64(t):
        return dt.DoubleType()
    if pa.types.is_decimal(t):
        return dt.DecimalType(t.precision, t.scale)
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return dt.StringType()
    if pa.types.is_binary(t) or pa.types.is_large_binary(t):
        return dt.BinaryType()
    if pa.types.is_date32(t):
        return dt.DateType()
    if pa.types.is_date64(t):
        return dt.DateType()
    if pa.types.is_timestamp(t):
        return dt.TimestampType(t.tz)
    if pa.types.is_time(t):
        return dt.TimeType()
    if pa.types.is_duration(t):
        return dt.DayTimeIntervalType()
    if pa.types.is_interval(t):
        return dt.YearMonthIntervalType()
    if pa.types.is_dictionary(t):
        return arrow_type_to_spec(t.value_type)
    if pa.types.is_null(t):
        return dt.NullType()
    if pa.types.is_list(t) or pa.types.is_large_list(t):
        return dt.ArrayType(arrow_type_to_spec(t.value_type))
    if pa.types.is_struct(t):
        return dt.StructType(tuple(
            dt.StructField(f.name, arrow_type_to_spec(f.type), f.nullable)
            for f in t))
    if pa.types.is_map(t):
        return dt.MapType(arrow_type_to_spec(t.key_type), arrow_type_to_spec(t.item_type))
    raise TypeError(f"unsupported arrow type {t}")


def spec_type_to_arrow(d: dt.DataType) -> pa.DataType:
    if isinstance(d, dt.BooleanType):
        return pa.bool_()
    if isinstance(d, dt.ByteType):
        return pa.int8()
    if isinstance(d, dt.ShortType):
        return pa.int16()
    if isinstance(d, dt.IntegerType):
        return pa.int32()
    if isinstance(d, dt.LongType):
        return pa.int64()
    if isinstance(d, dt.FloatType):
        return pa.float32()
    if isinstance(d, dt.DoubleType):
        return pa.float64()
    if isinstance(d, dt.DecimalType):
        return pa.decimal128(d.precision, d.scale)
    if isinstance(d, dt.StringType):
        return pa.string()
    if isinstance(d, dt.BinaryType):
        return pa.binary()
    if isinstance(d, dt.DateType):
        return pa.date32()
    if isinstance(d, dt.TimestampType):
        return pa.timestamp("us", tz=d.timezone)
    if isinstance(d, dt.TimeType):
        return pa.time64("us")
    if isinstance(d, dt.DayTimeIntervalType):
        return pa.duration("us")
    if isinstance(d, dt.YearMonthIntervalType):
        return pa.month_day_nano_interval()  # months carry the value
    if isinstance(d, dt.NullType):
        return pa.null()
    if isinstance(d, dt.ArrayType):
        return pa.list_(spec_type_to_arrow(d.element_type))
    if isinstance(d, dt.StructType):
        return pa.struct([pa.field(f.name, spec_type_to_arrow(f.data_type), f.nullable)
                          for f in d.fields])
    if isinstance(d, dt.MapType):
        return pa.map_(spec_type_to_arrow(d.key_type), spec_type_to_arrow(d.value_type))
    raise TypeError(f"unsupported spec type {d}")


def _decimal_to_unscaled_int64(arr: pa.Array, validity=None) -> np.ndarray:
    """Unscaled int64 values of a decimal128 array (zero-copy-ish).

    Validates that every value fits in int64 (high word must be the sign
    extension of the low word) — wide-decimal overflow is a loud error, not
    silent corruption."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    buf = arr.buffers()[1]
    raw = np.frombuffer(buf, dtype=np.int64)
    # decimal128 is 16 bytes LE; low word at even indices (plus array offset)
    lo = raw[2 * arr.offset::2][: len(arr)]
    hi = raw[2 * arr.offset + 1::2][: len(arr)]
    ok = hi == (lo >> 63)
    if validity is not None:
        ok = ok | ~validity
    if len(lo) and not ok.all():
        raise TypeError(
            f"decimal values exceed the engine's int64 unscaled range "
            f"(type {arr.type}); reduce precision or cast to double")
    return lo.copy()


def _unscaled_int64_to_decimal(vals: np.ndarray, validity: Optional[np.ndarray],
                               d: dt.DecimalType) -> pa.Array:
    """Vectorized decimal128 construction from unscaled int64 values:
    low word = the value, high word = its sign extension."""
    n = len(vals)
    words = np.empty((n, 2), dtype=np.int64)
    words[:, 0] = vals
    words[:, 1] = vals >> 63  # arithmetic shift: 0 or -1
    data_buf = pa.py_buffer(words.tobytes())
    if validity is not None:
        null_buf = pa.py_buffer(np.packbits(validity.astype(np.uint8), bitorder="little").tobytes())
    else:
        null_buf = None
    return pa.Array.from_buffers(pa.decimal128(d.precision, d.scale), n,
                                 [null_buf, data_buf])


#: a string or binary dictionary of at most this many values is sorted and
#: interned; a larger one (``l_comment``-like columns, whose values never
#: repeat across chunks) stays as the encoder made it
INTERN_MAX_VALUES = 65_536
#: what the intern table holds at most, in values summed over its
#: dictionaries; past it the least recently used go
INTERN_CAPACITY_VALUES = 1 << 20


def _content_key(values: pa.Array) -> tuple:
    """(type, length, digest of the value lengths and bytes): equal for
    two null-free string/binary arrays of equal values, whatever their
    offsets into their buffers."""
    n = len(values)
    if n == 0:
        return (str(values.type), 0, b"")
    large = pa.types.is_large_string(values.type) or \
        pa.types.is_large_binary(values.type)
    _validity, offsets, data = values.buffers()
    off = np.frombuffer(offsets, dtype=np.int64 if large else np.int32)[
        values.offset: values.offset + n + 1]
    h = hashlib.blake2b(np.diff(off).tobytes(), digest_size=16)
    if data is not None:
        h.update(memoryview(data)[int(off[0]):int(off[-1])])
    return (str(values.type), n, h.digest())


class DictionaryInterner:
    """One ``pa.Array`` per dictionary content, bounded as an LRU by the
    values it holds. The op cache keys a program on the identity of the
    dictionaries it was bound to (``exec/local.py _OpCache``), so tables
    with one value set hit one entry only when they hand it one object:
    every chunk of a streamed scan, every merge of their partials."""

    def __init__(self, capacity_values: int = INTERN_CAPACITY_VALUES):
        self._capacity = capacity_values
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, pa.Array]" = OrderedDict()
        self._values = 0

    def intern(self, values: pa.Array) -> Tuple[pa.Array, bool]:
        """The stored array equal to ``values`` and True, or ``values``,
        stored now, and False."""
        key = _content_key(values)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                if not hit.equals(values):  # a digest collision
                    return values, False
                self._entries.move_to_end(key)
                return hit, True
            self._entries[key] = values
            self._values += len(values)
            while self._values > self._capacity and len(self._entries) > 1:
                _key, old = self._entries.popitem(last=False)
                self._values -= len(old)
            return values, False

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._values = 0


#: the process's intern table: sessions share it, as they share the op cache
DICTIONARIES = DictionaryInterner()


def _canonical_dictionary(dictionary: pa.Array, indices: pa.Array
                          ) -> Tuple[pa.Array, pa.Array, bool]:
    """``dictionary`` in sorted order and interned, the null-free
    ``indices`` remapped to it, and whether the dictionary came out of the
    intern table. A dictionary over ``INTERN_MAX_VALUES`` values or
    holding a null comes back as it is."""
    if len(dictionary) > INTERN_MAX_VALUES or dictionary.null_count:
        return dictionary, indices, False
    order = np.asarray(pc.sort_indices(dictionary))
    if (order != np.arange(len(order))).any():
        dictionary = dictionary.take(order)
        rank = np.empty(len(order), dtype=np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
        # Arrow's take: half of numpy's fancy indexing over a chunk's rows
        indices = pc.take(pa.array(rank), indices)
    dictionary, hit = DICTIONARIES.intern(dictionary)
    return dictionary, indices, hit


def from_arrow(table: pa.Table, capacity: Optional[int] = None,
               bucket_key=None) -> HostBatch:
    """Convert a pyarrow Table to a HostBatch (uploads to default device).

    ``bucket_key`` names the consuming program (structural cache key) so
    the pinned-bucket registry can hold the padded capacity stable
    across calls — see :func:`columnar.batch.bucket_capacity`."""
    from .. import tracing as tr
    n = table.num_rows
    cap = capacity if capacity is not None else \
        bucket_capacity(n, key=bucket_key)
    columns: Dict[str, Tuple[np.ndarray, Optional[np.ndarray], dt.DataType]] = {}
    dicts: Dict[str, pa.Array] = {}
    # the host's share of the conversion; padding and device_put are
    # make_batch's ``upload`` span, this one's sibling
    with tr.span("arrow.convert", {"rows": n,
                                   "columns": table.num_columns}) as sp:
        strings = decimals = dicts_interned = 0
        for name, col in zip(table.column_names, table.columns):
            spec_t = arrow_type_to_spec(col.type)
            arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
            validity = None
            if arr.null_count > 0:
                validity = np.asarray(pc.is_valid(arr))
            if pa.types.is_uint64(arr.type):
                mx = pc.max(arr).as_py()
                if mx is not None and mx >= 2**63:
                    raise TypeError(
                        f"column {name!r}: uint64 values >= 2^63 cannot be represented "
                        f"on device (int64); cast to decimal or string first")
            if isinstance(spec_t, (dt.StringType, dt.BinaryType)):
                if pa.types.is_dictionary(arr.type):
                    denc = arr
                else:
                    denc = pc.dictionary_encode(arr)
                if isinstance(denc, pa.ChunkedArray):
                    denc = denc.combine_chunks()
                dicts[name], codes, interned = _canonical_dictionary(
                    denc.dictionary, denc.indices.fill_null(0))
                # make_batch copies the codes into the padded int32 column
                columns[name] = (np.asarray(codes), validity, spec_t)
                strings += 1
                dicts_interned += interned
            elif isinstance(spec_t, dt.DecimalType) and spec_t.physical_dtype == "int64":
                if pa.types.is_decimal256(arr.type):
                    arr = arr.cast(pa.decimal128(spec_t.precision, spec_t.scale))
                vals = _decimal_to_unscaled_int64(arr, validity)
                columns[name] = (vals, validity, spec_t)
                decimals += 1
            elif isinstance(spec_t, dt.DecimalType):
                vals = np.asarray(arr.cast(pa.float64()).fill_null(0.0))
                columns[name] = (vals, validity, spec_t)
            elif isinstance(spec_t, dt.NullType):
                columns[name] = (np.zeros(n, dtype=np.int8), np.zeros(n, dtype=bool), spec_t)
            elif isinstance(spec_t, (dt.ArrayType, dt.StructType, dt.MapType)):
                # Nested types stay host-side in v0: dictionary-encode the whole
                # value so the device carries an opaque int32 handle.
                import pickle
                py = arr.to_pylist()
                uniq: Dict[bytes, int] = {}
                codes = np.empty(n, dtype=np.int32)
                values = []
                for i, v in enumerate(py):
                    k = pickle.dumps(v)
                    if k not in uniq:
                        uniq[k] = len(values)
                        values.append(v)
                    codes[i] = uniq[k]
                dicts[name] = pa.array(values, type=arr.type)
                columns[name] = (codes, validity, spec_t)
            else:
                # Temporal types upload as their epoch integers.
                if isinstance(spec_t, dt.DateType):
                    if pa.types.is_date64(arr.type):
                        arr = arr.cast(pa.date32())
                    arr = arr.view(pa.int32())
                elif isinstance(spec_t, dt.TimestampType):
                    arr = arr.cast(pa.timestamp("us", tz=arr.type.tz)).view(pa.int64())
                elif isinstance(spec_t, dt.DayTimeIntervalType):
                    arr = arr.cast(pa.duration("us")).view(pa.int64())
                elif isinstance(spec_t, dt.TimeType):
                    arr = arr.cast(pa.time64("us")).view(pa.int64())
                elif isinstance(spec_t, dt.YearMonthIntervalType) and \
                        pa.types.is_interval(arr.type):
                    months = np.array(
                        [0 if v is None else v[0] for v in arr.to_pylist()],
                        dtype=np.int32)
                    columns[name] = (months, validity, spec_t)
                    continue
                fill = False if pa.types.is_boolean(arr.type) else 0
                np_vals = np.asarray(arr.fill_null(fill) if arr.null_count else arr)
                columns[name] = (np_vals, validity, spec_t)
        sp.attributes["strings"] = strings
        sp.attributes["decimals"] = decimals
        sp.attributes["dicts_interned"] = dicts_interned
    device = make_batch(columns, n, cap)
    return HostBatch(device, dicts)


def column_values_to_arrow(data, validity, d, dictionary=None) -> pa.Array:
    """Convert host numpy column data (physical encoding) to a pa.Array."""
    name_in_dicts = dictionary is not None
    return _column_to_arrow(data, validity, d, dictionary, name_in_dicts)


def to_arrow(batch: HostBatch) -> pa.Table:
    """Download a HostBatch to a pyarrow Table (live rows only, in order).

    All device arrays (sel + every column's data/validity) are fetched in
    ONE ``jax.device_get`` call: each blocking fetch is a device sync,
    so per-column ``np.asarray`` loops are O(columns) syncs while a
    batched get overlaps the transfers."""
    import jax

    dev = batch.device
    fetch = {"sel": dev.sel}
    for name, col in dev.columns.items():
        fetch[f"d:{name}"] = col.data
        if col.validity is not None:
            fetch[f"v:{name}"] = col.validity
    from .. import profiler
    host = profiler.host_sync("to_arrow", fetch)
    sel = np.asarray(host["sel"])
    idx = np.nonzero(sel)[0]
    arrays = []
    fields = []
    for name, col in dev.columns.items():
        data = np.asarray(host[f"d:{name}"])[idx]
        validity = (np.asarray(host[f"v:{name}"])[idx]
                    if col.validity is not None else None)
        arr = _column_to_arrow(data, validity, col.dtype,
                               batch.dicts.get(name), name in batch.dicts)
        arrays.append(arr)
        fields.append(pa.field(name, arr.type, nullable=True))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def _column_to_arrow(data, validity, d, dictionary, has_dict) -> pa.Array:
    if isinstance(d, (dt.StringType, dt.BinaryType)) and has_dict:
        codes = pa.array(data.astype(np.int32),
                         mask=None if validity is None else ~validity)
        arr = pa.DictionaryArray.from_arrays(codes, dictionary).cast(
            pa.string() if isinstance(d, dt.StringType) else pa.binary())
    elif isinstance(d, (dt.ArrayType, dt.StructType, dt.MapType)) and has_dict:
        # nested dictionaries can't cast; take() materializes (null index →
        # null value)
        codes = pa.array(data.astype(np.int64),
                         mask=None if validity is None else ~validity)
        arr = dictionary.take(codes)
    elif isinstance(d, dt.DecimalType) and d.physical_dtype == "int64":
        arr = _unscaled_int64_to_decimal(data, validity, d)
    elif isinstance(d, dt.DecimalType):
        arr = pa.array(data, mask=None if validity is None else ~validity)
        arr = arr.cast(pa.decimal128(d.precision, d.scale), safe=False)
    elif isinstance(d, dt.NullType):
        arr = pa.nulls(len(data))
    else:
        at = spec_type_to_arrow(d)
        if isinstance(d, dt.TimestampType):
            arr = pa.array(data.astype("datetime64[us]"),
                           mask=None if validity is None else ~validity).cast(at)
        elif isinstance(d, dt.DateType):
            arr = pa.array(data.astype(np.int32),
                           mask=None if validity is None else ~validity).cast(at)
        elif isinstance(d, dt.DayTimeIntervalType):
            arr = pa.array(data.astype("timedelta64[us]"),
                           mask=None if validity is None else ~validity)
        elif isinstance(d, dt.YearMonthIntervalType):
            vals = [None if (validity is not None and not validity[i])
                    else (int(data[i]), 0, 0) for i in range(len(data))]
            arr = pa.array(vals, type=pa.month_day_nano_interval())
        elif isinstance(d, dt.TimeType):
            arr = pa.array(data.astype(np.int64),
                           mask=None if validity is None else ~validity
                           ).cast(pa.time64("us"))
        else:
            arr = pa.array(data, mask=None if validity is None else ~validity)
            if arr.type != at:
                arr = arr.cast(at, safe=False)
    return arr


def unify_dictionaries(dict_a: pa.Array, dict_b: pa.Array) -> Tuple[pa.Array, np.ndarray, np.ndarray]:
    """Merge two dictionaries; returns (merged, remap_a, remap_b) where
    remap_x maps old codes → merged codes. Used before joins/unions on
    string columns so device-side code comparison is exact."""
    merged_tbl = pa.concat_arrays([dict_a.cast(pa.string()), dict_b.cast(pa.string())])
    enc = pc.dictionary_encode(merged_tbl)
    if isinstance(enc, pa.ChunkedArray):
        enc = enc.combine_chunks()
    codes = np.asarray(enc.indices)
    remap_a = codes[: len(dict_a)].astype(np.int32)
    remap_b = codes[len(dict_a):].astype(np.int32)
    return enc.dictionary, remap_a, remap_b


def dictionary_ranks(dictionary: pa.Array) -> np.ndarray:
    """Order-preserving rank per dictionary code (for ORDER BY / range
    comparisons on dictionary-encoded strings)."""
    order = pc.sort_indices(dictionary)
    ranks = np.empty(len(dictionary), dtype=np.int32)
    ranks[np.asarray(order)] = np.arange(len(dictionary), dtype=np.int32)
    return ranks
