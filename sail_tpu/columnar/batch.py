"""Device-resident columnar batches.

This is the TPU-native replacement for the reference's Arrow RecordBatch
execution substrate (reference role: arrow-rs arrays flowing through
DataFusion operators). Design, driven by XLA's static-shape compilation
model:

- A ``Column`` is a fixed-capacity padded device array plus an optional
  validity (null) mask. Capacity is a *static* (compile-time) property;
  live row count is carried dynamically by the batch selection mask.
- A ``DeviceBatch`` holds named columns plus a boolean *selection* mask;
  filters never compact (compaction creates dynamic shapes) — they narrow
  the selection, and XLA fuses the mask arithmetic into downstream ops.
  Explicit ``compact`` reorders live rows to the front when an op (sort,
  join build, limit) benefits.
- Variable-width data (strings/binary) is dictionary-encoded: the device
  carries int32 codes; the dictionary (a pyarrow Array) stays host-side in
  the ``HostBatch`` wrapper and never enters jit.

Both Column and DeviceBatch are pytrees, so jitted kernels take and return
them directly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..spec import data_type as dt


@jax.tree_util.register_pytree_node_class
class Column:
    """A padded device array + optional validity mask + logical type."""

    __slots__ = ("data", "validity", "dtype")

    def __init__(self, data, validity, dtype: dt.DataType):
        self.data = data
        self.validity = validity  # bool[capacity] or None (all valid)
        self.dtype = dtype

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def valid_mask(self):
        if self.validity is None:
            return jnp.ones(self.data.shape[0], dtype=jnp.bool_)
        return self.validity

    def with_data(self, data, validity="__keep__") -> "Column":
        v = self.validity if isinstance(validity, str) else validity
        return Column(data, v, self.dtype)

    def tree_flatten(self):
        return (self.data, self.validity), (self.dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity = children
        return cls(data, validity, aux[0])

    def __repr__(self):
        return f"Column({self.dtype.simple_string()}, cap={self.data.shape[0] if hasattr(self.data, 'shape') else '?'})"


@jax.tree_util.register_pytree_node_class
class DeviceBatch:
    """Named columns + selection mask. All arrays share one capacity."""

    __slots__ = ("columns", "sel")

    def __init__(self, columns: Dict[str, Column], sel):
        self.columns = columns
        self.sel = sel  # bool[capacity]

    @property
    def capacity(self) -> int:
        return self.sel.shape[0]

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def num_rows(self):
        """Dynamic live row count (device scalar)."""
        return jnp.sum(self.sel.astype(jnp.int32))

    @property
    def nbytes(self) -> int:
        """What the batch holds on the device: every column's data
        and validity and the selection, at the capacity (no sync)."""
        return self.sel.nbytes + sum(
            c.data.nbytes + (0 if c.validity is None else c.validity.nbytes)
            for c in self.columns.values())

    def select(self, names) -> "DeviceBatch":
        return DeviceBatch({n: self.columns[n] for n in names}, self.sel)

    def with_columns(self, new: Dict[str, Column]) -> "DeviceBatch":
        cols = dict(self.columns)
        cols.update(new)
        return DeviceBatch(cols, self.sel)

    def with_sel(self, sel) -> "DeviceBatch":
        return DeviceBatch(self.columns, sel)

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        children = tuple(self.columns[n] for n in names) + (self.sel,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1])

    def __repr__(self):
        return f"DeviceBatch({list(self.columns)}, cap={self.capacity})"


@dataclasses.dataclass
class HostBatch:
    """A DeviceBatch plus its host-side string dictionaries.

    Physical operators pass HostBatch between themselves; the jit boundary
    receives only the inner DeviceBatch pytree. ``dicts`` maps column name →
    pyarrow Array of dictionary values for String/Binary columns.
    """

    device: DeviceBatch
    dicts: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def capacity(self) -> int:
        return self.device.capacity

    @property
    def names(self) -> Tuple[str, ...]:
        return self.device.names

    def schema_types(self) -> Dict[str, dt.DataType]:
        return {n: c.dtype for n, c in self.device.columns.items()}

    def num_rows(self) -> int:
        return int(self.device.num_rows())


_CAPACITY_MIN: Optional[int] = None


def _capacity_min() -> int:
    """``execution.batch_capacity_min``, read once per process (this
    sits under every batch construction)."""
    global _CAPACITY_MIN
    if _CAPACITY_MIN is None:
        try:
            from ..config import get as config_get
            _CAPACITY_MIN = max(1, int(config_get(
                "execution.batch_capacity_min", 8)))
        except (TypeError, ValueError, ImportError):
            _CAPACITY_MIN = 8
    return _CAPACITY_MIN


def round_capacity(n: int, minimum: Optional[int] = None) -> int:
    """Round a row count up to the padded device capacity.

    Buckets to 1.25^k-ish steps on top of powers of two fragments so that
    repeated scans with similar sizes hit the jit cache instead of
    recompiling (XLA static shapes).
    """
    if minimum is None:
        minimum = _capacity_min()
    if n <= minimum:
        return minimum
    p = 1 << (int(n - 1).bit_length() - 1)  # largest pow2 <= n-1... p < n <= 2p
    for frac in (p + p // 4, p + p // 2, p + 3 * (p // 4), 2 * p):
        if n <= frac:
            return frac
    return 2 * p


def bucket_capacity(n: int, key=None,
                    minimum: Optional[int] = None) -> int:
    """THE capacity policy: every padded-capacity derivation in the
    engine routes through here (the capacity-policy lint fails direct
    ``round_capacity`` calls anywhere else).

    With a ``key`` (a structural program/stage cache key — the same
    vocabulary the retrace ledger fingerprints), delegates to the
    pinned grow-only bucket registry (``exec/capacity.py``): once a
    program is warmed its bucket only grows, and growth needs a
    sustained overflow streak, so oscillating input sizes stop crossing
    bucket boundaries (zero capacity-bucket retraces after warmup).
    Without a key — or with pinning disabled — this is plain
    ``round_capacity`` rounding.
    """
    if key is None:
        return round_capacity(n, minimum)
    try:
        from ..exec.capacity import bucket_for
    except ImportError:
        return round_capacity(n, minimum)
    return bucket_for(key, n, minimum)


def physical_jnp_dtype(d: dt.DataType):
    if isinstance(d, (dt.ArrayType, dt.MapType, dt.StructType)):
        return jnp.dtype("int32")  # dictionary code handle (values on host)
    name = d.physical_dtype
    if name is None:
        raise TypeError(f"type {d.simple_string()} has no device representation")
    return jnp.dtype(name)


def make_batch(columns: Dict[str, Tuple[np.ndarray, Optional[np.ndarray], dt.DataType]],
               num_rows: int, capacity: Optional[int] = None,
               bucket_key=None) -> DeviceBatch:
    import jax

    from .. import tracing as tr
    from ..profiler import note_transfer_bytes

    cap = capacity if capacity is not None else \
        bucket_capacity(num_rows, key=bucket_key)
    # padding to the capacity bucket + the transfer: the host's share of
    # getting a table onto the device
    with tr.span("upload") as sp:
        host = {}
        types = {}
        for name, (values, validity, dtype) in columns.items():
            n = len(values)
            data = np.zeros(cap, dtype=physical_jnp_dtype(dtype))
            data[:n] = values
            v = None
            if validity is not None:
                v = np.zeros(cap, dtype=bool)
                v[:n] = validity
            host[name] = (data, v)
            types[name] = dtype
        sel = np.zeros(cap, dtype=bool)
        sel[:num_rows] = True
        # ONE batched transfer for all columns (a per-column jnp.asarray
        # costs ~1 ms of dispatch each; the output of a small aggregate
        # was paying 10+ ms in uploads alone)
        nbytes = sel.nbytes + sum(
            d.nbytes + (v.nbytes if v is not None else 0)
            for d, v in host.values())
        sp.attributes["bytes"] = nbytes
        note_transfer_bytes(nbytes)
        dhost, dsel = jax.device_put((host, sel))
    cols = {name: Column(dhost[name][0], dhost[name][1], types[name])
            for name in host}
    return DeviceBatch(cols, dsel)


def empty_batch(types: Dict[str, dt.DataType], capacity: int = 8) -> DeviceBatch:
    cols = {}
    for name, d in types.items():
        jdt = physical_jnp_dtype(d)
        cols[name] = Column(jnp.zeros(capacity, dtype=jdt),
                            jnp.zeros(capacity, dtype=jnp.bool_), d)
    return DeviceBatch(cols, jnp.zeros(capacity, dtype=jnp.bool_))
