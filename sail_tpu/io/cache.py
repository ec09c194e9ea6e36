"""File-listing and parquet-metadata caches.

Reference role: crates/sail-cache/src/file_listing_cache.rs and
file_metadata_cache.rs (moka TTL caches wired into the session). Every
query otherwise re-walks scan directories and re-reads parquet footers.

Validation strategy:
- listing entries carry a TTL (``execution.file_listing_cache.ttl_secs``,
  0 disables) AND re-stat the input roots on every hit — an external
  write to a flat directory invalidates immediately via the root's mtime;
  only nested partition-directory adds ride out the TTL window. Engine
  writes clear the cache explicitly.
- parquet footer metadata validates by (size, mtime) per file — always
  sound, no TTL needed. What is derived from a footer (a column's
  statistics over its row groups) is kept beside it and goes with it.

Counters (hits/misses) are exposed for tests and system tables.
"""

from __future__ import annotations

import datetime
import os
import time
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple


def _stat_sig(path: str) -> Optional[Tuple[float, int]]:
    try:
        st = os.stat(path)
        return (st.st_mtime, st.st_size)
    except OSError:
        return None


from ..metrics import record as _record_metric


def _record(metric: str) -> None:
    _record_metric(metric, 1)


class FileListingCache:
    def __init__(self):
        self._lock = threading.Lock()
        self._data: Dict[Tuple[str, ...], Tuple[float, tuple, List[str]]] = {}
        self.hits = 0
        self.misses = 0
        self._ttl_cached: Optional[float] = None

    def _ttl(self) -> float:
        # read once (config lookups re-flatten the whole tree — too slow
        # for the scan planning hot path); clear() re-reads
        if self._ttl_cached is None:
            from ..config import get as config_get
            try:
                self._ttl_cached = float(
                    config_get("execution.file_listing_cache.ttl_secs", 30))
            except (TypeError, ValueError):
                self._ttl_cached = 30.0
        return self._ttl_cached

    def get(self, paths: Sequence[str]) -> Optional[List[str]]:
        key = tuple(paths)
        with self._lock:
            entry = self._data.get(key)
            if entry is None:
                self.misses += 1
                _record("cache.file_listing.miss_count")
                return None
            expires, validator, files = entry
            if time.time() > expires:
                del self._data[key]
                self.misses += 1
                _record("cache.file_listing.miss_count")
                return None
        if tuple(_stat_sig(p) for p in key) != validator:
            with self._lock:
                self._data.pop(key, None)
                self.misses += 1
            _record("cache.file_listing.miss_count")
            return None
        with self._lock:
            self.hits += 1
        _record("cache.file_listing.hit_count")
        return list(files)

    def put(self, paths: Sequence[str], files: List[str]) -> None:
        ttl = self._ttl()
        if ttl <= 0:
            return
        key = tuple(paths)
        validator = tuple(_stat_sig(p) for p in key)
        with self._lock:
            while len(self._data) > 256:
                self._data.pop(next(iter(self._data)))
            self._data[key] = (time.time() + ttl, validator, files)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._ttl_cached = None

    def invalidate_root(self, root: str) -> None:
        """Drop every listing whose input paths touch ``root`` (equal
        or nested either way) — the engine-write hook. Nested
        partition-directory adds don't move the root's mtime, so
        without this they ride out the whole TTL window."""
        prefix = os.path.normpath(root) + os.sep
        with self._lock:
            doomed = [key for key in self._data
                      if any(os.path.normpath(p) == prefix[:-1]
                             or os.path.normpath(p).startswith(prefix)
                             or prefix[:-1].startswith(
                                 os.path.normpath(p) + os.sep)
                             for p in key)]
            for key in doomed:
                del self._data[key]


class ColumnStats(NamedTuple):
    """What one file's footer says of one column, over all its row
    groups. ``distinct`` is the writer's ``distinct_count`` summed (an
    upper bound of the file's; None unless every column chunk carries
    one); ``lo`` / ``hi`` the smallest ``min`` and largest ``max`` of an
    integer, boolean or date column (None for any other type, or where
    a chunk with values has no statistics)."""

    distinct: Optional[int]
    lo: object
    hi: object


def column_stats(md, column: str) -> Optional[ColumnStats]:
    """Walk the row groups of a ``pq.FileMetaData`` for ``column``'s
    statistics. None where the file has no such top-level column or the
    footer says nothing usable about it. Reads the footer only."""
    index, chunks = None, []
    for g in range(md.num_row_groups):
        rg = md.row_group(g)
        if index is None:
            index = next((j for j in range(rg.num_columns)
                          if rg.column(j).path_in_schema == column), None)
            if index is None:
                return None
        chunk = rg.column(index)
        st = chunk.statistics
        if chunk.num_values == 0 or (st is not None and not st.has_min_max
                                     and st.null_count == chunk.num_values):
            continue  # an empty or all-NULL chunk holds no key value
        if st is None:
            return None
        chunks.append(st)
    if not chunks:
        return None
    distinct = lo = hi = None
    if all(st.has_distinct_count and st.distinct_count for st in chunks):
        distinct = sum(st.distinct_count for st in chunks)
    if all(st.has_min_max and _rangeable(st.min) for st in chunks):
        lo = min(st.min for st in chunks)
        hi = max(st.max for st in chunks)
    if distinct is None and lo is None:
        return None
    return ColumnStats(distinct, lo, hi)


def _rangeable(v) -> bool:
    """Values whose max − min + 1 bounds a distinct count: integers,
    booleans, dates (a timestamp, decimal, float or string is not)."""
    return isinstance(v, (int, datetime.date)) \
        and not isinstance(v, datetime.datetime)


class ParquetMetadataCache:
    """Per file, validated by (mtime, size): the footer
    (``pq.FileMetaData``: row counts for the planner's cardinality
    model, the schema) and, derived from it on first request, each asked
    column's ``ColumnStats`` (the join reorder's distinct-count bound).
    Footers only: nothing here decodes a column."""

    def __init__(self):
        self._lock = threading.Lock()
        self._data: Dict[str, Tuple[Tuple[float, int], object,
                                    Dict[str, Optional[ColumnStats]]]] = {}
        self.hits = 0
        self.misses = 0

    def _entry(self, path: str):
        sig = _stat_sig(path)
        with self._lock:
            entry = self._data.get(path)
            if entry is not None and entry[0] == sig:
                self.hits += 1
                _record("cache.parquet_metadata.hit_count")
                return entry
            self.misses += 1
        _record("cache.parquet_metadata.miss_count")
        import pyarrow.parquet as pq
        entry = (sig, pq.ParquetFile(path).metadata, {})
        with self._lock:
            while len(self._data) > 4096:
                self._data.pop(next(iter(self._data)))
            self._data[path] = entry
        return entry

    def metadata(self, path: str):
        """pq.FileMetaData for ``path``, validated by (mtime, size)."""
        return self._entry(path)[1]

    def num_rows(self, path: str) -> int:
        return int(self.metadata(path).num_rows)

    def column_stats(self, path: str, column: str) -> Optional[ColumnStats]:
        """``column_stats`` of ``path``'s footer, walked once per
        (file signature, column) and kept beside the footer."""
        _sig, md, stats = self._entry(path)
        if column not in stats:
            stats[column] = column_stats(md, column)
        return stats[column]

    def clear(self) -> None:
        with self._lock:
            self._data.clear()


LISTING_CACHE = FileListingCache()
METADATA_CACHE = ParquetMetadataCache()


def invalidate_listings(root: Optional[str] = None) -> None:
    """Called by every engine-side write (files added/removed). With a
    ``root``, only listings touching that root are dropped — commit
    paths pass the written table root so unrelated tables keep their
    warm listings."""
    if root is None:
        LISTING_CACHE.clear()
    else:
        LISTING_CACHE.invalidate_root(root)
