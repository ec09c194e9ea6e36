"""Result + materialized-fragment cache and continuously-maintained views.

The serving workload this targets is thousands of near-identical
dashboard queries over slowly-changing tables: with the compile
caches hot, first-scan decode/upload dominates cold latency.
Three reuse tiers sit above the scan path:

- **result tier** (``ResultCache``): whole-query results keyed by
  ``plan_fingerprint`` (plan/stages.py) + a *version vector* over every
  scanned table — Delta log versions and file mtimes give precise
  invalidation for lakehouse tables, a DML-bumped counter versions
  memory tables. A hit skips resolution's downstream entirely (local,
  mesh and cluster paths alike).
- **fragment tier** (``FragmentCache``): decoded, device-resident scan
  batches — the successor of exec/local.py's ``_SCAN_CACHE`` — with
  byte-budgeted, cost-weighted eviction (evict ascending (decode
  cost, last access): cheapest-to-rebuild, coldest first). Fragment
  stores feed
  ``join_reorder.note_observed_rows`` so AQE/join ordering treat cached
  fragments as grounded, observed-exact inputs.
- **view tier** (``MaterializedViewManager``): ``CACHE MATERIALIZED``
  declares a defining query a continuously-maintained view. Base-table
  DML folds change deltas through the incremental keyed-state store
  (streaming_state.KeyedStateStore — the PR 15 machinery) into the
  cached fragment at marker cadence; non-mergeable plans fall back to
  full recompute per marker. Reads resolve against the materialized
  memory table and never rescan base data.

Invalidation contract: ``bump_table_version`` is the single hook every
write path calls (memory DML via ``Session._table_mutated``, Delta
``Transaction.commit``, Iceberg metadata writes). It versions the
table, drops file-listing cache entries for the written root, evicts
dependent result/fragment entries, and triggers view maintenance.

Staleness soundness: memory tables are snapshot-by-identity (DML
replaces ``entry.data`` wholesale; cached entries pin the old object,
so an id match implies the exact snapshot), Delta versions are
monotonic and read at probe time. A store racing a commit can only
serve data *fresher* than its key claims — never stale.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Dict, FrozenSet, List, NamedTuple, Optional, Tuple

import pyarrow as pa

from ..metrics import record as _record_metric

# ---------------------------------------------------------------------------
# table-version registry
# ---------------------------------------------------------------------------

_VERSIONS_LOCK = threading.Lock()
_TABLE_VERSIONS: Dict[str, int] = {}


def memory_table_key(name) -> str:
    """Dependency key for a memory table (dotted name, lowercased)."""
    if isinstance(name, (tuple, list)):
        name = ".".join(str(p) for p in name)
    return "mem:" + str(name).lower()


def entry_table_key(entry) -> Tuple[str, Optional[str]]:
    """``(dependency key, filesystem root)`` for a catalog TableEntry.
    Path-backed tables key on their root path (shared with the Delta/
    Iceberg commit hooks); memory tables on their dotted name."""
    if entry.paths:
        root = entry.paths[0]
        return root, root
    return memory_table_key(entry.name), None


def table_version(key: str) -> int:
    with _VERSIONS_LOCK:
        return _TABLE_VERSIONS.get(key, 0)


def bump_table_version(key: str, root: Optional[str] = None) -> None:
    """The write hook: version the table, clear file listings for the
    written root (nested partition-directory adds would otherwise ride
    out the listing TTL), and proactively evict dependent entries."""
    with _VERSIONS_LOCK:
        _TABLE_VERSIONS[key] = _TABLE_VERSIONS.get(key, 0) + 1
    if root is not None:
        from ..io.cache import invalidate_listings
        invalidate_listings(root)
    RESULT_CACHE.invalidate_table(key)
    FRAGMENT_CACHE.invalidate_table(key)


# ---------------------------------------------------------------------------
# cacheability probe
# ---------------------------------------------------------------------------

#: scalar functions whose value depends on execution time, process
#: state or an RNG drawn at EXECUTION time (exec/host_interp.py) — a
#: result-cache hit would freeze them, so plans calling any are
#: uncacheable. ``__pyudf`` covers arbitrary Python UDFs.
NONDETERMINISTIC_FNS = frozenset({
    "rand", "randn", "random", "uuid", "shuffle",
    "now", "current_timestamp", "localtimestamp", "current_date",
    "current_timezone", "unix_timestamp",
    "monotonically_increasing_id", "spark_partition_id",
    "input_file_name", "__pyudf",
})


def _value_nondeterministic(value) -> bool:
    """Walk a plan-node field value's Rex trees for nondeterministic
    calls. PlanNode children are skipped — walk_plan visits those."""
    from ..plan import nodes as pn
    from ..plan import rex as rx
    stack = [value]
    while stack:
        v = stack.pop()
        if isinstance(v, pn.PlanNode):
            continue
        if isinstance(v, rx.RCall) and \
                str(v.fn).lower() in NONDETERMINISTIC_FNS:
            return True
        if isinstance(v, (tuple, list)):
            stack.extend(v)
        elif dataclasses.is_dataclass(v) and not isinstance(v, type):
            for f in dataclasses.fields(v):
                stack.append(getattr(v, f.name))
    return False


def plan_deterministic(node) -> bool:
    from ..plan import nodes as pn
    for n in pn.walk_plan(node):
        for f in dataclasses.fields(n):
            if _value_nondeterministic(getattr(n, f.name)):
                return False
    return True


def _scan_leaf_version(scan) -> Optional[Tuple[str, tuple]]:
    """``(dependency key, version-vector part)`` for one ScanExec leaf,
    or ``None`` when the leaf makes the plan uncacheable (user python
    data sources, system tables materialized fresh per resolve)."""
    import os
    if scan.format == "python_ds":
        return None
    if scan.source is not None:
        if not scan.table_name:
            # system tables: a fresh pa.Table per resolve, no identity
            return None
        key = memory_table_key(scan.table_name)
        return key, ("mem", key, id(scan.source), table_version(key))
    if not scan.paths:
        return None
    root = scan.paths[0]
    if scan.format == "delta":
        try:
            from ..lakehouse.delta import DeltaLog
            ver = DeltaLog(root).latest_version()
        except Exception:  # noqa: BLE001 — unreadable log: don't cache
            return None
        return root, ("delta", root, ver, table_version(root))
    try:
        from ..io.formats import expand_paths
        files = tuple(expand_paths(scan.paths))
        mtimes = tuple(int(os.path.getmtime(f) * 1e6) for f in files)
    except Exception:  # noqa: BLE001 — unlistable paths: don't cache
        return None
    return root, ("file", files, mtimes, table_version(root))


class CacheProbe(NamedTuple):
    """A cacheable resolved plan: the full cache key (fingerprint +
    version vector + session knobs), the table keys the entry depends
    on, and the memory-table objects to pin and identity-verify."""

    key: tuple
    depends: FrozenSet[str]
    sources: Tuple[object, ...]


def probe(node, session_key: tuple = ()) -> Optional[CacheProbe]:
    """Classify a RESOLVED plan for result caching. ``None`` means
    uncacheable: no scans (constant plans are cheap), a nondeterministic
    expression, an unversionable leaf, or an unhashable fingerprint."""
    from ..plan import nodes as pn
    from ..plan.stages import plan_fingerprint
    scans = [n for n in pn.walk_plan(node) if isinstance(n, pn.ScanExec)]
    if not scans:
        return None
    if not plan_deterministic(node):
        return None
    depends = set()
    versions = []
    for s in scans:
        leaf = _scan_leaf_version(s)
        if leaf is None:
            return None
        dep, part = leaf
        depends.add(dep)
        versions.append(part)
    try:
        fp_key, sources = plan_fingerprint(node)
        full = (fp_key, tuple(versions), tuple(session_key))
        hash(full)
    except Exception:  # noqa: BLE001 — unhashable fingerprint
        return None
    return CacheProbe(full, frozenset(depends), tuple(sources))


# ---------------------------------------------------------------------------
# result tier
# ---------------------------------------------------------------------------

_FRAGMENT_IDS = itertools.count(1)


def _budget_bytes(value, default_mb: float) -> int:
    try:
        return int(float(value) * 1024 * 1024)
    except (TypeError, ValueError):
        return int(default_mb * 1024 * 1024)


@dataclasses.dataclass
class _ResultEntry:
    fragment_id: str
    key: tuple
    table: pa.Table
    sources: Tuple[object, ...]
    depends: FrozenSet[str]
    nbytes: int
    build_ms: float
    created: float
    last_access: float
    hits: int = 0


class ResultCache:
    """Whole-query results keyed by ``CacheProbe.key``. Byte-budgeted
    (``cache.result.max_mb``); eviction ascending (build cost, last
    access)."""

    tier = "result"

    def __init__(self, max_mb: Optional[float] = None):
        self._lock = threading.Lock()
        self._entries: Dict[tuple, _ResultEntry] = {}
        self._max_mb = max_mb
        self._budget_cached: Optional[int] = None

    def _budget(self) -> int:
        if self._max_mb is not None:
            return _budget_bytes(self._max_mb, 256)
        if self._budget_cached is None:
            from ..config import get as config_get
            self._budget_cached = _budget_bytes(
                config_get("cache.result.max_mb", 256), 256)
        return self._budget_cached

    def _verify(self, e: Optional[_ResultEntry],
                p: CacheProbe) -> Optional[_ResultEntry]:
        if e is None or len(e.sources) != len(p.sources):
            return None
        if not all(a is b for a, b in zip(e.sources, p.sources)):
            return None
        return e

    def lookup(self, p: CacheProbe) -> Optional[_ResultEntry]:
        with self._lock:
            e = self._verify(self._entries.get(p.key), p)
            if e is not None:
                e.hits += 1
                e.last_access = time.time()
        if e is None:
            _record_metric("execution.result_cache.miss_count", 1,
                           tier="result")
            return None
        _record_metric("execution.result_cache.hit_count", 1,
                       tier="result")
        _record_metric("execution.result_cache.bytes_served", e.nbytes,
                       tier="result")
        return e

    def peek(self, p: CacheProbe) -> Optional[_ResultEntry]:
        """Non-counting lookup for EXPLAIN: no hit bump, no metrics."""
        with self._lock:
            return self._verify(self._entries.get(p.key), p)

    def store(self, p: CacheProbe, table: pa.Table,
              build_ms: float) -> Optional[_ResultEntry]:
        try:
            nbytes = int(table.nbytes)
        except Exception:  # noqa: BLE001 — size is advisory
            nbytes = 0
        budget = self._budget()
        if budget <= 0 or nbytes > budget // 4:
            # dashboard results are small; one bulk export must not
            # churn the whole tier
            return None
        now = time.time()
        e = _ResultEntry("rc-%d" % next(_FRAGMENT_IDS), p.key, table,
                         p.sources, p.depends, nbytes, build_ms, now, now)
        with self._lock:
            self._entries[p.key] = e
            evicted = self._evict_over_budget(budget, keep=p.key)
        if evicted:
            _record_metric("execution.result_cache.evicted_count",
                           evicted, tier="result")
        return e

    def _evict_over_budget(self, budget: int, keep: tuple) -> int:
        total = sum(e.nbytes for e in self._entries.values())
        if total <= budget:
            return 0
        order = sorted(self._entries.values(),
                       key=lambda e: (e.build_ms, e.last_access))
        n = 0
        for e in order:
            if total <= budget:
                break
            if e.key == keep:
                continue
            del self._entries[e.key]
            total -= e.nbytes
            n += 1
        return n

    def invalidate_table(self, key: str) -> None:
        with self._lock:
            doomed = [k for k, e in self._entries.items()
                      if key in e.depends]
            for k in doomed:
                del self._entries[k]
        if doomed:
            _record_metric("execution.result_cache.invalidated_count",
                           len(doomed), tier="result")

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._budget_cached = None

    def snapshot(self) -> List[dict]:
        with self._lock:
            entries = list(self._entries.values())
        return [{"tier": "result", "id": e.fragment_id,
                 "key": repr(e.key[0])[:200],
                 "tables": sorted(e.depends),
                 "bytes": e.nbytes, "rows": e.table.num_rows,
                 "hit_count": e.hits, "cost_ms": e.build_ms,
                 "versions": repr(e.key[1]),
                 "last_access": e.last_access} for e in entries]


# ---------------------------------------------------------------------------
# fragment tier
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _FragmentEntry:
    fragment_id: str
    key: tuple
    source: Optional[object]       # memory-table pin, identity-verified
    batch: object                  # device-resident HostBatch
    rtf_stats: Optional[tuple]
    table_key: Optional[str]
    nbytes: int
    rows: int
    decode_ms: float
    created: float
    last_access: float
    hits: int = 0


class FragmentCache:
    """Decoded device-resident scan fragments, keyed by the scan cache
    key vocabulary of exec/local.py (_exec_ScanExec). Count-bounded by
    ``runtime.scan_cache_size`` (compat with the _SCAN_CACHE it
    replaces) and byte-budgeted by ``cache.fragment.max_mb`` with
    (decode cost, last access)-ascending eviction."""

    tier = "fragment"

    def __init__(self, max_mb: Optional[float] = None):
        self._lock = threading.Lock()
        self._entries: Dict[tuple, _FragmentEntry] = {}
        self._max_mb = max_mb
        self._budget_cached: Optional[int] = None
        self._count_cached: Optional[int] = None

    def _budget(self) -> int:
        if self._max_mb is not None:
            return _budget_bytes(self._max_mb, 8192)
        if self._budget_cached is None:
            from ..config import get as config_get
            self._budget_cached = _budget_bytes(
                config_get("cache.fragment.max_mb", 8192), 8192)
        return self._budget_cached

    def _count_bound(self) -> int:
        if self._count_cached is None:
            try:
                from ..config import get as config_get
                self._count_cached = max(
                    1, int(config_get("runtime.scan_cache_size", 64)))
            except (TypeError, ValueError, ImportError):
                self._count_cached = 64
        return self._count_cached

    def get(self, key: tuple, source) -> Optional[_FragmentEntry]:
        with self._lock:
            e = self._entries.get(key)
            if e is not None and source is not None \
                    and e.source is not source:
                e = None
            if e is not None:
                e.hits += 1
                e.last_access = time.time()
        if e is None:
            _record_metric("execution.result_cache.miss_count", 1,
                           tier="fragment")
            return None
        _record_metric("execution.result_cache.hit_count", 1,
                       tier="fragment")
        _record_metric("execution.result_cache.bytes_served", e.nbytes,
                       tier="fragment")
        return e

    def put(self, key: tuple, source, batch, rtf_stats, *,
            table_key: Optional[str] = None, nbytes: int = 0,
            rows: int = 0, decode_ms: float = 0.0) -> _FragmentEntry:
        now = time.time()
        e = _FragmentEntry("fg-%d" % next(_FRAGMENT_IDS), key, source,
                           batch, rtf_stats, table_key, int(nbytes),
                           int(rows), decode_ms, now, now)
        evicted = 0
        with self._lock:
            self._entries[key] = e
            while len(self._entries) > self._count_bound():
                victim = next(iter(self._entries))
                if victim == key:
                    break
                del self._entries[victim]
                evicted += 1
            budget = self._budget()
            if budget > 0:
                total = sum(x.nbytes for x in self._entries.values())
                if total > budget:
                    order = sorted(self._entries.values(),
                                   key=lambda x: (x.decode_ms,
                                                  x.last_access))
                    for x in order:
                        if total <= budget:
                            break
                        if x.key == key:
                            continue  # never the just-decoded fragment
                        del self._entries[x.key]
                        total -= x.nbytes
                        evicted += 1
        if evicted:
            _record_metric("execution.result_cache.evicted_count",
                           evicted, tier="fragment")
        return e

    def invalidate_table(self, key: str) -> None:
        with self._lock:
            doomed = [k for k, e in self._entries.items()
                      if e.table_key == key]
            for k in doomed:
                del self._entries[k]
        if doomed:
            _record_metric("execution.result_cache.invalidated_count",
                           len(doomed), tier="fragment")

    def drop_mem(self, table_id: int) -> None:
        """Drop entries pinning one memory table by id (chunked scans
        evict their slice entries to avoid pinning device memory)."""
        with self._lock:
            doomed = [k for k in self._entries
                      if k and k[0] == "mem" and k[1] == table_id]
            for k in doomed:
                del self._entries[k]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._budget_cached = None
            self._count_cached = None

    def snapshot(self) -> List[dict]:
        with self._lock:
            entries = list(self._entries.values())
        return [{"tier": "fragment", "id": e.fragment_id,
                 "key": repr(e.key)[:200],
                 "tables": [e.table_key] if e.table_key else [],
                 "bytes": e.nbytes, "rows": e.rows,
                 "hit_count": e.hits, "cost_ms": e.decode_ms,
                 "versions": "", "last_access": e.last_access}
                for e in entries]


# ---------------------------------------------------------------------------
# view tier: continuously-maintained materialized views
# ---------------------------------------------------------------------------

def _collect_read_names(plan) -> List[Tuple[str, ...]]:
    from ..spec import plan as sp
    names: List[Tuple[str, ...]] = []
    stack = [plan]
    while stack:
        v = stack.pop()
        if isinstance(v, sp.ReadNamedTable):
            names.append(tuple(v.name))
        if isinstance(v, (tuple, list)):
            stack.extend(v)
        elif dataclasses.is_dataclass(v) and not isinstance(v, type):
            for f in dataclasses.fields(v):
                stack.append(getattr(v, f.name))
    return names


def _substitute_read(plan, name_lower: str, replacement):
    """Replace every ReadNamedTable of ``name_lower`` in a SPEC plan
    (mirrors streaming.py's _substitute_source, sans stream leaves)."""
    from ..spec import plan as sp
    if isinstance(plan, sp.ReadNamedTable) and plan.name \
            and plan.name[-1].lower() == name_lower:
        return replacement
    for f in (dataclasses.fields(plan)
              if dataclasses.is_dataclass(plan) else []):
        v = getattr(plan, f.name)
        if isinstance(v, sp.QueryPlan):
            plan = dataclasses.replace(plan, **{
                f.name: _substitute_read(v, name_lower, replacement)})
    return plan


def _schema_of(table: pa.Table):
    from ..spec import data_type as dt
    from ..columnar.arrow_interop import arrow_type_to_spec
    return dt.StructType(tuple(
        dt.StructField(n, arrow_type_to_spec(c.type), True)
        for n, c in zip(table.column_names, table.columns)))


@dataclasses.dataclass
class MaterializedView:
    name: str
    plan: object                        # defining spec QueryPlan
    entry: object                       # catalog TableEntry serving reads
    catalog: object                     # owning CatalogManager
    depends: FrozenSet[str]
    base_name: Optional[str] = None     # single base (incremental mode)
    spec: object = None                 # streaming_state.AggSpec or None
    store: object = None                # KeyedStateStore or None
    marker: int = 0


class MaterializedViewManager:
    """``CACHE MATERIALIZED`` views. Maintenance runs synchronously in
    the mutating session's DML path (markers = commits): mergeable
    single-base aggregates fold just the appended delta through a
    KeyedStateStore and re-run the cheap residual plan; everything else
    recomputes the defining query. Reads resolve against the
    materialized memory table (a TableEntry with data, no view_plan) and
    never rescan base tables."""

    def __init__(self):
        self._lock = threading.Lock()
        self._views: Dict[str, MaterializedView] = {}

    # -- registry ------------------------------------------------------
    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._views)

    def is_view(self, table_name) -> bool:
        if not table_name:
            return False
        name = str(table_name).split(".")[-1].lower()
        with self._lock:
            return name in self._views

    def get(self, name: str) -> Optional[MaterializedView]:
        with self._lock:
            return self._views.get(str(name).lower())

    # -- lifecycle -----------------------------------------------------
    def create(self, session, name: str, plan) -> MaterializedView:
        from ..catalog.manager import TableEntry
        from .. import streaming_state as ss
        name = str(name).lower()
        cm = session.catalog_manager
        depends = set()
        base_names = []
        for nm in _collect_read_names(plan):
            entry = cm.lookup_table(nm)
            if entry is None:
                raise ValueError(
                    f"CACHE MATERIALIZED {name}: unknown base table "
                    f"{'.'.join(nm)}")
            key, _root = entry_table_key(entry)
            depends.add(key)
            base_names.append(nm[-1].lower())
        if not depends:
            raise ValueError(
                f"CACHE MATERIALIZED {name}: defining query reads no "
                f"base table")
        from ..config import get as config_get
        incremental_ok = bool(config_get("cache.view.incremental", True)) \
            and len(set(base_names)) == 1
        spec = ss.analyze_plan(plan) if incremental_ok else None
        store = None
        table = None
        if spec is not None:
            try:
                store = ss.KeyedStateStore(spec.merge_kinds)
                partial = session._execute_query(spec.agg)
                store.merge_delta(partial)
                emit = store.to_table()
                table = session._execute_query(ss.substitute_node(
                    plan, spec.agg, _local_relation(emit)))
            except Exception:  # noqa: BLE001 — fall back to full mode
                spec, store, table = None, None, None
        if table is None:
            table = session._execute_query(plan)
        entry = TableEntry((name,), _schema_of(table), table, (),
                           "memory")
        view = MaterializedView(name, plan, entry, cm,
                                frozenset(depends),
                                base_names[0] if spec else None,
                                spec, store)
        with self._lock:
            self._views[name] = view
        # the entry goes straight into temp_views: register_temp_view
        # would set view_plan and reads would re-run the defining query
        cm.temp_views[name] = entry
        bump_table_version(memory_table_key(name))
        return view

    def drop(self, catalog_manager, name: str,
             if_exists: bool = False) -> bool:
        name = str(name).lower()
        with self._lock:
            view = self._views.pop(name, None)
        if view is None:
            if not if_exists:
                raise ValueError(f"materialized view not found: {name}")
            return False
        catalog_manager.temp_views.pop(name, None)
        bump_table_version(memory_table_key(name))
        return True

    def clear(self) -> None:
        with self._lock:
            views = list(self._views.values())
            self._views.clear()
        for v in views:
            v.catalog.temp_views.pop(v.name, None)

    # -- maintenance ---------------------------------------------------
    def dependents(self, key: str) -> List[MaterializedView]:
        with self._lock:
            return [v for v in self._views.values() if key in v.depends]

    def on_mutation(self, key: str, session, kind: str = "append",
                    delta: Optional[pa.Table] = None) -> None:
        """Fold one base-table change into every dependent view. Runs
        in the mutating thread BEFORE the DML statement returns, so a
        committed write is visible to view reads at the next marker."""
        for view in self.dependents(key):
            with self._lock:
                view.marker += 1
            mode = "full"
            table = None
            if view.spec is not None and kind == "append" \
                    and delta is not None:
                try:
                    table = self._fold_delta(session, view, delta)
                    mode = "incremental"
                except Exception:  # noqa: BLE001 — delta fold failed
                    table = None
            if table is None:
                table = self._recompute(session, view)
            view.entry.data = table
            view.entry.schema = _schema_of(table)
            bump_table_version(memory_table_key(view.name))
            _record_metric("execution.result_cache.view_refresh_count",
                           1, mode=mode)

    def _fold_delta(self, session, view, delta: pa.Table) -> pa.Table:
        from .. import streaming_state as ss
        agg = view.spec.agg
        below = _substitute_read(agg.input, view.base_name,
                                 _local_relation(delta))
        partial = session._execute_query(
            dataclasses.replace(agg, input=below))
        view.store.merge_delta(partial)
        emit = view.store.to_table()
        return session._execute_query(ss.substitute_node(
            view.plan, agg, _local_relation(emit)))

    def _recompute(self, session, view) -> pa.Table:
        from .. import streaming_state as ss
        table = session._execute_query(view.plan)
        if view.spec is not None:
            # rebuild the fold state so later appends can go back to
            # the incremental path
            try:
                store = ss.KeyedStateStore(view.spec.merge_kinds)
                store.merge_delta(session._execute_query(view.spec.agg))
                view.store = store
            except Exception:  # noqa: BLE001 — stay on full recompute
                view.spec, view.store = None, None
        return table


def _local_relation(table: pa.Table):
    from ..spec import plan as sp
    return sp.LocalRelation(table, _schema_of(table))


# ---------------------------------------------------------------------------
# process singletons + the session-facing write hook
# ---------------------------------------------------------------------------

RESULT_CACHE = ResultCache()
FRAGMENT_CACHE = FragmentCache()
VIEWS = MaterializedViewManager()


def result_cache_enabled(conf) -> bool:
    """Process default ``cache.result.enabled`` with the per-session
    ``spark.sail.cache.result.enabled`` mirror on top."""
    mirror = conf.get("spark.sail.cache.result.enabled") \
        if conf is not None else None
    if mirror is not None and str(mirror) != "":
        return str(mirror).strip().lower() in ("1", "true", "yes")
    from ..config import get as config_get
    return bool(config_get("cache.result.enabled", True))


def table_mutated(session, entry, kind: str = "append",
                  delta: Optional[pa.Table] = None) -> None:
    """Single entry point for every session-side write: bump the
    version (which also invalidates listings + cached entries), then
    fold the change into dependent materialized views."""
    key, root = entry_table_key(entry)
    bump_table_version(key, root=root)
    if VIEWS.is_view(entry.name[-1] if entry.name else None):
        return  # a direct write INTO a view: no self-maintenance
    if delta is not None:
        delta = _align_delta(entry, delta)
    VIEWS.on_mutation(key, session, kind=kind, delta=delta)


def _align_delta(entry, delta: pa.Table) -> Optional[pa.Table]:
    """Cast an appended slice to the base table's declared schema —
    INSERT literals keep their parsed types (a `7.0` is decimal) while
    the stored column may be double, and folding the raw slice through
    the view's aggregate would drift its output types. None (→ full
    recompute) when the slice cannot be aligned."""
    target = None
    if getattr(entry, "data", None) is not None:
        target = entry.data.schema
    elif getattr(entry, "schema", None) is not None:
        from ..columnar.arrow_interop import spec_type_to_arrow
        target = pa.schema([(f.name, spec_type_to_arrow(f.data_type))
                            for f in entry.schema.fields])
    if target is None:
        return delta
    try:
        return delta.select(target.names).cast(target)
    except Exception:  # noqa: BLE001 — shape mismatch: recompute instead
        return None


def clear_all() -> None:
    """CLEAR CACHE semantics for the reuse tiers (views stay registered
    — they are named objects dropped via UNCACHE MATERIALIZED)."""
    RESULT_CACHE.clear()
    FRAGMENT_CACHE.clear()
