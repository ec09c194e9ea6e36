"""Local (single-process) executor.

Reference role: LocalJobRunner + DataFusion's operator execution
(crates/sail-execution/src/job_runner.rs:47-66) — here the operators are
interpreted on the host while all bulk compute runs as jnp/XLA ops over
DeviceBatches. Batches use positional column names (c0, c1, …) internally;
plan-schema names are applied only at the Arrow boundary (duplicate output
names are legal in SQL).

Host↔device sync points (kept deliberately few):
- aggregate output shrink (live group count → smaller padded capacity)
- join build-duplicate check + expand-capacity computation
- scalar subquery evaluation
"""

from __future__ import annotations

import dataclasses
import datetime
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import profiler
from ..columnar import arrow_interop as ai
from ..metrics import record as _record_metric
from ..columnar.batch import (Column, DeviceBatch, HostBatch,
                              bucket_capacity, empty_batch,
                              physical_jnp_dtype)
from ..ops import aggregate as aggk
from ..ops import join as joink
from ..ops import sort as sortk
from ..plan import nodes as pn
from ..plan import rex as rx
from ..plan import stages as pst
from ..plan.compiler import Compiled, ExprCompiler, HostFallback
from ..spec import data_type as dt
from ..spec.literal import Literal as LV


class _NativeMiss(Exception):
    """Native fast-path declined; discards its telemetry span."""


class ExecutionError(RuntimeError):
    pass


def _generate_rows(kind: str, args: List, col_names: List[str]
                   ) -> List[tuple]:
    n_cols = len(col_names)
    if kind == "explode":
        c = args[0]
        if c is None:
            return []
        if isinstance(c, dict):
            return [(k, v) for k, v in c.items()]
        return [(x,) for x in c]
    if kind == "posexplode":
        c = args[0]
        if c is None:
            return []
        if isinstance(c, dict):
            return [(i, k, v) for i, (k, v) in enumerate(c.items())]
        return [(i, x) for i, x in enumerate(c)]
    if kind == "inline":
        c = args[0]
        if c is None:
            return []
        out = []
        for st in c:
            if st is None:
                out.append(tuple([None] * n_cols))
            elif all(n in st for n in col_names):
                # match struct fields by NAME (dict insertion order may
                # differ between elements)
                out.append(tuple(st[n] for n in col_names))
            else:
                vals = list(st.values())
                out.append(tuple(vals[:n_cols] +
                                 [None] * (n_cols - len(vals))))
        return out
    if kind == "json_tuple":
        import json as _json
        s = args[0]
        try:
            v = _json.loads(s) if s is not None else None
        except ValueError:
            v = None
        if not isinstance(v, dict):
            return [tuple([None] * n_cols)]
        row = []
        for key in args[1:]:
            x = v.get(key)
            if x is None:
                row.append(None)
            elif isinstance(x, (dict, list)):
                row.append(_json.dumps(x, separators=(",", ":")))
            elif isinstance(x, bool):
                row.append("true" if x else "false")
            else:
                row.append(str(x))
        return [tuple(row)]
    if kind == "stack":
        n_rows = int(args[0])
        vals = args[1:]
        per = -(-len(vals) // n_rows) if n_rows else 0
        out = []
        for r in range(n_rows):
            row = vals[r * per:(r + 1) * per]
            out.append(tuple(list(row) + [None] * (per - len(row))))
        return out
    raise ExecutionError(f"unknown generator {kind!r}")


def _replace_node(plan: pn.PlanNode, target: pn.PlanNode,
                  replacement: pn.PlanNode) -> pn.PlanNode:
    if plan is target:
        return replacement
    if isinstance(plan, pn.JoinExec):
        return dataclasses.replace(
            plan, left=_replace_node(plan.left, target, replacement),
            right=_replace_node(plan.right, target, replacement))
    if isinstance(plan, pn.UnionExec):
        return dataclasses.replace(plan, inputs=tuple(
            _replace_node(c, target, replacement) for c in plan.inputs))
    if hasattr(plan, "input") and plan.input is not None:
        return dataclasses.replace(
            plan, input=_replace_node(plan.input, target, replacement))
    return plan


def _empty_arrow(schema) -> "pa.Table":
    return pa.Table.from_arrays(
        [pa.array([], type=ai.spec_type_to_arrow(f.dtype)) for f in schema],
        names=[f.name for f in schema])


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple((k, _hashable(x)) for k, x in v.items())
    return v


def _sort_key(v):
    """Total-order sort key for nested values: nulls first at every
    nesting level (Spark ordering), arrays/structs lexicographic."""
    if v is None:
        return (0,)
    if isinstance(v, (list, tuple)):
        return (1, tuple(_sort_key(x) for x in v))
    if isinstance(v, dict):
        return (1, tuple((k, _sort_key(x)) for k, x in v.items()))
    return (1, v)


def _dict_order_ranks(dictionary: pa.Array) -> np.ndarray:
    """Order-preserving rank per dictionary code. Arrow sort covers
    string/binary dictionaries; array/struct dictionaries (which Arrow
    cannot sort) fall back to a host lexicographic sort."""
    try:
        return ai.dictionary_ranks(dictionary)
    except (pa.ArrowNotImplementedError, pa.ArrowInvalid):
        vals = dictionary.to_pylist()
        order = sorted(range(len(vals)), key=lambda i: _sort_key(vals[i]))
        ranks = np.empty(len(vals), dtype=np.int32)
        ranks[order] = np.arange(len(vals), dtype=np.int32)
        return ranks


def _norm_intervals(vals):
    """Host aggregates see intervals as plain numbers: YM → int months,
    DT → int microseconds (recursing into struct-packed arg rows)."""
    import datetime as _dtm

    def norm(v):
        if v is None:
            return None
        if type(v).__name__ == "MonthDayNano":
            return int(v[0])
        if isinstance(v, _dtm.timedelta):
            return round(v.total_seconds() * 1e6)
        if isinstance(v, dict):
            return {k: norm(x) for k, x in v.items()}
        if isinstance(v, list):
            return [norm(x) for x in v]
        return v

    return [norm(v) for v in vals]


def _intervalize(v, d):
    """Numbers back to interval values per the declared output type."""
    import datetime as _dtm

    if v is None:
        return None
    if isinstance(d, dt.YearMonthIntervalType):
        return (int(round(float(v))), 0, 0)
    if isinstance(d, dt.DayTimeIntervalType):
        if isinstance(v, _dtm.timedelta):
            return v
        return _dtm.timedelta(microseconds=round(float(v)))
    if isinstance(d, dt.ArrayType) and isinstance(v, list):
        return [_intervalize(x, d.element_type) for x in v]
    return v


def _host_agg_one(spec, cols, rows_idx, host_aggs):
    """One aggregate over one group's row indices (host path)."""
    fn = spec.fn
    vals = None if spec.arg is None else [cols[spec.arg][i]
                                          for i in rows_idx]
    if fn.startswith("__host__"):
        name = fn[len("__host__"):]
        ha = host_aggs[name]
        assert vals is not None
        if name.startswith("__udaf_"):
            # wire UDAFs see the FULL group including nulls (PySpark hands
            # the grouped-agg pandas UDF the whole Series, NaN for NULL)
            if vals and isinstance(vals[0], dict):
                rows = [tuple(v.values()) if v is not None else None
                        for v in vals]
            else:
                rows = list(vals)
            return ha.impl(rows)
        if vals and isinstance(vals[0], dict):
            tuples = [tuple(v.values()) if v is not None else None
                      for v in vals]
            # per-function null eligibility: max_by/min_by drop rows with a
            # null ORDERING key (the value may be null); value-first
            # aggregates drop rows with a null value; statistical pairs
            # drop rows with any null
            if name in ("max_by", "min_by"):
                rows = [t for t in tuples
                        if t is not None and t[1] is not None]
            elif name in ("listagg", "string_agg", "percentile",
                          "percentile_approx", "approx_percentile",
                          "percentile_cont", "percentile_disc",
                          "histogram_numeric", "__listagg_ordered",
                          "__mode_ordered", "mode", "approx_top_k",
                          "kll_sketch_agg_bigint", "kll_sketch_agg_double",
                          "kll_sketch_agg_float", "hll_sketch_agg",
                          "theta_sketch_agg", "count_min_sketch"):
                rows = [t for t in tuples
                        if t is not None and t[0] is not None]
            else:
                rows = [t for t in tuples
                        if t is not None and all(x is not None for x in t)]
        else:
            rows = [v for v in vals if v is not None]
        if spec.distinct:
            seen = []
            rows = [r for r in rows
                    if not (r in seen or seen.append(r))]
        return ha.impl(rows)
    nn = None if vals is None else [v for v in vals if v is not None]
    if spec.distinct and nn:
        # dedup on the hashable key but keep the ORIGINAL values, so
        # min/max/first over array/struct columns return lists/dicts
        seen: dict = {}
        for v in nn:
            seen.setdefault(_hashable(v), v)
        nn = list(seen.values())
    if fn == "count":
        return len(rows_idx) if vals is None else len(nn)
    if fn == "sum":
        return sum(nn) if nn else None
    if fn == "min":
        # compare via the sort key so array/struct values (incl. nested
        # nulls) order per Spark but the ORIGINAL value returns
        return min(nn, key=_sort_key) if nn else None
    if fn == "max":
        return max(nn, key=_sort_key) if nn else None
    if fn == "first":
        pool = nn if spec.ignore_nulls else vals
        return pool[0] if pool else None
    if fn == "last":
        pool = nn if spec.ignore_nulls else vals
        return pool[-1] if pool else None
    if fn == "bool_and":
        return all(nn) if nn else None
    if fn == "bool_or":
        return any(nn) if nn else None
    raise ExecutionError(f"aggregate {fn!r} has no host path")


def _fit_capacity(data, validity, cap: int):
    """Broadcast constant (scalar / 1-element) expression results to the
    batch capacity, so literal projections over OneRow line up with the
    selection mask (UNIONs of FROM-less SELECTs concatenate per-column)."""
    if data.ndim == 0:
        data = jnp.broadcast_to(data[None], (cap,))
    elif data.shape[0] != cap and data.shape[0] == 1:
        data = jnp.broadcast_to(data, (cap,))
    if validity is not None:
        if validity.ndim == 0:
            validity = jnp.broadcast_to(validity[None], (cap,))
        elif validity.shape[0] != cap and validity.shape[0] == 1:
            validity = jnp.broadcast_to(validity, (cap,))
    return data, validity


def _col_name(i: int) -> str:
    return f"c{i}"


class _OpCache:
    """Compiled-operator cache.

    Keyed by (plan-node structural key, input-dictionary identity). The
    bind-time closures bake host lookup tables derived from dictionaries, so
    a cached entry is valid exactly while the same dictionary objects flow
    in — the entry holds strong references and verifies identity on hit.
    Combined with the scan cache (stable dictionaries per table) and the
    interning of small dictionaries by content (``arrow_interop.
    DICTIONARIES``: one object for every streamed chunk of a column),
    repeated queries of the same shape skip both tracing and XLA
    compilation.
    """

    def __init__(self, max_entries: Optional[int] = None):
        from collections import OrderedDict
        self.entries = OrderedDict()
        self._max_entries = max_entries

    @property
    def max_entries(self) -> int:
        # resolved lazily so the config layer is ready by first use
        if self._max_entries is None:
            self._max_entries = _runtime_cache_size(
                "runtime.op_cache_size", 512)
        return self._max_entries

    def get(self, key, dict_objs: Tuple, builder):
        ident = tuple(id(d) for d in dict_objs)
        hit = self.entries.get((key, ident))
        if hit is not None:
            stored, value = hit
            if all(s is d for s, d in zip(stored, dict_objs)):
                self.entries.move_to_end((key, ident))
                return value
        value = builder()
        while len(self.entries) >= self.max_entries:
            evicted_key, _ = self.entries.popitem(last=False)  # LRU
            try:
                # the dropped program's next compile is an eviction
                # retrace — the ledger keeps its signature history
                from . import retrace
                retrace.LEDGER.note_eviction(evicted_key[0])
            except Exception:  # noqa: BLE001 — forensics never break exec
                pass
        self.entries[(key, ident)] = (tuple(dict_objs), value)
        return value


def _compile_timed(fn, key, fused=False):
    """Wrap a jitted fn so every call that actually traces and XLA-
    compiles (jax.jit itself is lazy) is timed, charged to the active
    query, and attributed to a typed retrace cause (exec/retrace.py).

    Detection: jax's jitted callables expose ``_cache_size()`` — the
    number of compiled signatures resident in the jit cache. A call
    after which it GREW compiled; anything else ran a bound executable.
    That sees every retrace (new aval signature, capacity-bucket
    churn), not the first call only. ``fused`` marks whole-stage programs:
    their compile time additionally rides
    ``execution.fusion.compile_time``.

    Every call is a ``dispatch`` span (attribute ``program``, the name
    the XLA module runs under: ``pcache.program_name(key)``); a call
    that compiled holds a ``compile`` span (``source`` ``trace``,
    ``cause`` as ``retrace.attribute`` typed it) covering it."""
    import time as _time

    from .. import tracing as tr
    from . import pcache, retrace

    cache_size = fn._cache_size
    name = pcache.program_name(key)

    def _charge(elapsed_s: float, args) -> None:
        key_repr = repr(key[0]) if isinstance(key, tuple) and key \
            else repr(key)
        if fused:
            try:
                from ..metrics import record as _record_metric
                _record_metric("execution.fusion.compile_time",
                               elapsed_s)
            except Exception:  # noqa: BLE001 — timing must never raise
                pass
        # known to have compiled only now that the call is over: the
        # span starts where the call did
        with tr.span("compile", {"program": name, "source": "trace"},
                     backdate_ns=int(elapsed_s * 1e9)) as sp:
            profiler.note_compile_time(elapsed_s, key=key_repr)
            sp.attributes["cause"] = retrace.attribute(
                key, pcache.signature(args), elapsed_s, site="memory")

    def wrapper(*args, **kwargs):
        with tr.span("dispatch", {"program": name}):
            n0 = cache_size()
            t0 = _time.perf_counter()
            out = fn(*args, **kwargs)
            if cache_size() > n0:
                _charge(_time.perf_counter() - t0, args)
            return out

    return wrapper


def _runtime_cache_size(key: str, default: int) -> int:
    """Process-wide cache bound from config, read once per key (these
    sit on hot paths; app-config flattening must not ride every hit)."""
    size = _RUNTIME_CACHE_SIZES.get(key)
    if size is None:
        try:
            from ..config import get as config_get
            size = max(1, int(config_get(key, default)))
        except (TypeError, ValueError, ImportError):
            size = default
        _RUNTIME_CACHE_SIZES[key] = size
    return size


_RUNTIME_CACHE_SIZES: Dict[str, int] = {}
_OP_CACHE = _OpCache()
# runtime join filters: join-structure key → last observed scan-site prune
# ratio; joins whose filters proved useless skip the build on later
# executions (adaptive)
_RTF_HISTORY: Dict = {}


class _RtfConf(NamedTuple):
    """spark.sail.join.runtimeFilter.* resolved for one executor."""

    enabled: bool
    min_build_rows: int
    in_list_max: int
    ndv_ratio: float
    min_selectivity: float


class _Rtf(NamedTuple):
    """A runtime filter that was built and pushed into the other side's
    scans: what ``_rtf_finish`` needs for the adaptive verdict."""

    fids: Tuple[int, ...]      # annotated filter ids (scan stat lookup)
    history_key: object        # adaptive-skip key (None if unhashable)
    pushed: int                # scan targets that received conjuncts


def clear_caches():
    from . import capacity, result_cache, retrace
    _OP_CACHE.entries.clear()
    ai.DICTIONARIES.clear()
    _RTF_HISTORY.clear()
    _RUNTIME_CACHE_SIZES.clear()
    result_cache.clear_all()
    retrace.clear()
    capacity.reload()


class LocalExecutor:
    def __init__(self, config: Optional[dict] = None):
        self.config = config or {}
        self._subquery_cache: Dict[int, LV] = {}
        # runtime join filters: per-fid (rows_before, rows_after) scan
        # pruning observed while executing this plan (adaptive feedback)
        self._rtf_scan_stats: Dict[int, Tuple[int, int]] = {}
        # whole-stage fusion gate, resolved once per executor
        self._fusion: Optional[bool] = None
        # concurrent-scan sharing (enabled, wait_timeout_s), resolved
        # once per executor (io/prefetch.scan_share_conf)
        self._scan_share_conf: Optional[Tuple[bool, float]] = None
        # per-stage backend routing decisions of the current plan
        # (exec/router.py): stage sid -> Decision, plus the node->sid
        # map the decisions were made under
        self._backend_routes: Dict = {}
        self._route_stage_of: Dict = {}

    def _fusion_on(self) -> bool:
        """``spark.sail.execution.fusion.enabled`` (session conf) over
        ``execution.fusion.enabled`` (app config), default on. Off
        restores pre-fusion per-operator execution for A/B and
        bisection."""
        if self._fusion is None:
            from ..plan.stages import fusion_enabled
            self._fusion = fusion_enabled(
                self.config.get("spark.sail.execution.fusion.enabled"))
        return self._fusion

    def _note_stage_split(self, plan: pn.PlanNode) -> None:
        """Stage-split accounting + the fused-stage invariant walk (the
        splitter's output drives this query's fusion decisions, so a bad
        split must surface here, not as a wrong answer)."""
        from ..analysis.invariants import (VALIDATE_OFF,
                                           validate_stage_split,
                                           validation_mode)
        from ..plan import stages as pst

        split = pst.split_stages(plan)
        _record_metric("execution.fusion.stage_count", len(split.stages))
        fused_ops = split.fused_op_count
        if fused_ops:
            _record_metric("execution.fusion.fused_op_count", fused_ops)
        profiler.note_fusion(stages=len(split.stages),
                             fused_ops=fused_ops)
        # per-stage backend routing, decided HERE — at stage-split time
        # — so execution consults a recorded decision instead of making
        # an implicit one per operator (exec/router.py)
        from . import router
        decisions = router.decide_split(
            split, force=router.forced_backend(self.config),
            slo_ctx=router.slo_context(self.config))
        self._backend_routes = {d.stage: d for d in decisions}
        self._route_stage_of = split.stage_of
        router.record_decisions(decisions)
        mode = validation_mode(
            self.config.get("spark.sail.analysis.validatePlans"))
        if mode != VALIDATE_OFF:
            validate_stage_split(plan, split)
            profiler.note_plan_validated()

    def _note_fusion_fallback(self, site: str) -> None:
        """One pipeline declined whole-stage fusion at execution time
        (host-only expressions etc.) and ran per-op instead."""
        _record_metric("execution.fusion.fallback_count", 1, site=site)
        profiler.note_fusion(fallbacks=1)

    # ------------------------------------------------------------------
    def execute(self, plan: pn.PlanNode) -> pa.Table:
        """Run a plan to an Arrow table with the plan's output names."""
        import contextlib

        # a nested executor (scalar subquery, command sub-plan) runs
        # entirely inside the outer "execute" timer — recording its
        # fetch separately would overlap the phases
        prof = profiler.current_profile()
        nested = prof is not None and prof.is_open("execute")
        with profiler.maybe_phase("execute"):
            self._pre_eval_subqueries(plan)
            if self._fusion_on():
                self._note_stage_split(plan)
            batch = self.run(plan)
        with contextlib.nullcontext() if nested \
                else profiler.maybe_phase("fetch"):
            table = ai.to_arrow(batch)
            names = [f.name for f in plan.schema]
            return table.rename_columns(names)

    def run(self, plan: pn.PlanNode) -> HostBatch:
        method = getattr(self, "_exec_" + type(plan).__name__, None)
        if method is None:
            raise ExecutionError(f"no executor for {type(plan).__name__}")
        from .. import telemetry as tel
        detail = ""
        if isinstance(plan, pn.ScanExec):
            detail = plan.table_name or ",".join(plan.paths)
        # the one wrapper: an op.<PlanNode> span always, and under
        # EXPLAIN ANALYZE (m is not None) the operator's metrics
        with tel.operator_span(type(plan).__name__, detail) as m:
            out = method(plan)
            if m is not None:
                # rows/capacity force a device sync — only under
                # EXPLAIN ANALYZE
                m.output_rows = int(out.device.num_rows())
                m.capacity = out.capacity
            return out

    # ------------------------------------------------------------------
    # scalar subqueries
    # ------------------------------------------------------------------
    def _pre_eval_subqueries(self, plan: pn.PlanNode):
        for node in pn.walk_plan(plan):
            for r in _node_rex(node):
                for sub in rx.walk(r):
                    if isinstance(sub, rx.RScalarSubquery) and \
                            id(sub) not in self._subquery_cache:
                        self._subquery_cache[id(sub)] = self._eval_scalar(sub)

    def _eval_scalar(self, sub: rx.RScalarSubquery) -> LV:
        inner = LocalExecutor(self.config)
        inner._subquery_cache = self._subquery_cache
        table = inner.execute(sub.plan)
        if table.num_rows == 0:
            return LV(sub.dtype, None)
        if table.num_rows > 1:
            raise ExecutionError("scalar subquery returned more than one row")
        v = table.column(0)[0].as_py()
        return LV(sub.dtype, v)

    # ------------------------------------------------------------------
    # expression plumbing
    # ------------------------------------------------------------------
    def _compiler(self, batch: HostBatch, schema: pn.Schema) -> ExprCompiler:
        types = [f.dtype for f in schema]
        dicts = {}
        for i in range(len(schema)):
            name = _col_name(i)
            if name in batch.dicts:
                dicts[i] = batch.dicts[name]
        return ExprCompiler(types, dicts, self._subquery_cache)

    @staticmethod
    def _cols(batch: HostBatch) -> List:
        dev = batch.device
        return [(dev.columns[_col_name(i)].data, dev.columns[_col_name(i)].validity)
                for i in range(len(dev.columns))]

    def _eval(self, compiled: Compiled, batch: HostBatch):
        return compiled.fn(self._cols(batch))

    def _dict_objs(self, batch: HostBatch) -> Tuple:
        return tuple(batch.dicts[k] for k in sorted(batch.dicts))

    def _op_key(self, *parts):
        """Structural cache key, or None when unhashable (e.g. embedded
        scalar-subquery plans holding memory tables).

        Scalar-subquery values are baked into compiled closures, so the key
        appends each referenced subquery's value in rex-walk order (stable
        across executions of structurally-equal plans)."""
        sub_vals = []
        for part in parts:
            for r in _walk_part_rex(part):
                for node in rx.walk(r):
                    if isinstance(node, rx.RScalarSubquery):
                        v = self._subquery_cache.get(id(node))
                        sub_vals.append(repr(None if v is None else v.value))
        key = parts + (tuple(sub_vals),)
        try:
            hash(key)
            return key
        except TypeError:
            return None

    def _jitted(self, key, dict_objs: Tuple, builder, fused=False):
        """Returns (fn, aux) where fn is jit-compiled and cached when the
        key is hashable, else built fresh and run eagerly.

        Compile accounting: every call is a compile-cache hit or miss
        (``execution.compile.{cache_hit_count,cache_miss_count}`` and the
        active query profile); a miss additionally times the jitted
        program's FIRST invocation — where jax traces and XLA compiles —
        as ``execution.compile.compile_time`` (and, for whole-stage
        fused programs, ``execution.fusion.compile_time``).

        The program is ``jax.jit`` of the builder's function under its
        stable name (``pcache.program_name``): across processes it is
        JAX's persistent compilation cache that answers the compile
        (``pcache.place_jax_cache``), keyed by that module."""
        import jax

        if key is None:
            # unhashable plan key: uncached eager build — still a miss
            profiler.note_compile_cache(hit=False)
            fn, aux = builder()
            return fn, aux

        def build():
            from . import pcache
            missed.append(True)
            fn, aux = builder()
            # the XLA module, the dispatch span and the device trace's
            # operations all carry this name (pcache.program_name)
            fn = pcache.named(fn, pcache.program_name(key))
            return _compile_timed(jax.jit(fn), key, fused=fused), aux

        missed: list = []
        value = _OP_CACHE.get(key, dict_objs, build)
        profiler.note_compile_cache(hit=not missed)
        return value

    # ------------------------------------------------------------------
    # leaves
    # ------------------------------------------------------------------
    def _exec_ScanExec(self, p: pn.ScanExec) -> HostBatch:
        from ..io.formats import expand_paths
        import os
        if p.format == "python_ds":
            # user data source: read at EXECUTION, never cached — the
            # engine can't know the external source is stable
            from ..io.python_datasource import materialize
            from ..spec import data_type as dt_
            ds_cls, opts = p.source
            st = dt_.StructType(tuple(
                dt_.StructField(f.name, f.dtype, f.nullable)
                for f in p.out_schema))
            table, _ = materialize(ds_cls, dict(opts), st)
            if p.projection is not None:
                table = table.select(list(p.projection))
            return _positional(ai.from_arrow(table))
        from . import result_cache as rc
        rtf_preds = p.runtime_predicates
        if p.source is not None:
            cache_key = ("mem", id(p.source), p.projection, rtf_preds)
            table_key = rc.memory_table_key(p.table_name) \
                if p.table_name else None
        elif p.format == "delta":
            from ..lakehouse.delta import DeltaLog
            files = p.paths
            mtimes = (DeltaLog(p.paths[0]).latest_version(),
                      tuple(sorted(dict(p.options).items())))
            cache_key = ("delta", files, mtimes, p.projection,
                         tuple((f.name, f.dtype) for f in p.schema))
            table_key = p.paths[0] if p.paths else None
        else:
            try:
                files = tuple(expand_paths(p.paths))
                mtimes = tuple(int(os.path.getmtime(f) * 1e6) for f in files)
            except OSError:
                files, mtimes = p.paths, ()
            cache_key = ("file", files, mtimes, p.projection, p.predicates,
                         rtf_preds,
                         tuple(sorted(dict(p.options).items())),
                         tuple((f.name, f.dtype) for f in p.schema))
            table_key = p.paths[0] if p.paths else None
        hit = rc.FRAGMENT_CACHE.get(cache_key, p.source)
        if hit is not None:
            _note_scan(p, hit.batch, hit.rows, "hit")
            self._note_rtf_scan(p, hit.rtf_stats)
            profiler.note_result_cache(fragment=hit.fragment_id,
                                       nbytes=hit.nbytes)
            return hit.batch
        # concurrent-scan sharing: a fragment miss races other queries
        # admitted in the same window — one leader decodes, followers
        # attach to the in-flight load instead of running N identical
        # scans (followers fall back to a local decode on timeout)
        leader, flight = False, None
        share_enabled, share_timeout = self._scan_share()
        if share_enabled:
            from ..io.prefetch import SCAN_LOADS
            leader, flight = SCAN_LOADS.begin(cache_key)
            if not leader:
                _record_metric("execution.scan_share.attached_count", 1)
                try:
                    ok, entry = flight.wait(share_timeout)
                finally:
                    SCAN_LOADS.detach(flight)
                if ok and entry is not None and \
                        (p.source is None or entry.source is p.source):
                    _record_metric(
                        "execution.scan_share.decode_passes_saved", 1)
                    _note_scan(p, entry.batch, entry.rows, "shared")
                    self._note_rtf_scan(p, entry.rtf_stats)
                    profiler.note_result_cache(
                        status="shared-scan", fragment=entry.fragment_id,
                        nbytes=entry.nbytes, attached=1, saved=1)
                    return entry.batch
                flight = None
        try:
            hb = self._decode_scan(p, cache_key, table_key, files
                                   if p.source is None else None,
                                   flight if leader else None)
            return hb
        finally:
            if leader and flight is not None:
                from ..io.prefetch import SCAN_LOADS
                SCAN_LOADS.finish(cache_key, flight)

    def _decode_scan(self, p: pn.ScanExec, cache_key, table_key,
                     files, flight) -> HostBatch:
        """The actual decode/upload pass (fragment-cache fill). When a
        ScanFlight is handed in, publishes the stored fragment to
        attached followers — or the failure, which propagates."""
        from . import result_cache as rc
        import time as _time
        t0 = _time.perf_counter()
        try:
            hb, table, rtf_stats = self._decode_scan_table(p, files)
        except BaseException as exc:
            if flight is not None:
                flight.fail(exc)
            raise
        _note_scan(p, hb, table.num_rows, "decoded")
        self._note_rtf_scan(p, rtf_stats)
        try:
            nbytes = int(table.nbytes)
        except Exception:  # noqa: BLE001 — size is advisory
            nbytes = 0
        entry = rc.FRAGMENT_CACHE.put(
            cache_key, p.source, hb, rtf_stats, table_key=table_key,
            nbytes=nbytes, rows=table.num_rows,
            decode_ms=(_time.perf_counter() - t0) * 1000.0)
        # observed-exact cardinality: the cached fragment is a grounded
        # input for AQE/join ordering on every later substitution
        from ..plan import join_reorder
        join_reorder.note_observed_rows(p, table.num_rows)
        if flight is not None:
            flight.publish(entry)
        return hb

    def _scan_share(self) -> Tuple[bool, float]:
        if self._scan_share_conf is None:
            from ..io.prefetch import scan_share_conf
            self._scan_share_conf = scan_share_conf(self.config)
        return self._scan_share_conf

    def _decode_scan_table(self, p: pn.ScanExec, files):
        from ..io.formats import read_table
        rtf_preds = p.runtime_predicates
        rtf_stats = None
        if p.source is not None:
            table = p.source
            if p.projection is not None:
                table = table.select(list(p.projection))
            if rtf_preds:
                # runtime join-filter conjuncts: prune probe rows HOST-side
                # before upload, so every downstream kernel runs at the
                # pruned (bucketed) capacity
                table, rtf_stats = _apply_runtime_predicates(
                    table, rtf_preds, p.schema)
        else:
            filter_expr = None
            preds = p.predicates
            if p.format == "parquet" and (preds or rtf_preds):
                from ..io.formats import rex_predicates_to_arrow, \
                    row_group_pruning_enabled
                if not row_group_pruning_enabled():
                    preds = rtf_preds = ()
                if rtf_preds:
                    # runtime filter conjuncts join the static predicates
                    # for parquet row-group/page skipping; fall back to
                    # static-only if the combination fails to convert
                    filter_expr = rex_predicates_to_arrow(
                        preds + rtf_preds, p.schema)
                if filter_expr is None and preds:
                    filter_expr = rex_predicates_to_arrow(preds, p.schema)
            table = read_table(p.format, p.paths, dict(p.options),
                               columns=p.projection,
                               filter_expr=filter_expr)
            table = self._apply_declared_schema(table, p.schema)
            if rtf_preds and filter_expr is not None and not p.predicates:
                # adaptive evidence for parquet pruning: with no static
                # predicates in the filter, footer row counts give the
                # exact pre-filter cardinality for free
                try:
                    from ..io.cache import METADATA_CACHE
                    before = sum(METADATA_CACHE.num_rows(f)
                                 for f in files)
                    rtf_stats = (int(before), table.num_rows)
                except Exception:  # noqa: BLE001 — stats are advisory
                    rtf_stats = None
        hb = _positional(ai.from_arrow(table, bucket_key=_scan_cap_key(p)))
        return hb, table, rtf_stats

    def _note_rtf_scan(self, p: pn.ScanExec, stats) -> None:
        """Record one scan's runtime-filter pruning (executor-local for
        the join's adaptive feedback, registry + profiler for
        observability). Cache hits replay the cached stats: the pruning
        is baked into the cached batch and still shapes this query."""
        if not p.runtime_filters or stats is None:
            return
        before, after = stats
        for t in p.runtime_filters:
            self._rtf_scan_stats[t.fid] = (before, after)
        pruned = before - after
        if pruned <= 0:
            return
        from .. import telemetry as tel
        _record_metric("execution.runtime_filter.rows_pruned", pruned,
                       site="scan")
        profiler.note_runtime_filter(rows_pruned=pruned)
        if tel.current_collector() is not None:
            tel.note("RuntimeFilter",
                     f"scan {p.table_name or p.format}",
                     rows_pruned=pruned, rows_in=before)

    @staticmethod
    def _apply_declared_schema(table: pa.Table, schema: pn.Schema) -> pa.Table:
        """Reorder/cast file data to the plan's declared schema (a user-set
        read schema may differ from the file's natural order and types)."""
        arrays = []
        names = []
        for f in schema:
            at = ai.spec_type_to_arrow(f.dtype)
            if f.name in table.column_names:
                col = table.column(f.name)
                if col.type != at:
                    col = col.cast(at, safe=False)
            else:
                col = pa.nulls(table.num_rows, type=at)
            arrays.append(col)
            names.append(f.name)
        return pa.table(dict(zip(names, arrays)))

    def _exec_OneRowExec(self, p: pn.OneRowExec) -> HostBatch:
        sel = np.zeros(8, dtype=bool)
        sel[0] = True
        return HostBatch(DeviceBatch({}, jnp.asarray(sel)), {})

    def _exec_ValuesExec(self, p: pn.ValuesExec) -> HostBatch:
        arrays = []
        for j, f in enumerate(p.out_schema):
            vals = [row[j] for row in p.rows]
            at = ai.spec_type_to_arrow(f.dtype)
            if isinstance(f.dtype, dt.YearMonthIntervalType):
                arrays.append(pa.array(
                    [None if v.value is None else (int(v.value), 0, 0)
                     for v in vals], type=at))
                continue
            arrays.append(pa.array([v.value for v in vals], type=at))
        table = pa.table(dict(zip([_col_name(j) for j in range(len(arrays))], arrays)))
        return ai.from_arrow(table)

    def _exec_RangeExec(self, p: pn.RangeExec) -> HostBatch:
        n = max(0, -(-(p.end - p.start) // p.step)) if p.step else 0
        vals = np.arange(p.start, p.end, p.step, dtype=np.int64)
        table = pa.table({"c0": pa.array(vals, type=pa.int64())})
        return ai.from_arrow(table)

    # ------------------------------------------------------------------
    # unary operators
    # ------------------------------------------------------------------
    def _exec_ProjectExec(self, p: pn.ProjectExec) -> HostBatch:
        if self._fusion_on():
            out = self._try_fused_chain(p)
            if out is not None:
                return out
        return self._project_over(p, self.run(p.input))

    def _project_over(self, p: pn.ProjectExec, child: HostBatch
                      ) -> HostBatch:
        dev = child.device
        if not p.exprs:  # SELECT of zero columns
            return HostBatch(DeviceBatch({}, dev.sel), {})

        def builder():
            comp = self._compiler(child, p.input.schema)
            compiled = [comp.compile(e) for _, e in p.exprs]
            types = [rx.rex_type(e) for _, e in p.exprs]
            jdts = [physical_jnp_dtype(t) for t in types]

            def fn(cols):
                out = []
                for c, jdt in zip(compiled, jdts):
                    data, validity = c.fn(cols)
                    if data.dtype != jnp.dtype(jdt):
                        data = data.astype(jdt)
                    out.append((data, validity))
                return tuple(out)

            dicts = {_col_name(i): c.dictionary
                     for i, c in enumerate(compiled) if c.dictionary is not None}
            return fn, dicts

        key = self._op_key("project", p.exprs,
                           tuple((f.name, f.dtype) for f in p.input.schema))
        try:
            fn, out_dicts = self._jitted(key, self._dict_objs(child), builder)
        except HostFallback:
            return self._project_host_path(p, child)
        results = fn(self._cols(child))
        cap = dev.sel.shape[0]
        out_cols = {}
        for i, ((d, v), (_, e)) in enumerate(zip(results, p.exprs)):
            d, v = _fit_capacity(d, v, cap)
            out_cols[_col_name(i)] = Column(d, v, rx.rex_type(e))
        return HostBatch(DeviceBatch(out_cols, dev.sel), out_dicts)

    def _project_host_path(self, p: pn.ProjectExec, child: HostBatch) -> HostBatch:
        """Per-expression evaluation with host fallback for expressions the
        device compiler can't lower (string-returning Python UDFs, …)."""
        comp = self._compiler(child, p.input.schema)
        dev = child.device
        out_cols: Dict[str, Column] = {}
        out_dicts: Dict[str, pa.Array] = {}
        for i, (name, e) in enumerate(p.exprs):
            keyn = _col_name(i)
            try:
                c = comp.compile(e)
                data, validity = self._eval(c, child)
                data, validity = _fit_capacity(data, validity,
                                               dev.sel.shape[0])
                if c.dictionary is not None:
                    out_dicts[keyn] = c.dictionary
            except HostFallback:
                data, validity, dictionary = self._host_eval(e, comp, child)
                if dictionary is not None:
                    out_dicts[keyn] = dictionary
            odt = rx.rex_type(e)
            if not isinstance(odt, (dt.ArrayType, dt.MapType,
                                    dt.StructType, dt.NullType)):
                jdt = physical_jnp_dtype(odt)
                if data.dtype != jnp.dtype(jdt):
                    data = data.astype(jdt)
            out_cols[keyn] = Column(data, validity, odt)
        return HostBatch(DeviceBatch(out_cols, dev.sel), out_dicts)

    def _host_eval(self, e: rx.Rex, comp: ExprCompiler, child: HostBatch):
        """Host evaluation of a __pyudf call (incl. string returns): args
        evaluate on device, rows run through the Python function, string
        results dictionary-encode."""
        if isinstance(e, rx.RCast) and isinstance(e.dtype, dt.StringType) \
                and not isinstance(rx.rex_type(e.child),
                                   (dt.ArrayType, dt.MapType, dt.StructType)):
            try:
                return self._host_cast_to_string(e, comp, child)
            except HostFallback:
                pass
        if not (isinstance(e, rx.RCall) and e.fn == "__pyudf"):
            # general host interpreter (arrays/maps/structs/json/lambdas/…)
            from .host_interp import HostInterpreter, encode_host_column
            interp = HostInterpreter(self, comp, child)
            values = interp.values(e)
            return encode_host_column(values, rx.rex_type(e),
                                      child.device.capacity)
        from ..plan.compiler import (udf_arg_decoder, udf_decode_column,
                                     udf_encode_numeric, udf_invoke)
        u = dict(e.options)["udf"]
        n = child.capacity
        cols_py = []
        for a in e.args:
            ac = comp.compile(a)
            data, validity = self._eval(ac, child)
            dec = udf_arg_decoder(rx.rex_type(a), ac.dictionary)
            cols_py.append(udf_decode_column(
                dec, np.asarray(data),
                None if validity is None else np.asarray(validity)))
        res = udf_invoke(u, cols_py, n)
        out_t = u.return_type
        if isinstance(out_t, (dt.StringType, dt.BinaryType)):
            def _null_like(v):
                if v is None:
                    return True
                try:
                    return bool(v != v)  # NaN
                except (TypeError, ValueError):
                    return True  # pd.NA: truth value is ambiguous → NULL
            arr = pa.array([None if _null_like(v) else str(v)
                            for v in res], type=pa.string())
            enc = arr.dictionary_encode()
            codes = np.asarray(enc.indices.fill_null(0)).astype(np.int32)
            import pyarrow.compute as _pc
            validity = jnp.asarray(np.asarray(_pc.is_valid(arr)))
            return jnp.asarray(codes), validity, enc.dictionary
        jdt = physical_jnp_dtype(out_t)
        out, mask = udf_encode_numeric(res, n, np.dtype(jdt))
        return jnp.asarray(out), jnp.asarray(mask), None

    def _host_cast_to_string(self, e: rx.RCast, comp: ExprCompiler,
                             child: HostBatch):
        """CAST(x AS STRING) for non-dictionary columns: evaluate the child
        on device, format values on host with Spark's text forms, and
        dictionary-encode the result."""
        import datetime as _dtm
        import decimal as _dec

        ac = comp.compile(e.child)
        data, validity = self._eval(ac, child)
        src_t = rx.rex_type(e.child)
        arr = ai.column_values_to_arrow(np.asarray(data),
                                        None if validity is None
                                        else np.asarray(validity),
                                        src_t, ac.dictionary)

        def fmt(v):
            if v is None:
                return None
            if isinstance(v, bool):
                return "true" if v else "false"
            if isinstance(v, float):
                from ..utils.format import format_double
                return format_double(v)
            if isinstance(v, _dtm.datetime):
                if v.tzinfo is not None:
                    from ..utils.tz import session_zone
                    v = v.astimezone(session_zone())
                s = v.strftime("%Y-%m-%d %H:%M:%S")
                if v.microsecond:
                    s += f".{v.microsecond:06d}".rstrip("0")
                return s
            if isinstance(v, _dtm.date):
                return v.isoformat()
            if isinstance(v, _dec.Decimal):
                return format(v, "f")
            return str(v)

        sarr = pa.array([fmt(v) for v in arr.to_pylist()], type=pa.string())
        enc = sarr.dictionary_encode()
        codes = np.asarray(enc.indices.fill_null(0)).astype(np.int32)
        import pyarrow.compute as _pc
        out_validity = jnp.asarray(np.asarray(_pc.is_valid(sarr)))
        return jnp.asarray(codes), out_validity, enc.dictionary

    def _exec_GenerateExec(self, p: pn.GenerateExec) -> HostBatch:
        """Host row expansion for explode/posexplode/inline/stack."""
        from .host_interp import HostInterpreter

        child = self.run(p.input)
        comp = self._compiler(child, p.input.schema)
        interp = HostInterpreter(self, comp, child)
        sel = np.asarray(child.device.sel)
        live = np.nonzero(sel)[0]
        def live_vals(r):
            vals = interp.values(r)
            return [vals[i] for i in live]

        pt_vals = [(n, live_vals(r)) for n, r in p.passthrough]
        arg_vals = [live_vals(a) for a in p.args]
        out_rows: List[tuple] = []
        for row_i in range(len(live)):
            pt = tuple(vals[row_i] for _, vals in pt_vals)
            gen_rows = _generate_rows(
                p.generator, [col[row_i] for col in arg_vals],
                [f.name for f in p.gen_schema])
            if not gen_rows and p.outer:
                gen_rows = [tuple([None] * len(p.gen_schema))]
            for g in gen_rows:
                out_rows.append(pt + g)
        names = [n for n, _ in p.passthrough] + \
            [f.name for f in p.gen_schema]
        types = [rx.rex_type(r) for _, r in p.passthrough] + \
            [f.dtype for f in p.gen_schema]
        arrays = []
        for ci, (n, t) in enumerate(zip(names, types)):
            at = ai.spec_type_to_arrow(t)
            vals = [r[ci] for r in out_rows]
            from .host_interp import _pyarrowable
            arrays.append(pa.array([_pyarrowable(v, t) for v in vals],
                                   type=at))
        table = pa.Table.from_arrays(arrays, names=[f"c{i}" for i in
                                                    range(len(names))])
        return ai.from_arrow(table)

    # -- PySpark UDF relations (host-evaluated; reference:
    # sail-python-udf group/cogroup map + map-iter kinds) ---------------
    def _named_arrow(self, p_input) -> "pa.Table":
        child = self.run(p_input)
        table = ai.to_arrow(child)
        return table.rename_columns([f.name for f in p_input.schema])

    def _udf_result_to_batch(self, frames, out_schema) -> HostBatch:
        """pandas frames / arrow batches from a UDF → HostBatch matching
        the DECLARED output schema (cast, reorder, missing → error)."""
        import pandas as pd

        tables = []
        for f in frames:
            if isinstance(f, pa.Table):
                tables.append(f)
            elif isinstance(f, pa.RecordBatch):
                tables.append(pa.Table.from_batches([f]))
            elif isinstance(f, pd.DataFrame):
                tables.append(pa.Table.from_pandas(f, preserve_index=False))
            else:
                raise TypeError(
                    f"UDF returned {type(f).__name__}; expected DataFrame "
                    f"or arrow batch")
        names = [f.name for f in out_schema]
        types = [ai.spec_type_to_arrow(f.dtype) for f in out_schema]
        if not tables:
            table = pa.Table.from_arrays(
                [pa.array([], type=t) for t in types], names=names)
        else:
            table = pa.concat_tables(tables, promote_options="permissive")
            missing = [n for n in names if n not in table.column_names]
            if missing:
                raise ValueError(
                    f"UDF output is missing declared columns {missing}")
            cols = [table.column(n).cast(t, safe=False)
                    for n, t in zip(names, types)]
            table = pa.Table.from_arrays(cols, names=names)
        return _positional(ai.from_arrow(table))

    @staticmethod
    def _udf_arity(func, default: int) -> int:
        import inspect
        try:
            return len(inspect.signature(func).parameters)
        except (TypeError, ValueError):
            return default

    @staticmethod
    def _norm_key(key) -> tuple:
        """Group keys as comparable tuples: pandas represents null keys
        as NaN, and NaN != NaN would split one logical group across the
        two cogroup sides — normalize to None."""
        kt = key if isinstance(key, tuple) else (key,)
        return tuple(None if (isinstance(x, float) and x != x) else x
                     for x in kt)

    def _exec_UdtfExec(self, p: pn.UdtfExec) -> HostBatch:
        """Python UDTF: handler.eval(*args) yields rows (tuples or
        scalars); terminate() may yield trailing rows."""
        inst = p.handler() if isinstance(p.handler, type) else p.handler
        rows = []

        def extend(gen):
            if gen is None:
                return
            for row in gen:
                if not isinstance(row, (tuple, list)):
                    row = (row,)
                rows.append(tuple(row))

        extend(inst.eval(*p.args))
        if hasattr(inst, "terminate"):
            extend(inst.terminate())
        names = [f.name for f in p.out_schema]
        types = [ai.spec_type_to_arrow(f.dtype) for f in p.out_schema]
        arrays = []
        for ci, t in enumerate(types):
            arrays.append(pa.array(
                [r[ci] if ci < len(r) else None for r in rows], type=t))
        table = pa.Table.from_arrays(arrays, names=names)
        return _positional(ai.from_arrow(table))

    def _exec_GroupMapExec(self, p: pn.GroupMapExec) -> HostBatch:
        table = self._named_arrow(p.input)
        pdf = table.to_pandas()
        key_cols = [table.column_names[i] for i in p.key_indices]
        func = p.udf.func
        wants_key = self._udf_arity(func, 1) >= 2
        outs = []
        if len(pdf) and key_cols:
            for key, g in pdf.groupby(key_cols, dropna=False, sort=True):
                g = g.reset_index(drop=True)
                if wants_key:
                    k = key if isinstance(key, tuple) else (key,)
                    outs.append(func(k, g))
                else:
                    outs.append(func(g))
        elif len(pdf):
            outs.append(func(pdf))
        return self._udf_result_to_batch(outs, p.out_schema)

    def _exec_CoGroupMapExec(self, p: pn.CoGroupMapExec) -> HostBatch:
        import pandas as pd

        lt = self._named_arrow(p.left)
        rt = self._named_arrow(p.right)
        lpdf, rpdf = lt.to_pandas(), rt.to_pandas()
        lk = [lt.column_names[i] for i in p.left_keys]
        rk = [rt.column_names[i] for i in p.right_keys]
        lgroups = {self._norm_key(k): g
                   for k, g in lpdf.groupby(lk, dropna=False, sort=True)} \
            if len(lpdf) else {}
        rgroups = {self._norm_key(k): g
                   for k, g in rpdf.groupby(rk, dropna=False, sort=True)} \
            if len(rpdf) else {}
        func = p.udf.func
        nparams = self._udf_arity(func, 2)
        outs = []
        for key in sorted(set(lgroups) | set(rgroups),
                          key=lambda k: tuple(str(x) for x in k)):
            lg = lgroups.get(key)
            rg = rgroups.get(key)
            lg = (lg.reset_index(drop=True) if lg is not None
                  else lpdf.iloc[0:0].copy())
            rg = (rg.reset_index(drop=True) if rg is not None
                  else rpdf.iloc[0:0].copy())
            if nparams >= 3:
                outs.append(func(key, lg, rg))
            else:
                outs.append(func(lg, rg))
        return self._udf_result_to_batch(outs, p.out_schema)

    def _exec_MapPartitionsExec(self, p: pn.MapPartitionsExec) -> HostBatch:
        table = self._named_arrow(p.input)
        func = p.udf.func
        if p.udf.eval_type == "map_arrow":
            it = func(iter(table.to_batches()))
            outs = list(it)
        else:  # map_pandas
            it = func(iter([table.to_pandas()]))
            outs = list(it)
        return self._udf_result_to_batch(outs, p.out_schema)

    def _exec_FilterExec(self, p: pn.FilterExec) -> HostBatch:
        if self._fusion_on():
            out = self._try_fused_chain(p)
            if out is not None:
                return out
        return self._filter_over(p, self.run(p.input))

    def _filter_over(self, p: pn.FilterExec, child: HostBatch
                     ) -> HostBatch:
        dev = child.device

        def builder():
            comp = self._compiler(child, p.input.schema)
            c = comp.compile(p.condition)

            def fn(cols, sel):
                data, validity = c.fn(cols)
                keep = data.astype(jnp.bool_)
                if validity is not None:
                    keep = keep & validity
                return sel & keep

            return fn, None

        key = self._op_key("filter", p.condition,
                           tuple((f.name, f.dtype) for f in p.input.schema))
        try:
            fn, _ = self._jitted(key, self._dict_objs(child), builder)
        except HostFallback:
            # host-only predicate (arrays/json/…): interpret row-wise
            from .host_interp import HostInterpreter
            comp = self._compiler(child, p.input.schema)
            vals = HostInterpreter(self, comp, child).values(p.condition)
            keep = jnp.asarray(np.array([v is True for v in vals]))
            return HostBatch(dev.with_sel(dev.sel & keep), child.dicts)
        return HostBatch(dev.with_sel(fn(self._cols(child), dev.sel)),
                         child.dicts)

    def _exec_LimitExec(self, p: pn.LimitExec) -> HostBatch:
        child = self.run(p.input)
        dev = child.device
        if p.offset == -1:  # tail
            n = int(dev.num_rows())
            off = max(0, n - (p.limit or 0))
            out = sortk.limit(dev, p.limit or 0, off)
        else:
            out = sortk.limit(dev, p.limit if p.limit is not None else dev.capacity,
                              p.offset)
        return HostBatch(out, child.dicts)

    def _exec_SortExec(self, p: pn.SortExec) -> HostBatch:
        chain: List[pn.PlanNode] = []
        node = p.input
        if self._fusion_on():
            while isinstance(node, (pn.FilterExec, pn.ProjectExec)):
                chain.append(node)
                node = node.input
        if not chain:
            child = self.run(p.input)
            spilled = self._try_external_sort(p, child)
            if spilled is not None:
                return spilled
            return self._sort_over(p, child)
        # pre-sort pipeline: chain + key eval + gather compile to ONE
        # program. Out-of-core candidates (decided from the BOTTOM
        # batch's capacity and the sorted rows' widths, so no device
        # sync decides this) materialize the chain first and keep the
        # spill path byte-identical.
        child = self.run(node)
        if self._sort_out_of_core(p, child) is not None:
            mat = self._apply_chain(chain, child, node)
            spilled = self._try_external_sort(p, mat)
            if spilled is not None:
                return spilled
            return self._sort_over(p, mat)
        return self._fused_sort(p, chain, child, node)

    def _sort_out_of_core(self, p: pn.SortExec,
                          child: HostBatch) -> Optional[OutOfCore]:
        """``out_of_core`` for a sort whose rows have ``p.input``'s
        columns at ``child``'s capacity (``child`` may be the batch under
        a Filter/Project chain still to be applied: the capacity stays).
        Expression keys stay on the in-memory path."""
        if not p.keys or \
                any(not isinstance(k.expr, rx.BoundRef) for k in p.keys):
            return None
        return out_of_core(
            "execution.sort_spill_rows", child.device.capacity,
            sort_working_set(child.device.capacity,
                             _row_bytes(p.input.schema)))

    def _fused_sort(self, p: pn.SortExec, chain: List[pn.PlanNode],
                    child: HostBatch, bottom: pn.PlanNode) -> HostBatch:
        from ..plan import stages as pst

        key = self._op_key(
            "fused_sort", pst.stage_fingerprint([p] + chain,
                                                bottom.schema))

        def builder():
            chain_fn, top_dicts, top_schema = self._compile_chain(
                chain, child, bottom)
            top_schema = tuple(top_schema)
            comp = ExprCompiler(
                [f.dtype for f in top_schema],
                {i: top_dicts[_col_name(i)]
                 for i in range(len(top_schema))
                 if _col_name(i) in top_dicts},
                self._subquery_cache)
            compiled = [(comp.compile(k.expr), k) for k in p.keys]
            rank_luts = []
            for c, k in compiled:
                rank_luts.append(
                    jnp.asarray(ai.dictionary_ranks(c.dictionary))
                    if c.dictionary is not None
                    and len(c.dictionary) > 0 else None)

            def fn(cols, sel):
                pairs, sel2 = chain_fn(cols, sel)
                cap = sel2.shape[0]
                fitted = [_fit_capacity(d, v, cap) for d, v in pairs]
                keys = []
                for (c, k), lut in zip(compiled, rank_luts):
                    data, validity = c.fn(fitted)
                    kdt = rx.rex_type(k.expr)
                    if lut is not None:
                        data = lut[data]
                        kdt = dt.IntegerType()
                    keys.append((data, validity, kdt, k.ascending,
                                 k.nulls_first))
                perm = sortk.lexsort_perm(keys, sel2)
                out_d = [d[perm] for d, _ in fitted]
                out_v = [None if v is None else v[perm]
                         for _, v in fitted]
                out_sel = sel2[perm]
                if p.limit is not None:
                    idx = jnp.arange(out_sel.shape[0], dtype=jnp.int32)
                    out_sel = out_sel & (idx < p.limit)
                return out_d, out_v, out_sel

            return fn, (top_dicts, top_schema)

        from .. import telemetry as tel
        try:
            fn, aux = self._jitted(key, self._dict_objs(child), builder,
                                   fused=True)
        except HostFallback:
            # count the declined pipeline ONCE and apply the chain
            # per-op directly — re-attempting the fused chain program
            # here would recompile the same failing bind a second time
            self._note_fusion_fallback("sort")
            mat = child
            for op in reversed(chain):
                mat = self._apply_op(op, mat)
            return self._sort_over(p, mat)

        def finish():
            top_dicts, top_schema = aux
            out_d, out_v, out_sel = fn(self._cols(child),
                                       child.device.sel)
            cols = {_col_name(i): Column(d, v, f.dtype)
                    for i, (d, v, f) in enumerate(
                        zip(out_d, out_v, top_schema))}
            out = DeviceBatch(cols, out_sel)
            if p.limit is not None:
                out = _shrink(out, p.limit)
            return HostBatch(out, top_dicts)

        if tel.current_collector() is not None:
            ops = "+".join(type(n).__name__ for n in chain)
            with tel.operator_span("FusedSort", ops) as m:
                out = finish()
                m.output_rows = int(out.device.num_rows())
                m.capacity = out.capacity
                return out
        return finish()

    def _sort_over(self, p: pn.SortExec, child: HostBatch) -> HostBatch:
        def builder():
            comp = self._compiler(child, p.input.schema)
            compiled = [(comp.compile(k.expr), k) for k in p.keys]
            rank_luts = []
            for c, k in compiled:
                # an empty dictionary (0-row input) has no codes to remap —
                # and a 0-size LUT gather is a compile error
                rank_luts.append(jnp.asarray(ai.dictionary_ranks(c.dictionary))
                                 if c.dictionary is not None
                                 and len(c.dictionary) > 0 else None)

            def fn(cols, sel, datas, validities):
                keys = []
                for (c, k), lut in zip(compiled, rank_luts):
                    data, validity = c.fn(cols)
                    kdt = rx.rex_type(k.expr)
                    if lut is not None:
                        data = lut[data]
                        kdt = dt.IntegerType()
                    keys.append((data, validity, kdt, k.ascending, k.nulls_first))
                perm = sortk.lexsort_perm(keys, sel)
                out_d = [d[perm] for d in datas]
                out_v = [None if v is None else v[perm] for v in validities]
                out_sel = sel[perm]
                if p.limit is not None:
                    idx = jnp.arange(out_sel.shape[0], dtype=jnp.int32)
                    out_sel = out_sel & (idx < p.limit)
                return out_d, out_v, out_sel

            return fn, None

        key = self._op_key("sort", p.keys, p.limit,
                           tuple((f.name, f.dtype) for f in p.input.schema))
        try:
            fn, _ = self._jitted(key, self._dict_objs(child), builder)
        except HostFallback:
            # host-only sort keys (struct fields, host functions)
            return self._sort_host_fallback(p, child)
        dev = child.device
        names = [_col_name(i) for i in range(len(dev.columns))]
        datas = [dev.columns[n].data for n in names]
        validities = [dev.columns[n].validity for n in names]
        out_d, out_v, out_sel = fn(self._cols(child), dev.sel, datas, validities)
        cols = {n: Column(d, v, dev.columns[n].dtype)
                for n, d, v in zip(names, out_d, out_v)}
        out = DeviceBatch(cols, out_sel)
        if p.limit is not None:
            out = _shrink(out, p.limit)
        return HostBatch(out, child.dicts)

    def _sort_host_fallback(self, p: pn.SortExec,
                            child: HostBatch) -> HostBatch:
        """Sort keys the device compiler cannot express (struct fields,
        host-only functions): key VALUES come from the host interpreter,
        the permutation from a stable pandas sort, and the row gather
        stays on device."""
        import jax
        import pandas as pd

        from .host_interp import HostInterpreter

        comp = self._compiler(child, p.input.schema)
        interp = HostInterpreter(self, comp, child)
        sel = np.asarray(profiler.host_sync("sort.host_fallback",
                                            child.device.sel))
        frame: Dict[str, object] = {"__dead": ~sel}
        by = ["__dead"]          # dead rows sort to the end
        asc = [True]
        for i, k in enumerate(p.keys):
            vals = interp.values(k.expr)
            nulls_first = k.nulls_first if k.nulls_first is not None \
                else k.ascending
            isna = np.array([v is None for v in vals], dtype=bool)
            frame[f"n{i}"] = ~isna if nulls_first else isna
            by.append(f"n{i}")
            asc.append(True)
            fill = next((v for v in vals if v is not None), None)
            frame[f"k{i}"] = [fill if v is None else v for v in vals]
            by.append(f"k{i}")
            asc.append(k.ascending)
        perm = jnp.asarray(pd.DataFrame(frame).sort_values(
            by, ascending=asc, kind="stable").index.to_numpy())
        dev = child.device
        cols = {nm: Column(c.data[perm],
                           None if c.validity is None else c.validity[perm],
                           c.dtype)
                for nm, c in dev.columns.items()}
        out_sel = dev.sel[perm]
        if p.limit is not None:
            idx = jnp.arange(out_sel.shape[0], dtype=jnp.int32)
            out_sel = out_sel & (idx < p.limit)
        out = DeviceBatch(cols, out_sel)
        if p.limit is not None:
            out = _shrink(out, p.limit)
        return HostBatch(out, child.dicts)

    def _pipeline_chain(self, p: pn.PlanNode):
        """Collect the Filter/Project chain under ``p`` (top-down) and the
        batch below it — the chain fuses into the consumer's jit so XLA
        sees one program (no intermediate HBM materialization)."""
        chain = []
        node = p
        while isinstance(node, (pn.FilterExec, pn.ProjectExec)):
            chain.append(node)
            node = node.input
        child = self.run(node)
        return chain, child, node

    # -- whole-stage fusion: standalone pipeline stages -----------------
    def _try_fused_chain(self, top: pn.PlanNode) -> Optional[HostBatch]:
        """Execute a maximal Filter/Project pipeline as ONE jitted
        program (the ``pipeline`` stage of ``plan/stages.py``): the
        chain's intermediates never materialize between operators.
        Returns None when the chain is trivial (single operator — the
        per-op path already compiles one program) or needs host
        evaluation (the caller falls back per-op, which re-enters
        fusion on the shorter sub-chains)."""
        from .. import telemetry as tel

        chain: List[pn.PlanNode] = []
        node = top
        while isinstance(node, (pn.FilterExec, pn.ProjectExec)):
            chain.append(node)
            node = node.input
        if len(chain) < 2:
            return None
        child = self.run(node)
        try:
            if tel.current_collector() is not None:
                # aborted spans (HostFallback) are discarded by
                # operator_span, so the fallback run reports cleanly
                ops = "+".join(type(n).__name__ for n in chain)
                with tel.operator_span("FusedPipeline", ops) as m:
                    out = self._run_chain(chain, child, node)
                    m.output_rows = int(out.device.num_rows())
                    m.capacity = out.capacity
                    return out
            return self._run_chain(chain, child, node)
        except HostFallback:
            # per-op over the ALREADY-materialized bottom batch: falling
            # all the way back through run() would re-execute the input
            # subtree once per chain suffix (and over-count fallbacks)
            self._note_fusion_fallback("pipeline")
            out = child
            for op in reversed(chain):
                out = self._apply_op(op, out)
            return out

    def _run_chain(self, chain: List[pn.PlanNode], child: HostBatch,
                   bottom: pn.PlanNode) -> HostBatch:
        """One compiled program for a Filter/Project pipeline over an
        already-materialized bottom batch. Raises HostFallback when any
        chain expression needs host evaluation."""
        from ..plan import stages as pst

        key = self._op_key(
            "fused_chain", pst.stage_fingerprint(chain, bottom.schema))

        def builder():
            chain_fn, out_dicts, out_schema = self._compile_chain(
                chain, child, bottom)
            return chain_fn, (out_dicts, tuple(out_schema))

        fn, aux = self._jitted(key, self._dict_objs(child), builder,
                               fused=True)
        out_dicts, out_schema = aux
        cols2, sel2 = fn(self._cols(child), child.device.sel)
        if not any(isinstance(n, pn.ProjectExec) for n in chain):
            # filter-only pipeline: the batch's columns are untouched
            return HostBatch(child.device.with_sel(sel2), child.dicts)
        cap = child.device.sel.shape[0]
        out_cols: Dict[str, Column] = {}
        for i, ((d, v), f) in enumerate(zip(cols2, out_schema)):
            d, v = _fit_capacity(d, v, cap)
            out_cols[_col_name(i)] = Column(d, v, f.dtype)
        return HostBatch(DeviceBatch(out_cols, sel2), out_dicts)

    def _apply_op(self, op: pn.PlanNode, batch: HostBatch) -> HostBatch:
        """One Filter/Project over a given batch, with an operator span
        under EXPLAIN ANALYZE (these don't pass through ``run``)."""
        from .. import telemetry as tel

        def go():
            if isinstance(op, pn.FilterExec):
                return self._filter_over(op, batch)
            return self._project_over(op, batch)

        if tel.current_collector() is not None:
            with tel.operator_span(type(op).__name__) as m:
                out = go()
                m.output_rows = int(out.device.num_rows())
                m.capacity = out.capacity
                return out
        return go()

    def _apply_chain(self, chain: List[pn.PlanNode], child: HostBatch,
                     bottom: pn.PlanNode) -> HostBatch:
        """Materialize a chain's output over ``child``: the fused
        program when it compiles, per-operator evaluation otherwise."""
        if not chain:
            return child
        try:
            return self._run_chain(chain, child, bottom)
        except HostFallback:
            self._note_fusion_fallback("pipeline")
            out = child
            for op in reversed(chain):
                out = self._apply_op(op, out)
            return out

    def _compile_chain(self, chain, bottom: HostBatch, bottom_node: pn.PlanNode):
        """Returns (chain_fn, out_dicts, out_schema): chain_fn maps the
        bottom batch's (cols, sel) to the top of the chain's (cols, sel).
        Must be called at bind time (host): dictionaries propagate level by
        level."""
        levels = list(reversed(chain))  # bottom-up
        cur_batch = bottom
        cur_schema = bottom_node.schema
        steps = []
        for node in levels:
            comp = self._compiler(cur_batch, cur_schema)
            if isinstance(node, pn.FilterExec):
                c = comp.compile(node.condition)
                steps.append(("filter", c))
                # dicts/schema unchanged
            else:
                compiled = [comp.compile(e) for _, e in node.exprs]
                steps.append(("project", compiled,
                              [rx.rex_type(e) for _, e in node.exprs]))
                new_dicts = {_col_name(i): c.dictionary
                             for i, c in enumerate(compiled)
                             if c.dictionary is not None}
                # fabricate a dict-only HostBatch view for the next level's
                # compiler (only .dicts is consulted at bind time)
                cur_batch = HostBatch(cur_batch.device, new_dicts)
                cur_schema = node.schema
        out_dicts = dict(cur_batch.dicts)

        def chain_fn(cols, sel):
            for step in steps:
                if step[0] == "filter":
                    d, v = step[1].fn(cols)
                    keep = d.astype(jnp.bool_)
                    if v is not None:
                        keep = keep & v
                    sel = sel & keep
                else:
                    _, compiled, types = step
                    new_cols = []
                    for c, t in zip(compiled, types):
                        d, v = c.fn(cols)
                        jdt = physical_jnp_dtype(t)
                        if d.dtype != jnp.dtype(jdt):
                            d = d.astype(jdt)
                        new_cols.append((d, v))
                    cols = new_cols
            return cols, sel

        return chain_fn, out_dicts, cur_schema

    def _exec_AggregateExec(self, p: pn.AggregateExec) -> HostBatch:
        # Fuse the Filter/Project chain under the aggregate into ONE jitted
        # program: no intermediate batch materializes in HBM (the TPC-H Q1
        # hot path — filter, derived-expression projection, aggregation —
        # compiles to a single XLA executable). Under EXPLAIN ANALYZE run
        # unfused so every operator reports its own rows/time.
        from .. import telemetry as tel
        if any(a.fn.startswith("__host__") for a in p.aggs) or \
                any(a.distinct for a in p.aggs):
            return self._host_aggregate(p, self.run(p.input))
        chunked = self._try_chunked_aggregate(p)
        if chunked is not None:
            return chunked
        # Under EXPLAIN ANALYZE keep the PRODUCTION (fused) program and
        # report the pipeline as one fused operator — profiling must
        # measure the program that actually runs, not an unfused variant.
        chain, child, bottom_node = self._pipeline_chain(p.input)
        # CPU fallback fast path: fused C++ row loop over host buffers
        # (one pass for all aggregates; see sail_tpu/native/) — taken
        # only when the backend router's stage decision says native
        # (stage-split-time routing; `execution.backend.force` can pin
        # either substrate for A/B and bisection)
        from .. import native as _native
        from . import router

        from ..plan import stages as pst

        route = self._aggregate_route(p)
        go_native = _native.native_active() and \
            (route is None or route.backend == "native")
        obs_key = router.obs_key(
            tuple(pst.node_fingerprint(n) for n in [p] + chain))
        with router.observing(obs_key):
            if tel.current_collector() is not None:
                if go_native:
                    try:
                        with tel.operator_span(
                                "NativeFusedAggregate",
                                "fused C++ host kernel") as m:
                            native = _native.try_native_agg(
                                self, p, chain, child, bottom_node)
                            if native is None:
                                raise _NativeMiss()  # discard the span
                            m.output_rows = int(native.device.num_rows())
                            m.capacity = native.capacity
                            return native
                    except _NativeMiss:
                        pass
            elif go_native:
                native = _native.try_native_agg(self, p, chain, child,
                                                bottom_node)
                if native is not None:
                    return native
            return self._agg_xla_path(p, chain, child, bottom_node)

    def _aggregate_route(self, p: pn.AggregateExec):
        """The stage-split-time routing decision for this aggregate's
        stage, when one was recorded; a forced backend applies even
        when no split ran (fusion off)."""
        from . import router
        sid = self._route_stage_of.get(id(p))
        if sid is not None:
            dec = self._backend_routes.get(sid)
            if dec is not None:
                return dec
        force = router.forced_backend(self.config)
        if force:
            return router.Decision(-1, "aggregate",
                                   force if force != "mesh" else "xla",
                                   "forced")
        return None

    def _agg_xla_path(self, p, chain, child, bottom_node):
        from .. import telemetry as tel
        if tel.current_collector() is not None and chain:
            ops = "+".join(type(c).__name__ for c in chain)
            try:
                with tel.operator_span("FusedAggregate", ops) as m:
                    out = self._agg_with_chain(p, chain, child, bottom_node)
                    m.output_rows = int(out.device.num_rows())
                    m.capacity = out.capacity
                    return out
            except HostFallback:
                # the fused attempt aborted (span discarded): run and
                # profile the actual unfused program instead
                self._note_fusion_fallback("aggregate")
                child = self.run(chain[0])
                with tel.operator_span("AggregateExec",
                                       "unfused (host fallback)") as m:
                    out = self._agg_with_chain(p, [], child, p.input)
                    m.output_rows = int(out.device.num_rows())
                    m.capacity = out.capacity
                    return out
        return self._agg_with_chain_or_unfused(p, chain, child, bottom_node)

    def _agg_with_chain_or_unfused(self, p, chain, child, bottom_node):
        try:
            return self._agg_with_chain(p, chain, child, bottom_node)
        except HostFallback:
            # chains needing host evaluation (string UDFs, host-only casts)
            # cannot fuse — run the chain operators unfused instead
            if chain:
                self._note_fusion_fallback("aggregate")
                child = self.run(chain[0])
            return self._agg_with_chain(p, [], child, p.input)

    def _agg_with_chain(self, p: pn.AggregateExec, chain, child: HostBatch,
                        bottom_node: pn.PlanNode) -> HostBatch:
        dev = child.device
        in_schema = p.input.schema
        if p.group_indices:
            max_groups = p.max_groups_hint or dev.capacity
        else:
            max_groups = 1

        from ..plan import stages as pst
        stage_key = pst.stage_fingerprint([p] + chain, bottom_node.schema)

        def make_builder(mg):
            def builder():
                chain_fn, top_dicts, _ = self._compile_chain(chain, child,
                                                             bottom_node)
                # direct binning when every group key has a known small
                # domain (dictionary codes / booleans) — no sort needed.
                # Decided at bind time; the cache key's dictionary identity
                # pins the decision's inputs.
                domains = _direct_domains(p, in_schema, top_dicts)
                # what the trace learns of the program: the operands of
                # the sorted route's sort
                traced = {}

                # min/max over a dictionary-encoded column must order by
                # VALUE, not code: remap codes through an order-preserving
                # rank LUT before the aggregate (and its sort) and back after
                minmax_luts = {}
                for j, a in enumerate(p.aggs):
                    if a.fn in ("min", "max") and a.arg is not None:
                        name = _col_name(a.arg)
                        if name in top_dicts and len(top_dicts[name]) > 1:
                            ranks = _dict_order_ranks(top_dicts[name])
                            inv = np.empty_like(ranks)
                            inv[ranks] = np.arange(len(ranks),
                                                   dtype=ranks.dtype)
                            minmax_luts[j] = (jnp.asarray(ranks),
                                              jnp.asarray(inv))

                def fn(cols, sel):
                    cols, sel = chain_fn(cols, sel)
                    key_cols = [Column(cols[i][0], cols[i][1],
                                       in_schema[i].dtype)
                                for i in p.group_indices]
                    calls = []
                    for j, a in enumerate(p.aggs):
                        if a.fn not in aggk.AGG_FNS:
                            raise ExecutionError(
                                f"aggregate {a.fn!r} not implemented")
                        arg = None if a.arg is None else \
                            Column(cols[a.arg][0], cols[a.arg][1],
                                   in_schema[a.arg].dtype)
                        lut = minmax_luts.get(j)
                        if lut is not None:
                            ranks_lut = lut[0]
                            codes = jnp.clip(arg.data, 0,
                                             ranks_lut.shape[0] - 1)
                            arg = Column(ranks_lut[codes], arg.validity,
                                         arg.dtype)
                        calls.append(aggk.AggCall(a.fn, arg, a.out_dtype,
                                                  a.ignore_nulls))
                    g = aggk.group_aggregate(key_cols, sel, mg, calls,
                                             domains)
                    traced["sort_operands"] = g.sort_operands
                    outs = []
                    for j, col in enumerate(g.aggs):
                        lut = minmax_luts.get(j)
                        if lut is not None:
                            inv_lut = lut[1]
                            col = Column(
                                inv_lut[jnp.clip(col.data, 0,
                                                 inv_lut.shape[0] - 1)],
                                col.validity, col.dtype)
                        outs.append((col.data, col.validity))
                    return ([(k.data, k.validity) for k in g.keys], outs,
                            g.sel, g.num_groups, g.overflow)
                return fn, (top_dicts, traced)
            return builder

        import jax

        key = self._op_key("agg", stage_key, max_groups)
        fn, (top_dicts, traced) = self._jitted(key, self._dict_objs(child),
                                               make_builder(max_groups),
                                               fused=bool(chain))
        gk, aggs_out, gsel, n_groups, overflow = fn(self._cols(child), dev.sel)
        # one batched fetch: each blocking scalar read is a device sync
        n_groups, overflow = profiler.host_sync(
            "agg.n_groups", (n_groups, overflow))
        if p.max_groups_hint and bool(overflow):
            key2 = self._op_key("agg2", stage_key, dev.capacity)
            fn2, (top_dicts, traced) = self._jitted(
                key2, self._dict_objs(child), make_builder(dev.capacity),
                fused=bool(chain))
            gk, aggs_out, gsel, n_groups, overflow = fn2(self._cols(child), dev.sel)
            n_groups = profiler.host_sync("agg2.n_groups", n_groups)
        # on the open aggregate span: the grouping route the program took,
        # the rows it ran over, the groups it found and, where it sorted,
        # the arrays its sort moved
        from .. import tracing as tr
        if not p.group_indices:
            path = aggk.GLOBAL
        elif _direct_domains(p, in_schema, top_dicts) is not None:
            path = aggk.DIRECT
        else:
            path = aggk.SORTED
            tr.set_attribute("sort_operands", traced["sort_operands"])
        tr.set_attribute("path", path)
        tr.set_attribute("input_capacity", dev.capacity)
        tr.set_attribute("groups", int(n_groups))
        out_cols: Dict[str, Column] = {}
        out_dicts: Dict[str, pa.Array] = {}
        for j, gi in enumerate(p.group_indices):
            k = _col_name(j)
            out_cols[k] = Column(gk[j][0], gk[j][1], in_schema[gi].dtype)
            src = _col_name(gi)
            if src in top_dicts:
                out_dicts[k] = top_dicts[src]
        ng = len(p.group_indices)
        for j, a in enumerate(p.aggs):
            k = _col_name(ng + j)
            out_cols[k] = Column(aggs_out[j][0], aggs_out[j][1], a.out_dtype)
            if a.arg is not None and a.fn in ("min", "max", "first", "last"):
                src = _col_name(a.arg)
                if src in top_dicts:
                    out_dicts[k] = top_dicts[src]
        out = DeviceBatch(out_cols, gsel)
        out = _shrink(out, int(n_groups),
                      bucket_key=("agg-shrink", pst.node_fingerprint(p)))
        return HostBatch(out, out_dicts)

    # out-of-core: aggregates over big parquet scans stream chunk-wise
    # through the fused partial-agg program, so a table never needs to fit
    # in HBM whole (reference role: DataFusion memory pools + morsel scan;
    # TPU shape: fixed-capacity chunks re-use ONE compiled XLA program).
    # The scan side is PIPELINED: a bounded background producer drives
    # parquet decode + declared-schema normalization while this thread
    # runs the jitted partial-aggregate on the previous chunk, and
    # partials fold incrementally so peak host memory stays bounded by
    # prefetch depth × chunk size rather than the number of chunks.
    _CHUNK_MERGE = {"sum": "sum", "count": "sum", "min": "min",
                    "max": "max", "first": "first", "last": "last",
                    "bool_and": "bool_and", "bool_or": "bool_or"}

    def _prefetch_depth(self) -> int:
        from ..io.prefetch import prefetch_depth
        return prefetch_depth(self.config)

    def _try_chunked_aggregate(self, p: pn.AggregateExec
                               ) -> Optional[HostBatch]:
        import pyarrow.dataset as pads
        from .. import telemetry as tel
        from ..io.formats import expand_paths, rex_predicates_to_arrow
        from ..io.prefetch import Prefetcher

        if any(a.distinct or a.fn not in self._CHUNK_MERGE or
               a.filter is not None for a in p.aggs):
            return None
        # find the chain bottom scan
        node = p.input
        while isinstance(node, (pn.FilterExec, pn.ProjectExec)):
            node = node.input
        if not (isinstance(node, pn.ScanExec) and node.paths
                and node.format == "parquet"):
            return None
        chunk_rows = int(self.config.get("spark.sail.scan.chunkRows", 0) or 0)
        try:
            files = expand_paths(node.paths)
            total_bytes = sum(os.path.getsize(f) for f in files)
        except OSError:
            return None
        if chunk_rows <= 0:
            if total_bytes < 1 << 30:
                return None  # small scans take the resident path
            chunk_rows = 8_000_000
        filter_expr = None
        if node.predicates:
            from ..io.formats import row_group_pruning_enabled
            if row_group_pruning_enabled():
                filter_expr = rex_predicates_to_arrow(node.predicates,
                                                      node.schema)
        ds = pads.dataset(files, format="parquet")
        scanner = ds.scanner(
            columns=list(node.projection) if node.projection else None,
            filter=filter_expr, batch_size=chunk_rows)
        nk = len(p.group_indices)
        part_schema = tuple(
            pn.Field(f"p{i}", f.dtype, True)
            for i, f in enumerate(p.schema))
        final_aggs = tuple(
            pn.AggSpec(self._CHUNK_MERGE[a.fn], nk + j, False, a.out_dtype,
                       None, a.ignore_nulls)
            for j, a in enumerate(p.aggs))

        def merge_plan(partials_table: pa.Table) -> pn.AggregateExec:
            return pn.AggregateExec(
                pn.ScanExec(part_schema, partials_table, (), "memory"),
                tuple(range(nk)), final_aggs, p.out_names,
                p.max_groups_hint)

        def chunks():
            # coalesce scanner batches up to chunk_rows: parquet hands
            # back row-group-sized batches no matter what batch_size
            # asks for, and every undersized chunk pays a full
            # plan-rewrite + executor dispatch — amortize it
            acc, rows = [], 0
            for b in scanner.to_batches():
                if b.num_rows == 0:
                    continue
                acc.append(b)
                rows += b.num_rows
                if rows >= chunk_rows:
                    yield acc
                    acc, rows = [], 0
            if acc:
                yield acc

        def decode(batches) -> pa.Table:
            # runs on the producer thread: Arrow materialization and
            # schema normalization overlap the consumer's jitted compute
            table = pa.Table.from_batches(batches)
            return self._apply_declared_schema(table, node.schema)

        depth = self._prefetch_depth()
        src = chunks()
        pending: List[pa.Table] = []
        pending_rows = 0
        folded_rows = 0
        with Prefetcher(src, transform=decode, depth=depth,
                        kind="scan") as pf:
            for table in pf:
                chunk_scan = pn.ScanExec(node.out_schema, table, (),
                                         "memory",
                                         projection=node.projection)
                chunk_plan = _replace_node(p, node, chunk_scan)
                pending.append(ai.to_arrow(self.run(chunk_plan)))
                pending_rows += pending[-1].num_rows
                # drop the scan cache entry so chunks don't pile up in HBM
                _drop_mem_scan_entry(table)
                if len(pending) > 1 and \
                        pending_rows > max(chunk_rows, 2 * folded_rows):
                    # streaming fold: compact accumulated partials through
                    # the merge aggregate instead of holding them all for
                    # one giant end-of-scan concat. The 2× guard keeps
                    # high-cardinality groupings amortized O(n): a fold
                    # that can't shrink below the distinct-group count
                    # must not re-run after every chunk
                    folded = pa.concat_tables(pending,
                                              promote_options="permissive")
                    compacted = ai.to_arrow(self.run(merge_plan(folded)))
                    _drop_mem_scan_entry(folded)
                    pending = [compacted]
                    pending_rows = compacted.num_rows
                    folded_rows = pending_rows
        tel.note("ScanPrefetch", "chunked scan→aggregate",
                 **pf.stats.as_extra())
        if not pending:
            empty_scan = pn.ScanExec(node.out_schema,
                                     _empty_arrow(node.schema), (),
                                     "memory", projection=node.projection)
            return self.run(_replace_node(p, node, empty_scan))
        merged = pa.concat_tables(pending, promote_options="permissive")
        out = self.run(merge_plan(merged))
        _drop_mem_scan_entry(merged)
        return out

    def _host_aggregate(self, p: pn.AggregateExec, child: HostBatch
                        ) -> HostBatch:
        """Python grouping path for the statistical/collection aggregate
        tail (reference role: sail-function aggregates). The group slices
        reaching here are already small; the hot sum/count/min/max path
        stays on the device segment kernels."""
        from ..functions.host_aggregates import HOST_AGGS

        table = ai.to_arrow(child)
        cols = {i: _norm_intervals(table.column(i).to_pylist())
                for i in range(table.num_columns)}
        n = table.num_rows
        if p.group_indices:
            groups: Dict[tuple, list] = {}
            for r in range(n):
                key = tuple(_hashable(cols[g][r]) for g in p.group_indices)
                groups.setdefault(key, []).append(r)
            items = list(groups.items())
        else:
            items = [((), list(range(n)))]
        key_out: List[list] = [[] for _ in p.group_indices]
        agg_out: List[list] = [[] for _ in p.aggs]
        for key, rows_idx in items:
            for ki, g in enumerate(p.group_indices):
                key_out[ki].append(cols[g][rows_idx[0]])
            for ai_, spec in enumerate(p.aggs):
                agg_out[ai_].append(
                    _host_agg_one(spec, cols, rows_idx, HOST_AGGS))
        import pyarrow as pa
        arrays = []
        names = []
        in_schema = p.input.schema
        for ki, g in enumerate(p.group_indices):
            at = ai.spec_type_to_arrow(in_schema[g].dtype)
            vals_k = [_intervalize(v, in_schema[g].dtype)
                      for v in key_out[ki]]
            arrays.append(pa.array(vals_k, type=at))
            names.append(p.out_names[ki])
        for ai_, spec in enumerate(p.aggs):
            at = ai.spec_type_to_arrow(spec.out_dtype)
            agg_out[ai_] = [_intervalize(v, spec.out_dtype)
                            for v in agg_out[ai_]]
            try:
                arrays.append(pa.array(agg_out[ai_], type=at))
            except (pa.ArrowInvalid, pa.ArrowTypeError):
                # coerce through the declared type rather than silently
                # changing the column type the plan schema promised
                from .host_interp import py_cast
                coerced = [None if v is None else
                           py_cast(v, dt.NullType(), spec.out_dtype)
                           for v in agg_out[ai_]]
                arrays.append(pa.array(coerced, type=at))
            names.append(p.out_names[len(p.group_indices) + ai_])
        out = pa.Table.from_arrays(arrays, names=names)
        return _positional(ai.from_arrow(out))

    # ------------------------------------------------------------------
    # joins
    # ------------------------------------------------------------------
    def _exec_JoinExec(self, p: pn.JoinExec) -> HostBatch:
        left, right, rtf = self._run_join_inputs(p)
        jt = p.join_type
        if jt == "anti" and p.null_aware:
            return self._null_aware_anti(p, left, right)
        if jt in ("cross", "inner") and not p.left_keys:
            out = self._cross_join(p, left, right)
            if p.residual is not None:
                comb_schema = tuple(p.left.schema) + tuple(p.right.schema)
                comp = ExprCompiler(
                    [f.dtype for f in comb_schema],
                    {i: out.dicts[_col_name(i)] for i in range(len(comb_schema))
                     if _col_name(i) in out.dicts},
                    self._subquery_cache)
                c = comp.compile(p.residual)
                data, validity = self._eval(c, out)
                keep = data.astype(jnp.bool_)
                if validity is not None:
                    keep = keep & validity
                out = HostBatch(out.device.with_sel(out.device.sel & keep),
                                out.dicts)
            return out
        if jt == "right":
            flipped = pn.JoinExec(p.right, p.left, "left", p.right_keys,
                                  p.left_keys,
                                  _flip_residual(p.residual, len(p.left.schema),
                                                 len(p.right.schema)))
            out = self._join(flipped, right, left)
            return _reorder_right(out, len(p.right.schema), len(p.left.schema))
        return self._join(p, left, right, rtf=rtf)

    def _null_aware_anti(self, p: pn.JoinExec, left: HostBatch,
                         right: HostBatch) -> HostBatch:
        """NOT IN (subquery) anti join (reference role:
        crates/sail-plan null-aware anti join selection).

        The IN key is the last key pair; earlier pairs are correlation
        keys. NOT IN over an empty set is TRUE; any NULL build key makes
        every membership test unknown (no rows); NULL probe keys are
        excluded while the build side is non-empty.
        """
        rcomp = self._compiler(right, p.right.schema)
        _, rval = self._eval(rcomp.compile(p.right_keys[-1]), right)
        rsel = right.device.sel
        if int(jnp.sum(rsel)) == 0:
            return left
        # Residual conjuncts are per-row correlation too: the membership set
        # differs per probe row, so the global NULL shortcuts don't apply.
        correlated = len(p.left_keys) > 1 or p.residual is not None
        if rval is not None and bool(jnp.any(rsel & ~rval)):
            if correlated:
                raise ExecutionError(
                    "correlated NOT IN with NULL subquery keys not supported")
            return HostBatch(
                left.device.with_sel(jnp.zeros_like(left.device.sel)),
                left.dicts)
        out = self._join(p, left, right)
        lcomp = self._compiler(left, p.left.schema)
        _, lval = self._eval(lcomp.compile(p.left_keys[-1]), left)
        if lval is not None and bool(jnp.any(left.device.sel & ~lval)):
            if correlated:
                raise ExecutionError(
                    "correlated NOT IN with NULL probe keys not supported")
            out = HostBatch(out.device.with_sel(out.device.sel & lval),
                            out.dicts)
        return out

    # -- runtime join filters (sideways information passing) -----------
    def _run_join_inputs(self, p: pn.JoinExec):
        """Run a join's children. For runtime-filter-annotated inner/semi
        joins the estimated-SMALLER side runs first; a filter derived
        from its keys is pushed into the other subtree's annotated scans
        before that side executes. Forward = build (right) filters
        probe; reverse = probe (left) filters build — the direction that
        matters when join reordering made the fact table the build side
        of the topmost joins. The join itself runs unfiltered: the
        returned ``_Rtf`` only carries the adaptive verdict's inputs."""
        conf = self._rtf_conf()
        use = (conf.enabled and p.runtime_filters and p.left_keys
               and p.join_type in ("inner", "semi") and not p.null_aware)
        if not use:
            return self.run(p.left), self.run(p.right), None
        try:
            est_l, est_r = _rtf_est_rows(p.left), _rtf_est_rows(p.right)
        except Exception:  # noqa: BLE001 — estimation is advisory
            est_l = est_r = None
        reverse = (est_l is not None and est_r is not None
                   and est_l < est_r
                   and any(t.side == "build" for t in p.runtime_filters))
        if reverse:
            left = self.run(p.left)
            rtf, build_plan = self._rtf_prepare(p, left, conf, True,
                                                est_l, est_r)
            right = self.run(build_plan)
        else:
            right = self.run(p.right)
            rtf, probe_plan = self._rtf_prepare(p, right, conf, False,
                                                est_r, est_l)
            left = self.run(probe_plan)
        return left, right, rtf

    def _rtf_conf(self) -> "_RtfConf":
        from ..config import get as config_get

        def setting(spark_key: str, app_key: str, default):
            v = self.config.get(spark_key)
            if v is None:
                v = config_get(app_key, default)
            return v

        def as_bool(v) -> bool:
            return str(v).strip().lower() not in ("0", "false", "off",
                                                  "no")

        def as_int(v, d: int) -> int:
            try:
                return int(v)
            except (TypeError, ValueError):
                return d

        def as_float(v, d: float) -> float:
            try:
                return float(v)
            except (TypeError, ValueError):
                return d

        pfx = "spark.sail.join.runtimeFilter."
        apfx = "join.runtime_filter."
        return _RtfConf(
            enabled=as_bool(setting(pfx + "enabled",
                                    apfx + "enabled", "true")),
            min_build_rows=as_int(setting(pfx + "minBuildRows",
                                          apfx + "min_build_rows", 0), 0),
            in_list_max=as_int(setting(pfx + "inListMax",
                                       apfx + "in_list_max", 8192), 8192),
            ndv_ratio=as_float(setting(pfx + "ndvRatio",
                                       apfx + "ndv_ratio", 0.75), 0.75),
            min_selectivity=as_float(setting(pfx + "minSelectivity",
                                             apfx + "min_selectivity",
                                             0.02), 0.02))

    def _rtf_history_key(self, p: pn.JoinExec, reverse: bool):
        # the verdict must be specific to THIS query's join, not just its
        # key/schema shape: the same `fact JOIN dim` with a different
        # WHERE on dim has a completely different selectivity, so the
        # fingerprint folds in every filter condition and scan identity
        # reachable in both subtrees
        def fingerprint(node: pn.PlanNode):
            out = []
            for n in pn.walk_plan(node):
                if isinstance(n, pn.FilterExec):
                    out.append(n.condition)
                elif isinstance(n, pn.ScanExec):
                    out.append((n.table_name, n.paths,
                                id(n.source) if n.source is not None
                                else None))
            return tuple(out)

        key = ("rtf_hist", reverse, p.left_keys, p.right_keys,
               tuple((f.name, f.dtype) for f in p.left.schema),
               tuple((f.name, f.dtype) for f in p.right.schema),
               fingerprint(p.left), fingerprint(p.right))
        try:
            hash(key)
            return key
        except TypeError:
            return None

    def _rtf_prepare(self, p: pn.JoinExec, src: HostBatch,
                     conf: "_RtfConf", reverse: bool,
                     est_src, est_tgt):
        """Derive the runtime filter (key bounds, counts and, for a
        source with at most ``in_list_max`` usable rows, the key values)
        from the materialized SOURCE side (build side forward, probe side
        reverse) and push value conjuncts into the other subtree's
        annotated scans. Returns (rtf-or-None, rewritten target
        subtree)."""
        import time as _time

        from ..ops import hash as hashk
        from ..ops import runtime_filter as rtfk
        from ..plan import runtime_filters as rtfp

        src_node = p.left if reverse else p.right
        src_keys = p.left_keys if reverse else p.right_keys
        target_plan = p.right if reverse else p.left
        wanted_side = "build" if reverse else "probe"
        targets = tuple(t for t in p.runtime_filters
                        if t.side == wanted_side)

        hkey = self._rtf_history_key(p, reverse)
        if hkey is not None:
            past = _RTF_HISTORY.get(hkey)
            if past is not None and past < conf.min_selectivity:
                return None, target_plan  # observed useless: skip
        # a filter only pays when its source side is smaller than the
        # side it prunes: deriving one FROM a fact-sized side to prune a
        # dimension-sized side costs more than the join saves
        if est_src is not None and est_tgt is not None \
                and est_src >= est_tgt:
            return None, target_plan
        t0 = _time.perf_counter()
        try:
            comp = self._compiler(src, src_node.schema)
            compiled = [comp.compile(k) for k in src_keys]
        except HostFallback:
            return None, target_plan
        # eligible key ordinals: device-hashable physical types whose key
        # bits agree across sides WITHOUT dictionary unification (string
        # keys use per-side code spaces, so they cannot ride the filter).
        # The filter packs with the LEFT key's type — exactly the join's
        # own convention (_compile_join_keys labels both sides with
        # rex_type(lk)) — so source and filtered-side key bits agree.
        ordinals = tuple(
            i for i, (c, lk) in enumerate(zip(compiled, p.left_keys))
            if c.dictionary is None
            and getattr(rx.rex_type(lk), "physical_dtype", None)
            in hashk._KEY_BITS)
        if not ordinals:
            return None, target_plan
        # the usable rows' keys leave the device in a bucket of at most
        # in_list_max rows, whatever the source's capacity: a list is
        # decided by how many rows the source keeps (a HAVING filter over
        # a 1.5M-group aggregate keeps a handful at the aggregate's
        # capacity)
        bucket = max(0, min(conf.in_list_max, src.device.capacity))
        key = self._op_key("rtf_build", reverse, p.left_keys,
                           p.right_keys, ordinals, bucket,
                           tuple((f.name, f.dtype)
                                 for f in src_node.schema))

        def builder():
            bcomp = self._compiler(src, src_node.schema)
            bcompiled = [bcomp.compile(src_keys[i]) for i in ordinals]
            # LEFT key types, matching the join's key-bit convention
            ktypes = [rx.rex_type(p.left_keys[i]) for i in ordinals]

            def fn(scols, ssel):
                kcols = []
                usable = ssel
                for c, kt in zip(bcompiled, ktypes):
                    d, v = c.fn(scols)
                    kcols.append(Column(d, v, kt))
                    if v is not None:
                        usable = usable & v
                res = rtfk.key_stats(kcols, ssel)
                bounds = tuple(rtfk.column_bounds(c.data, usable)
                               for c in kcols)
                return res, bounds, rtfk.key_bucket(kcols, usable, bucket)

            return fn, None

        try:
            fn, _ = self._jitted(key, self._dict_objs(src), builder)
            res, bounds, keys = fn(self._cols(src), src.device.sel)
        except HostFallback:
            return None, target_plan
        # one batched fetch for every host decision value, the key bucket
        # included
        fetched = profiler.host_sync("rtf_build",
                                     (res.n_build, res.ndv, bounds, keys))
        n_build, ndv = int(fetched[0]), int(fetched[1])
        host_bounds, host_keys = fetched[2], fetched[3]
        if n_build < conf.min_build_rows:
            return None, target_plan
        if n_build > 0:
            # a filter cannot prune much when the source's distinct keys
            # rival the filtered side's row count (the PK→PK shape)
            if est_tgt is not None and ndv >= conf.ndv_ratio * est_tgt:
                return None, target_plan
        # every usable key in the bucket: each key column goes out as an
        # exact list (of at most in_list_max distinct values)
        values_by_ord = {
            i: np.unique(np.asarray(host_keys[oi])[:n_build])
            for oi, i in enumerate(ordinals)} if n_build <= bucket else {}
        if n_build == 0:
            # empty build: the device bounds are dtype-extreme sentinels
            # (min > max) which can overflow date literals — an explicit
            # always-false [1, 0] range prunes everything just the same
            bounds_by_ord = {i: (1, 0) for i in ordinals}
        else:
            bounds_by_ord = {i: host_bounds[oi]
                             for oi, i in enumerate(ordinals)}
        pushed = dropped = list_keys = 0
        listed = False
        for t in targets:
            if t.key not in bounds_by_ord:
                continue
            scan = rtfp.find_scan_by_fid(target_plan, t.fid)
            if scan is None:
                continue  # target scan lives outside this plan fragment
            if scan.source is None and scan.format != "parquet":
                continue
            field = scan.schema[t.column]
            if not rtfp.supports_bounds(field.dtype):
                continue
            lo, hi = bounds_by_ord[t.key]
            values = values_by_ord.get(t.key)
            if values is None:
                # bounds alone: the verdict _rtf_finish would reach after
                # the scan decoded under them is reached before it, from
                # the footers; conjuncts that cut next to nothing off the
                # column's range stay out of the scan's fragment key
                cut = _footer_cut_share(scan, field, int(lo), int(hi))
                if cut is not None and cut < conf.min_selectivity:
                    dropped += 1
                    continue
            try:
                conjs = rtfp.bounds_conjuncts(
                    t.column, field, int(lo), int(hi), values)
            except (OverflowError, ValueError):
                continue  # out-of-range literal (exotic date values)
            new_scan = dataclasses.replace(
                scan,
                runtime_predicates=scan.runtime_predicates + conjs)
            target_plan = _replace_node(target_plan, scan, new_scan)
            pushed += 1
            if values is not None:
                listed = True
                list_keys += len(values)
            _record_metric("execution.runtime_filter.pushed_count", 1,
                           site="scan")
        # on the open op.JoinExec span: why a list did or did not go out
        from .. import tracing as tr
        tr.set_attribute("rtf_source_rows", n_build)
        tr.set_attribute("rtf_ndv", ndv)
        tr.set_attribute("rtf_listed", listed)
        tr.set_attribute("rtf_list_keys", list_keys)
        tr.set_attribute("rtf_pushed", pushed)
        tr.set_attribute("rtf_dropped_by_footer", dropped)
        build_s = _time.perf_counter() - t0
        _record_metric("execution.runtime_filter.built_count", 1)
        _record_metric("execution.runtime_filter.build_time", build_s)
        profiler.note_runtime_filter(built=1, pushed=pushed,
                                     build_ms=build_s * 1000.0)
        rtf = _Rtf(fids=tuple(t.fid for t in targets),
                   history_key=hkey, pushed=pushed)
        return rtf, target_plan

    def _rtf_finish(self, rtf: "_Rtf") -> None:
        """Post-join accounting: the adaptive history's verdict on this
        join's filter, from the scan-site pruning of its fids."""
        # adaptive verdict: only SCAN-site pruning pays — fewer rows
        # decode/upload and every downstream kernel runs at the pruned
        # capacity. A filter whose value conjuncts never landed at a
        # scan is pure build overhead and stops rebuilding.
        # Pushed-but-unmeasured scans (parquet behind static predicates)
        # record NO verdict — the filter keeps building rather than
        # being falsely condemned.
        ratio = 0.0
        measured = False
        for fid in rtf.fids:
            st = self._rtf_scan_stats.get(fid)
            if st is not None and st[0] > 0:
                measured = True
                ratio = max(ratio, (st[0] - st[1]) / st[0])
        if rtf.history_key is not None and (measured or rtf.pushed == 0):
            while len(_RTF_HISTORY) > 256:
                _RTF_HISTORY.pop(next(iter(_RTF_HISTORY)))
            _RTF_HISTORY[rtf.history_key] = ratio

    def _compile_join_keys(self, p: pn.JoinExec, left: HostBatch, right: HostBatch,
                           seed: int):
        """Builder for the jitted build+probe phase of an equi-join."""
        def builder():
            lcomp = self._compiler(left, p.left.schema)
            rcomp = self._compiler(right, p.right.schema)
            pairs = []
            for lk, rk in zip(p.left_keys, p.right_keys):
                lc = lcomp.compile(lk)
                rc = rcomp.compile(rk)
                ktype = rx.rex_type(lk)
                luts = None
                if lc.dictionary is not None or rc.dictionary is not None:
                    merged, ra, rb = ai.unify_dictionaries(lc.dictionary,
                                                           rc.dictionary)
                    luts = (jnp.asarray(ra), jnp.asarray(rb))
                    ktype = dt.IntegerType()
                pairs.append((lc, rc, ktype, luts))

            def fn(lcols, lsel, rcols, rsel):
                lkeys, rkeys = [], []
                for lc, rc, ktype, luts in pairs:
                    ld, lv = lc.fn(lcols)
                    rd, rv = rc.fn(rcols)
                    if luts is not None:
                        ld = luts[0][ld]
                        rd = luts[1][rd]
                    lkeys.append(Column(ld, lv, ktype))
                    rkeys.append(Column(rd, rv, ktype))
                bt = joink.build_side(rkeys, rsel, seed)
                ambiguous = joink.hash_ambiguous(bt, rkeys) if not bt.exact \
                    else jnp.asarray(False)
                ranges = joink.probe_ranges(
                    bt, lkeys, lsel, build_key_cols=rkeys if not bt.exact else None)
                has_dup = joink.has_duplicate_build_keys(bt)
                inner_total = joink.join_output_count(ranges, lsel, "inner")
                return (bt.perm, bt.sorted_keys, bt.num_valid,
                        ranges.lo, ranges.cnt, ranges.usable,
                        has_dup, ambiguous, inner_total, bt.exact)

            return fn, None
        return builder

    def _join(self, p: pn.JoinExec, left: HostBatch, right: HostBatch,
              rtf=None) -> HostBatch:
        spilled = self._try_partitioned_join(p, left, right)
        if spilled is not None:
            if rtf is not None:
                # the spill path applies its own exact per-partition
                # masks; the SCAN-site pruning already happened —
                # record its verdict so a useless filter still shuts
                # off adaptively
                self._rtf_finish(rtf)
            return spilled
        jt = p.join_type
        schema_key = (tuple((f.name, f.dtype) for f in p.left.schema),
                      tuple((f.name, f.dtype) for f in p.right.schema))
        dict_objs = self._dict_objs(left) + self._dict_objs(right)
        lcols, lsel = self._cols(left), left.device.sel
        rcols, rsel = self._cols(right), right.device.sel
        import jax

        for seed in range(4):
            key = self._op_key("join_phase", p.left_keys, p.right_keys, seed,
                               schema_key)
            fn, _ = self._jitted(key, dict_objs,
                                 self._compile_join_keys(p, left, right, seed))
            (perm, sorted_keys, num_valid, lo, cnt, usable,
             has_dup_a, ambiguous, inner_total, exact) = fn(
                lcols, lsel, rcols, rsel)
            # one batched fetch for every host decision scalar (each
            # separate blocking read is a device round trip)
            has_dup_a, ambiguous, inner_total, exact = profiler.host_sync(
                "join_phase", (has_dup_a, ambiguous, inner_total, exact))
            if exact or not bool(ambiguous):
                break
        else:
            raise ExecutionError("could not build unambiguous hash join")
        if rtf is not None:
            self._rtf_finish(rtf)
        bt = joink.BuildTable(perm, sorted_keys, bool(exact), num_valid, seed)
        ranges = joink.MatchRanges(lo, cnt, usable)
        merged_dicts = dict(left.dicts)
        right_names = {}
        n_left = len(p.left.schema)
        # rename right columns to combined positions
        r_dev_cols = {}
        for i in range(len(p.right.schema)):
            r_dev_cols[_col_name(n_left + i)] = right.device.columns[_col_name(i)]
            if _col_name(i) in right.dicts:
                merged_dicts[_col_name(n_left + i)] = right.dicts[_col_name(i)]
        build_payload = DeviceBatch(r_dev_cols, right.device.sel)
        build_names = list(r_dev_cols.keys()) if jt not in ("semi", "anti") else []

        has_dup = bool(has_dup_a)
        # full outer always takes the expanding path (it appends unmatched
        # build rows, which the unique fast path cannot express)
        if not has_dup and p.residual is None and jt != "full":
            # exact/seed are baked into ufn's closure (the rebuilt
            # BuildTable), so they MUST ride the key: a repeat execution
            # whose hash build came out non-exact (or on a later seed)
            # would otherwise reuse a program compiled for the other mode
            ukey = self._op_key("join_unique", jt, len(build_names),
                                schema_key, bool(exact), seed)

            def ubuilder():
                def ufn(bt_arrays, ranges_arrays, ldev, bpayload):
                    b_perm, b_keys, b_nvalid = bt_arrays
                    bt_l = joink.BuildTable(perm=b_perm, sorted_keys=b_keys,
                                            exact=bool(exact),
                                            num_valid=b_nvalid, seed=seed)
                    rg = joink.MatchRanges(*ranges_arrays)
                    return joink.join_unique(bt_l, rg, ldev, bpayload, jt,
                                             build_names)
                return ufn, None

            ufn, _ = self._jitted(ukey, dict_objs, ubuilder)
            out_dev = ufn((perm, sorted_keys, num_valid), (lo, cnt, usable),
                          left.device, build_payload)
            _note_join_output(int(inner_total), left.device.capacity,
                              expanded=False)
            out_dicts = merged_dicts if jt not in ("semi", "anti") else left.dicts
            return HostBatch(out_dev, out_dicts)
        from .. import tracing as tr
        total = int(inner_total)
        cap = bucket_capacity(max(total, 1),
                              key=("join-expand", pst.node_fingerprint(p)))
        _note_join_output(total, cap, expanded=True)
        # the expansion runs eagerly: no _jitted program holds it, so no
        # dispatch span covers its gathers. Opened after the note, so the
        # output attributes stay on op.JoinExec
        with tr.span("join.expand", {
                "probe_capacity": left.device.capacity,
                "out_capacity": cap,
                "columns": len(left.device.columns)
                + len(build_payload.columns),
                "join_type": jt,
                "residual": p.residual is not None}):
            return self._join_expand(p, left, bt, ranges, build_payload,
                                     merged_dicts, cap)

    def _try_partitioned_join(self, p: pn.JoinExec, left: HostBatch,
                              right: HostBatch) -> Optional[HostBatch]:
        """Out-of-core partitioned equi-join (reference role: DataFusion's
        spilling hash join via memory pools + temp files, application.yaml
        runtime.* — SURVEY.md §5 long-context analogue).

        When ``out_of_core`` says the join's working set does not fit the
        device (or the inputs exceed an explicit
        ``execution.join_spill_rows``), both sides hash-partition on the
        join keys into temp parquet files; each partition pair joins
        independently (equal keys land in the same partition, so
        inner/left/full/semi/anti are all partition-wise exact), bounding
        the join step's peak memory to one pair plus its expansion. NULL
        keys hash to one partition, preserving outer/anti semantics."""
        if getattr(self, "_in_join_spill", False):
            return None  # partition pairs run the in-memory join
        from .. import tracing as tr
        # what the join phase's sorts run at, whatever the live rows
        tr.set_attribute("probe_capacity", left.device.capacity)
        tr.set_attribute("build_capacity", right.device.capacity)
        if not p.left_keys or p.null_aware:
            return None
        if p.join_type not in ("inner", "left", "full", "semi", "anti"):
            return None
        if not all(isinstance(k, rx.BoundRef)
                   for k in (*p.left_keys, *p.right_keys)):
            # simple column refs only (the planner rewrites casts and
            # expressions above the scan): the host hashes key COLUMNS
            return None
        out_row = _row_bytes(p.left.schema) + (
            0 if p.join_type in ("semi", "anti")
            else _row_bytes(p.right.schema))
        decision = out_of_core(
            "execution.join_spill_rows",
            left.device.capacity + right.device.capacity,
            join_working_set(left.device.capacity, right.device.capacity,
                             out_row))
        if decision is None:
            return None
        n_left, n_right = profiler.host_sync(  # ONE round trip, not two
            "join.spill_decision",
            (jnp.sum(left.device.sel), jnp.sum(right.device.sel)))
        n_left, n_right = int(n_left), int(n_right)
        if decision.by_rows and n_left + n_right <= decision.rows:
            return None
        with tr.span("spill", {"kind": "join",
                               "rows": n_left + n_right}) as sp:
            out = self._partitioned_join(p, left, right, decision.rows,
                                         n_left + n_right, sp)
        if out is not None:
            tr.set_attribute("spilled", True)
        return out

    def _partitioned_join(self, p: pn.JoinExec, left: HostBatch,
                          right: HostBatch, threshold: int, n_rows: int,
                          sp) -> Optional[HostBatch]:
        """The out-of-core work of ``_try_partitioned_join`` under its
        ``spill`` span ``sp``; None declines (keys the host cannot
        hash), and the join runs on the device after all."""
        import tempfile

        import pyarrow as pa
        import pyarrow.compute as pc
        import pyarrow.parquet as pq

        nparts = max(2, min(64, n_rows // max(threshold // 2, 1) + 1))
        lt = ai.to_arrow(left).rename_columns(
            [f.name for f in p.left.schema])
        rt = ai.to_arrow(right).rename_columns(
            [f.name for f in p.right.schema])

        lidx = [k.index for k in p.left_keys]
        ridx = [k.index for k in p.right_keys]
        modes = [_spill_key_mode(lt.column(li).type, rt.column(ri).type)
                 for li, ri in zip(lidx, ridx)]
        lh = _spill_partition_ids(lt, lidx, modes, nparts)
        rh = _spill_partition_ids(rt, ridx, modes, nparts)
        if lh is None or rh is None:
            sp.attributes["declined"] = True
            return None

        tmpdir = tempfile.mkdtemp(prefix="sail_join_spill_")
        self._last_join_spill_dir = tmpdir  # observable in tests
        _record_metric("execution.spill_count", 1, kind="join")
        spill_bytes = 0
        sides = []
        for name, table, h in (("l", lt, lh), ("r", rt, rh)):
            paths = []
            for part in range(nparts):
                mask = h == part
                sub = table.filter(pa.array(mask))
                fp = os.path.join(tmpdir, f"{name}{part}.parquet")
                pq.write_table(sub, fp)
                spill_bytes += os.path.getsize(fp)
                paths.append(fp)
            sides.append(paths)
        profiler.note_spill_bytes(spill_bytes)
        sp.attributes.update(bytes=spill_bytes, partitions=nparts)
        del lt, rt

        from .. import telemetry as tel
        from ..io.prefetch import Prefetcher

        rtf_conf = self._rtf_conf()

        def _empty_side(path):
            return pq.ParquetFile(path).schema_arrow.empty_table()

        def load_pair(part):
            # producer thread: the next partition pair decodes from temp
            # parquet while this thread joins the current pair on device.
            # Parquet footer row counts short-circuit BEFORE any decode:
            # a pair one side of which cannot contribute output skips
            # entirely, and build-empty left/anti/full pairs decode the
            # surviving side alone.
            lp, rp = sides[0][part], sides[1][part]
            ln = pq.ParquetFile(lp).metadata.num_rows
            rn = pq.ParquetFile(rp).metadata.num_rows
            jt = p.join_type
            if jt in ("inner", "semi") and (ln == 0 or rn == 0):
                return None
            if ln == 0 and rn == 0:
                return None
            if jt in ("left", "anti") and ln == 0:
                return None  # output rows come from the left side only
            if jt in ("left", "anti", "full") and rn == 0:
                return pq.read_table(lp), _empty_side(rp)
            if jt == "full" and ln == 0:
                return _empty_side(lp), pq.read_table(rp)
            lsub, rsub = pq.read_table(lp), pq.read_table(rp)
            if jt in ("inner", "semi") and rtf_conf.enabled:
                # runtime-filter the decoded probe chunk against the
                # build partition's exact key set before upload
                lsub = _spill_probe_mask(lsub, lidx, rsub, ridx,
                                         rtf_conf.in_list_max)
            return lsub, rsub

        pf = Prefetcher(range(nparts), transform=load_pair,
                        depth=self._prefetch_depth(), kind="spill_join")
        outs = []
        self._in_join_spill = True
        try:
            with pf:
                for pair in pf:
                    if pair is None:
                        continue
                    lsub, rsub = pair
                    if p.join_type in ("inner", "semi") and \
                            (lsub.num_rows == 0 or rsub.num_rows == 0):
                        continue
                    lhb = _positional(ai.from_arrow(lsub))
                    rhb = _positional(ai.from_arrow(rsub))
                    sub_out = self._join(p, lhb, rhb)
                    outs.append(ai.to_arrow(sub_out))
        finally:
            # the prefetcher is already closed (producer joined) before
            # this cleanup runs, so no reader races the rmtree
            self._in_join_spill = False
            import shutil
            shutil.rmtree(tmpdir, ignore_errors=True)
        tel.note("SpillJoinPrefetch", f"{nparts} partition pairs",
                 **pf.stats.as_extra())
        if not outs:
            schema = p.schema
            empty = pa.table({f"c{i}": pa.array(
                [], type=ai.spec_type_to_arrow(f.dtype))
                for i, f in enumerate(schema)})
            return _positional(ai.from_arrow(empty))
        merged = pa.concat_tables(outs, promote_options="permissive")
        return _positional(ai.from_arrow(merged))

    def _try_external_sort(self, p: pn.SortExec,
                           child: HostBatch) -> Optional[HostBatch]:
        """Out-of-core external sort (reference role: DataFusion's spilling
        ExternalSorter via memory pools + temp files — SURVEY.md §5
        out-of-core).

        Taken when ``out_of_core`` (through ``_sort_out_of_core``) says
        the sort's working set does not fit the device, or the input's
        live rows exceed an explicit ``execution.sort_spill_rows``: the
        wide rows spill to memory-mapped Arrow IPC runs while the
        global permutation is computed on the host from the key columns
        alone (a small fraction of the row width). The output gathers
        straight from the memory maps, so the O(n) sort workspace — the
        permuted column copies a device lexsort would materialize — never
        touches device HBM. Spark ordering semantics: nulls_first/last per
        key, NaN sorts greater than any non-null value (after +Inf)."""
        decision = self._sort_out_of_core(p, child)
        if decision is None:
            return None
        n = int(profiler.host_sync("sort.spill_decision",
                                   jnp.sum(child.device.sel)))
        if decision.by_rows and n <= decision.rows:
            return None
        from .. import tracing as tr
        with tr.span("spill", {"kind": "sort", "rows": n}) as sp:
            out = self._external_sort(p, child, decision.rows, n, sp)
        if out is not None:
            tr.set_attribute("spilled", True)
        return out

    def _external_sort(self, p: pn.SortExec, child: HostBatch,
                       threshold: int, n: int, sp) -> Optional[HostBatch]:
        """The out-of-core work of ``_try_external_sort`` under its
        ``spill`` span ``sp``; None declines (a key type the host sort
        does not order), and the sort runs on the device after all."""
        import shutil
        import tempfile

        import pandas as pd
        import pyarrow as pa
        import pyarrow.compute as pc
        import pyarrow.ipc as ipc

        table = ai.to_arrow(child)

        # -- sort-key frame (host memory; declines on exotic key types) --
        frame: Dict[str, object] = {}
        by: List[str] = []
        asc: List[bool] = []
        for i, k in enumerate(p.keys):
            col = table.column(k.expr.index).combine_chunks()
            if pa.types.is_dictionary(col.type):
                col = col.cast(col.type.value_type)
            t = col.type
            if not (pa.types.is_integer(t) or pa.types.is_floating(t)
                    or pa.types.is_boolean(t) or pa.types.is_string(t)
                    or pa.types.is_large_string(t) or pa.types.is_binary(t)
                    or pa.types.is_decimal(t) or pa.types.is_temporal(t)):
                sp.attributes["declined"] = True
                return None
            null_mask = col.is_null().to_numpy(zero_copy_only=False)
            # nulls_first/last is independent of the key direction: the
            # null rank column always sorts ascending. Unset → Spark
            # default (ASC: NULLS FIRST, DESC: NULLS LAST).
            nulls_first = (k.nulls_first if k.nulls_first is not None
                           else k.ascending)
            frame[f"n{i}"] = ~null_mask if nulls_first else null_mask
            by.append(f"n{i}")
            asc.append(True)
            if pa.types.is_floating(t):
                # NaN (non-null) outranks every value including +Inf; the
                # rank column isolates it so the filled 0.0 can't leak in
                vals = col.to_numpy(zero_copy_only=False).astype(
                    np.float64, copy=True)
                nan_mask = np.isnan(vals) & ~null_mask
                frame[f"f{i}"] = nan_mask
                by.append(f"f{i}")
                asc.append(k.ascending)
                vals[np.isnan(vals)] = 0.0
                frame[f"k{i}"] = vals
            else:
                if null_mask.any():
                    non_null = col.drop_null()
                    if len(non_null) == 0:
                        continue  # all null: the null rank decides alone
                    col = pc.fill_null(col, non_null[0])
                frame[f"k{i}"] = col.to_pandas()
            by.append(f"k{i}")
            asc.append(k.ascending)

        from .. import telemetry as tel
        from ..io.prefetch import Prefetcher

        tmpdir = tempfile.mkdtemp(prefix="sail_sort_spill_")
        self._last_sort_spill_dir = tmpdir  # observable in tests
        _record_metric("execution.spill_count", 1, kind="sort")
        try:
            # -- spill the wide rows to memory-mappable runs, in the
            # background: the run data is already on disk once written, so
            # the queue carries only paths and the producer never needs to
            # stall — pass the full run count as depth (0 still disables)
            run_rows = max(1, threshold // 2)
            starts = list(enumerate(range(0, n, run_rows)))

            def write_run(i_start):
                i, start = i_start
                fp = os.path.join(tmpdir, f"run{i}.arrow")
                with pa.OSFile(fp, "wb") as f, \
                        ipc.new_file(f, table.schema) as writer:
                    writer.write_table(table.slice(start, run_rows))
                return fp

            depth = self._prefetch_depth()
            with Prefetcher(starts, transform=write_run,
                            depth=0 if depth <= 0 else len(starts),
                            kind="spill_sort") as pf:
                # the global key permutation computes WHILE runs spill
                perm = pd.DataFrame(frame).sort_values(
                    by, ascending=asc, kind="stable").index.to_numpy()
                if p.limit is not None:
                    perm = perm[:p.limit]
                paths = list(pf)
            del table
            spill_bytes = sum(os.path.getsize(fp) for fp in paths)
            profiler.note_spill_bytes(spill_bytes)
            sp.attributes.update(bytes=spill_bytes, partitions=len(paths))
            tel.note("SpillSortPrefetch", f"{len(paths)} runs",
                     **pf.stats.as_extra())

            # -- gather output rows straight off the memory maps --
            runs = [ipc.open_file(pa.memory_map(fp, "r")).read_all()
                    for fp in paths]
            out = pa.concat_tables(runs).take(
                pa.array(perm, type=pa.int64()))
            out = out.combine_chunks()  # own the buffers before cleanup
            return _positional(ai.from_arrow(out))
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)

    def _join_expand(self, p: pn.JoinExec, left: HostBatch, bt, ranges,
                     build_payload, merged_dicts, cap: int) -> HostBatch:
        jt = p.join_type
        n_left = len(p.left.schema)
        res = joink.join_expand(bt, ranges, left.device, build_payload,
                                "inner", list(build_payload.columns.keys()),
                                cap)
        exp_batch, pi, is_match = res.batch, res.probe_index, res.is_match
        bix = res.build_index
        ok = exp_batch.sel
        if p.residual is not None:
            comb_schema = tuple(p.left.schema) + tuple(p.right.schema)
            comp = ExprCompiler([f.dtype for f in comb_schema],
                                {i: merged_dicts[_col_name(i)]
                                 for i in range(len(comb_schema))
                                 if _col_name(i) in merged_dicts},
                                self._subquery_cache)
            c = comp.compile(p.residual)
            cols = [(exp_batch.columns[_col_name(i)].data,
                     exp_batch.columns[_col_name(i)].validity)
                    for i in range(len(comb_schema))]
            rdat, rval = c.fn(cols)
            res_ok = rdat.astype(jnp.bool_)
            if rval is not None:
                res_ok = res_ok & rval
            ok = ok & res_ok
        if jt == "inner":
            return HostBatch(exp_batch.with_sel(ok), merged_dicts)
        # probe rows with >= 1 surviving match
        probe_cap = left.device.capacity
        matched_probe = jnp.zeros(probe_cap, dtype=jnp.bool_).at[pi].max(
            ok, mode="drop")
        if jt == "semi":
            return HostBatch(left.device.with_sel(left.device.sel & matched_probe),
                             left.dicts)
        if jt == "anti":
            return HostBatch(left.device.with_sel(left.device.sel & ~matched_probe),
                             left.dicts)
        if jt in ("left", "full"):
            # surviving inner rows + unmatched probe rows with null build cols
            unmatched = left.device.sel & ~matched_probe
            out_cap = cap + probe_cap
            cols = {}
            for i in range(n_left):
                key = _col_name(i)
                ec = exp_batch.columns[key]
                lc = left.device.columns[key]
                data = jnp.concatenate([ec.data, lc.data])
                validity = None
                if ec.validity is not None or lc.validity is not None:
                    ev = ec.validity if ec.validity is not None else \
                        jnp.ones(cap, dtype=jnp.bool_)
                    lv = lc.validity if lc.validity is not None else \
                        jnp.ones(probe_cap, dtype=jnp.bool_)
                    validity = jnp.concatenate([ev, lv])
                cols[key] = Column(data, validity, ec.dtype)
            for key in build_payload.columns:
                ec = exp_batch.columns[key]
                pad_v = jnp.zeros(probe_cap, dtype=jnp.bool_)
                ev = ec.validity if ec.validity is not None else \
                    jnp.ones(cap, dtype=jnp.bool_)
                cols[key] = Column(
                    jnp.concatenate([ec.data, jnp.zeros(probe_cap, dtype=ec.data.dtype)]),
                    jnp.concatenate([ev, pad_v]), ec.dtype)
            sel = jnp.concatenate([ok, unmatched])
            out = DeviceBatch(cols, sel)
            if jt == "full":
                out = self._append_unmatched_build(
                    out, p, bt, ranges, left, build_payload, ok, bix,
                    has_residual=p.residual is not None)
            return HostBatch(out, merged_dicts)
        raise ExecutionError(f"join type {jt!r} not implemented")

    def _append_unmatched_build(self, out: DeviceBatch, p, bt, ranges, left,
                                build_payload, ok, bix,
                                has_residual=False) -> DeviceBatch:
        if has_residual:
            # A build row counts as matched only if at least one of its
            # expanded rows survived the residual filter; scatter the
            # surviving flags back to build positions.
            bcap0 = build_payload.sel.shape[0]
            matched_build = jnp.zeros(bcap0, dtype=jnp.bool_).at[bix].max(
                ok, mode="drop")
        else:
            matched_build = joink.build_matched_mask(bt, ranges, left.device.sel)
        unmatched = build_payload.sel & ~matched_build
        n_left = len(p.left.schema)
        bcap = matched_build.shape[0]
        cols = {}
        for i in range(n_left):
            key = _col_name(i)
            c = out.columns[key]
            cols[key] = Column(
                jnp.concatenate([c.data, jnp.zeros(bcap, dtype=c.data.dtype)]),
                jnp.concatenate([c.validity if c.validity is not None
                                 else jnp.ones(c.data.shape[0], dtype=jnp.bool_),
                                 jnp.zeros(bcap, dtype=jnp.bool_)]), c.dtype)
        for key, c in build_payload.columns.items():
            oc = out.columns[key]
            v = c.validity if c.validity is not None else jnp.ones(bcap, dtype=jnp.bool_)
            cols[key] = Column(
                jnp.concatenate([oc.data, c.data]),
                jnp.concatenate([oc.validity if oc.validity is not None
                                 else jnp.ones(oc.data.shape[0], dtype=jnp.bool_), v]),
                c.dtype)
        sel = jnp.concatenate([out.sel, unmatched])
        return DeviceBatch(cols, sel)

    def _cross_join(self, p: pn.JoinExec, left: HostBatch, right: HostBatch) -> HostBatch:
        import jax
        n_left_rows, n_right_rows = (
            int(x) for x in profiler.host_sync(
                "cross_join.capacity", (left.device.num_rows(),
                                        right.device.num_rows())))
        total = n_left_rows * n_right_rows
        cap = bucket_capacity(max(total, 1),
                              key=("cross-join", pst.node_fingerprint(p)))
        lcomp = sortk.compact(left.device)
        rcomp_d = sortk.compact(right.device)
        idx = jnp.arange(cap, dtype=jnp.int32)
        li = jnp.clip(idx // max(n_right_rows, 1), 0, left.device.capacity - 1)
        ri = jnp.clip(idx % max(n_right_rows, 1), 0, right.device.capacity - 1)
        sel = idx < total
        cols = {}
        n_left = len(p.left.schema)
        for i in range(n_left):
            c = lcomp.columns[_col_name(i)]
            cols[_col_name(i)] = Column(c.data[li],
                                        None if c.validity is None else c.validity[li],
                                        c.dtype)
        dicts = dict(left.dicts)
        for i in range(len(p.right.schema)):
            c = rcomp_d.columns[_col_name(i)]
            cols[_col_name(n_left + i)] = Column(
                c.data[ri], None if c.validity is None else c.validity[ri], c.dtype)
            if _col_name(i) in right.dicts:
                dicts[_col_name(n_left + i)] = right.dicts[_col_name(i)]
        return HostBatch(DeviceBatch(cols, sel), dicts)

    # ------------------------------------------------------------------
    def _exec_WindowExec(self, p: pn.WindowExec) -> HostBatch:
        from ..ops import window as wink
        from ..ops.sort import order_bits
        child = self.run(p.input)
        dev = child.device
        in_schema = p.input.schema

        def builder():
            # precompute rank LUTs for dictionary-encoded order keys
            order_luts: Dict[int, jnp.ndarray] = {}
            for s in p.windows:
                for k in s.order_keys:
                    i = k.expr.index
                    name = _col_name(i)
                    if name in child.dicts and i not in order_luts:
                        order_luts[i] = jnp.asarray(
                            ai.dictionary_ranks(child.dicts[name]))
            # translate string lag/lead defaults to dictionary codes,
            # extending the dictionary when the default is unseen
            lag_defaults: Dict[int, object] = {}
            extended_dicts: Dict[int, pa.Array] = {}
            for j, s in enumerate(p.windows):
                opts = dict(s.options)
                default = opts.get("default")
                if s.function in ("lag", "lead") and isinstance(default, str):
                    src = _col_name(s.arg)
                    if src not in child.dicts:
                        raise ExecutionError(
                            f"{s.function}() string default over a "
                            f"non-string column")
                    vals = child.dicts[src].cast(pa.string()).to_pylist()
                    if default in vals:
                        lag_defaults[j] = vals.index(default)
                    else:
                        extended_dicts[j] = pa.array(vals + [default])
                        lag_defaults[j] = len(vals)
                elif s.function in ("lag", "lead"):
                    lag_defaults[j] = default

            def fn(cols, sel):
                ctx_cache = {}
                outs = []
                for j, s in enumerate(p.windows):
                    pkey = tuple(s.partition_indices)
                    okey = tuple((k.expr.index, k.ascending, k.nulls_first)
                                 for k in s.order_keys)
                    ck = (pkey, okey)
                    if ck not in ctx_cache:
                        part_cols = [Column(cols[i][0], cols[i][1],
                                            in_schema[i].dtype)
                                     for i in s.partition_indices]
                        order_keys = []
                        for k in s.order_keys:
                            i = k.expr.index
                            d, v = cols[i]
                            kdt = in_schema[i].dtype
                            if i in order_luts:
                                d = order_luts[i][d]
                                kdt = dt.IntegerType()
                            order_keys.append((d, v, kdt, k.ascending,
                                               k.nulls_first))
                        ctx = wink.build_window_context(part_cols, order_keys,
                                                        sel)
                        okbits = [(order_bits(d[ctx.perm], kdt, asc),
                                   None if v is None else v[ctx.perm])
                                  for (d, v, kdt, asc, nf) in order_keys]
                        ctx_cache[ck] = (ctx, okbits)
                    ctx, okbits = ctx_cache[ck]
                    opts = dict(s.options)
                    fnname = s.function
                    if fnname == "row_number":
                        outs.append((wink.row_number(ctx), None))
                    elif fnname == "rank":
                        outs.append((wink.rank(ctx, okbits), None))
                    elif fnname == "dense_rank":
                        outs.append((wink.dense_rank(ctx, okbits), None))
                    elif fnname == "percent_rank":
                        outs.append((wink.percent_rank(ctx, okbits), None))
                    elif fnname == "cume_dist":
                        outs.append((wink.cume_dist(ctx, okbits), None))
                    elif fnname == "ntile":
                        outs.append((wink.ntile(ctx, int(opts["n"])), None))
                    elif fnname in ("lag", "lead"):
                        arg = Column(cols[s.arg][0], cols[s.arg][1],
                                     in_schema[s.arg].dtype)
                        d, v = wink.shift(ctx, arg, int(opts["offset"]),
                                          lag_defaults.get(j))
                        outs.append((d, v))
                    elif fnname == "nth_value":
                        arg = Column(cols[s.arg][0], cols[s.arg][1],
                                     in_schema[s.arg].dtype)
                        peer = None
                        if s.frame_type == "range" or s.frame_lower is None:
                            peer = wink.peer_group_end(ctx, okbits)
                        d, v = wink.nth(ctx, arg, int(opts["n"]), peer)
                        outs.append((d, v))
                    else:
                        fnk = s.function
                        arg = None
                        inv_lut = None
                        if s.arg is not None:
                            adata, avalid = cols[s.arg]
                            adt = in_schema[s.arg].dtype
                            name = _col_name(s.arg)
                            if name in child.dicts and fnk in ("min", "max"):
                                # compare string codes in rank order, then
                                # map the winning rank back to a code
                                ranks = ai.dictionary_ranks(child.dicts[name])
                                inv = np.empty_like(ranks)
                                inv[ranks] = np.arange(len(ranks), dtype=ranks.dtype)
                                adata = jnp.asarray(ranks)[adata]
                                adt = dt.IntegerType()
                                inv_lut = jnp.asarray(inv)
                            arg = Column(adata, avalid, adt)
                        peer = None
                        if s.frame_type == "range":
                            if s.frame_lower is None and s.frame_upper == 0:
                                peer = wink.peer_group_end(ctx, okbits)
                            elif not (s.frame_lower is None and s.frame_upper is None):
                                raise ExecutionError(
                                    "RANGE frames with value offsets are not "
                                    "supported yet")
                        d, v = wink.framed_agg(ctx, arg, fnk,
                                               s.frame_lower, s.frame_upper,
                                               peer)
                        if inv_lut is not None:
                            d = inv_lut[jnp.clip(d, 0, inv_lut.shape[0] - 1)]
                        if fnk == "avg" and s.arg is not None and \
                                isinstance(in_schema[s.arg].dtype, dt.DecimalType):
                            d = d / (10.0 ** in_schema[s.arg].dtype.scale)
                        outs.append((d, v))
                return tuple(outs)

            return fn, extended_dicts

        key = self._op_key("window", p.windows,
                           tuple((f.name, f.dtype) for f in in_schema))
        fn, extended_dicts = self._jitted(key, self._dict_objs(child), builder)
        results = fn(self._cols(child), dev.sel)
        cols = dict(dev.columns)
        out_dicts = dict(child.dicts)
        n_in = len(in_schema)
        for j, (s, (d, v)) in enumerate(zip(p.windows, results)):
            keyn = _col_name(n_in + j)
            jdt = physical_jnp_dtype(s.out_dtype)
            if d.dtype != jnp.dtype(jdt):
                d = d.astype(jdt)
            cols[keyn] = Column(d, v, s.out_dtype)
            if s.arg is not None and s.function in ("lag", "lead", "min",
                                                    "max", "first", "last",
                                                    "nth_value"):
                src = _col_name(s.arg)
                if extended_dicts and j in extended_dicts:
                    out_dicts[keyn] = extended_dicts[j]
                elif src in child.dicts:
                    out_dicts[keyn] = child.dicts[src]
        return HostBatch(DeviceBatch(cols, dev.sel), out_dicts)

    def _exec_UnionExec(self, p: pn.UnionExec) -> HostBatch:
        parts = [self.run(c) for c in p.inputs]
        ncols = len(p.schema)
        total_cap = sum(b.device.capacity for b in parts)
        cols = {}
        dicts = {}
        for i in range(ncols):
            key = _col_name(i)
            f = p.schema[i]
            str_col = any(key in b.dicts for b in parts)
            if str_col and isinstance(f.dtype, (dt.ArrayType, dt.MapType,
                                                dt.StructType)):
                # complex dictionaries: concatenate with offset remapping
                import pyarrow as pa
                offset = 0
                datas = []
                chunks = []
                at = ai.spec_type_to_arrow(f.dtype)
                for b in parts:
                    d_b = b.dicts[key]
                    chunks.append(d_b)
                    datas.append(b.device.columns[key].data + offset)
                    offset += len(d_b)
                # unify branch nullability (e.g. struct<x not null> vs
                # struct<x>) to the union output type before concatenating
                dicts[key] = pa.concat_arrays(
                    [(c.combine_chunks() if isinstance(c, pa.ChunkedArray)
                      else c).cast(at) for c in chunks])
            elif str_col:
                from ..plan.compiler import _merge_dicts
                merged, remaps = _merge_dicts([b.dicts[key] for b in parts])
                datas = [jnp.asarray(rm)[b.device.columns[key].data]
                         for rm, b in zip(remaps, parts)]
                dicts[key] = merged
            else:
                jdt = physical_jnp_dtype(f.dtype)
                datas = [b.device.columns[key].data.astype(jdt) for b in parts]
            data = jnp.concatenate(datas)
            validities = []
            has_v = any(b.device.columns[key].validity is not None for b in parts)
            if has_v:
                for b in parts:
                    v = b.device.columns[key].validity
                    validities.append(v if v is not None else
                                      jnp.ones(b.device.capacity, dtype=jnp.bool_))
                validity = jnp.concatenate(validities)
            else:
                validity = None
            cols[key] = Column(data, validity, f.dtype)
        sel = jnp.concatenate([b.device.sel for b in parts])
        return HostBatch(DeviceBatch(cols, sel), dicts)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# the out-of-core decision of a join and a sort
# ---------------------------------------------------------------------------

#: share of the device's free memory an operator's working set may
#: take. The quarter left over is headroom for what the estimates below
#: do not count: XLA's own scratch, fragmentation of the allocator, and
#: the operator above, which starts while this one's output is alive
_SPILL_FREE_SHARE = 0.75
#: what the join phase's merge allocates per row of build and probe
#: keys sorted together (ops/join.py _merge_ranges): operand and result
#: of the sort of key + row number (2 x 12), the running count of build
#: rows (4), its value at the start of the key's run (4) and their
#: difference (4); the sort back to probe order reuses the first one's
#: space
_JOIN_MERGE_ROW_BYTES = 36
#: per PROBE row beside its inputs (ops/join.py probe_ranges): the
#: packed or hashed key (8), lo and cnt (2 x 4), usable (1), and its
#: row of the merge
_JOIN_PROBE_ROW_BYTES = 17 + _JOIN_MERGE_ROW_BYTES
#: per BUILD row (ops/join.py build_side): the key (8), its sorted copy
#: (8), operand and result of the one sort that carries dead flag, key
#: and row number (2 x 13), the permutation (4) and usable (1) come to
#: 47 of the 69 charged, the rest is margin; and its row of the merge
_JOIN_BUILD_ROW_BYTES = 69 + _JOIN_MERGE_ROW_BYTES
#: per row of one stable pass of a sort (ops/sort.py sort_pass): order
#: bits (8), their gather through the permutation (8), operand and
#: result of the argsort (2 x 12), the permutation before and after
#: (2 x 4); the passes of a lexicographic sort run one after another
_SORT_PASS_ROW_BYTES = 48


def _row_bytes(schema) -> int:
    """Bytes a copy of one row of a batch with this plan schema takes
    on the device at most: each column's value and a validity byte."""
    total = 0
    for f in schema:
        try:
            total += physical_jnp_dtype(f.dtype).itemsize + 1
        except TypeError:  # no device representation: a host handle
            total += 9
    return total


def join_working_set(probe_capacity: int, build_capacity: int,
                     out_row_bytes: int) -> int:
    """Upper bound of what an equi-join allocates on the device beside
    its inputs, from capacities alone (no device sync): the join phase
    over both sides, and an output of the probe's capacity whose rows
    take ``out_row_bytes``: a jitted program copies the probe's columns
    into its result, and the build side's payload columns come with a
    validity byte each (the unique-build path; an expanding join sizes
    its own output after the phase's sync). Asked of the v5e's compiler
    for Q3's two join phases at SF10 as the executor runs them: 1.30 GB
    for the lineitem join (a 1.5Mi-row probe, the 32Mi-row lineitem as
    the build: 0.88 GB of temporaries and 0.42 GB of results) against
    3.61 GB here, and 0.25 GB for the orders join (a 0.3Mi-row probe, an
    8Mi-row build: 0.15 and 0.10 GB) against 0.90."""
    return (probe_capacity * (_JOIN_PROBE_ROW_BYTES + out_row_bytes)
            + build_capacity * _JOIN_BUILD_ROW_BYTES)


def sort_working_set(capacity: int, row_bytes: int) -> int:
    """Upper bound of what a device sort allocates beside its input: one
    pass of the lexicographic sort at a time, and the gathered copy of
    every column plus the selection."""
    return capacity * (_SORT_PASS_ROW_BYTES + row_bytes + 1)


def _device_memory_stats() -> Optional[dict]:
    """The allocator's counters of the device the executor's arrays
    live on; None on the CPU backend."""
    import jax
    return jax.devices()[0].memory_stats()


def device_free_bytes() -> Optional[int]:
    """``bytes_limit - bytes_in_use`` of the device; None where the
    platform reports no memory."""
    stats = _device_memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


class OutOfCore(NamedTuple):
    """A decision to leave the device. ``rows``: what one partition pair
    of the join, or two runs of the sort, may hold. ``by_rows``: the
    count was set explicitly, so an input whose LIVE rows stay under it
    is kept on the device after all (one sync decides)."""

    rows: int
    by_rows: bool


def out_of_core(rows_key: str, capacity: int,
                working_set: int) -> Optional[OutOfCore]:
    """THE out-of-core decision, for a join and a sort alike; None keeps
    the operator on the device, and costs no device sync.

    ``rows_key`` (``execution.join_spill_rows`` /
    ``execution.sort_spill_rows``) set, in the configuration or as
    ``SAIL_EXECUTION__JOIN_SPILL_ROWS``: spill above that many rows, 0
    never. Unset, the default: spill when ``working_set`` (bytes, an
    upper bound reckoned from capacities and column widths) exceeds
    ``_SPILL_FREE_SHARE`` of the device's free memory; where the
    platform reports no memory nothing spills. The decision's inputs
    land on the open ``op.<PlanNode>`` span."""
    from .. import tracing as tr
    from ..config import get as config_get
    tr.set_attribute("working_set_bytes", int(working_set))
    tr.set_attribute("spilled", False)
    try:
        rows = int(config_get(rows_key))
    except (TypeError, ValueError):
        rows = None  # unset (or unreadable): by memory
    if rows is not None:
        if rows <= 0 or capacity <= rows:
            # capacity bounds live rows: the spill could never engage
            return None
        return OutOfCore(rows, True)
    free = device_free_bytes()
    if free is None:
        return None
    budget = int(free * _SPILL_FREE_SHARE)
    tr.set_attribute("free_bytes", free)
    tr.set_attribute("budget_bytes", budget)
    if working_set <= budget:
        return None
    # the rows whose share of the working set fits the budget
    return OutOfCore(max(1, capacity * max(budget, 0) // working_set), False)


def _spill_key_mode(lt_type: "pa.DataType", rt_type: "pa.DataType") -> str:
    """Hash family for one spill-join key PAIR, agreed by both sides:
    integral keys hash exactly as int64 (float64 canonicalization would
    collapse int64 keys above 2^53 — adjacent keys share a double — and
    skew partition sizes); the float64 path is reserved for float inputs;
    everything else hashes its canonical string form."""
    def one(t):
        if pa.types.is_floating(t):
            return "float"
        if pa.types.is_integer(t) or pa.types.is_boolean(t):
            return "int"
        return "str"

    ml, mr = one(lt_type), one(rt_type)
    if "float" in (ml, mr):
        return "float"
    if ml == mr == "int":
        return "int"
    return "str"


# all NULL keys land in one partition regardless of hash family
_SPILL_NULL_HASH = np.uint64(0x9E3779B97F4A7C15)


def _spill_partition_ids(table: "pa.Table", idx, modes, nparts: int):
    """Partition ids from key VALUES (stable across both sides —
    dictionary codes are not). None → decline the spill path."""
    import pandas as pd
    import pyarrow.compute as pc

    h = None
    for i, mode in zip(idx, modes):
        col = table.column(i).combine_chunks()
        null_mask = None
        if mode == "float":
            # canonical float64: a NULLABLE int side otherwise hashes as
            # float-with-NaN while the other side hashes as int — same
            # value, different partition. Spark join equality:
            # -0.0 == 0.0 (+ 0.0 normalizes the sign) and NaN == NaN
            # (one canonical payload) — mirrors ops/hash.py
            # _normalize_float.
            vals = col.to_numpy(zero_copy_only=False) \
                .astype(np.float64) + 0.0
            vals[np.isnan(vals)] = np.nan
        elif mode == "int":
            # promote to the common integer width; exact above 2^53
            null_mask = col.is_null().to_numpy(zero_copy_only=False)
            vals = pc.fill_null(col.cast(pa.int64(), safe=False), 0) \
                .to_numpy(zero_copy_only=False)
        else:
            # strings/dates/decimals: canonical string form; anything
            # uncastable declines the spill path
            try:
                vals = pc.cast(col, pa.string()).to_numpy(
                    zero_copy_only=False)
            except Exception:  # noqa: BLE001
                return None
        part = pd.util.hash_array(vals, categorize=False) \
            .astype(np.uint64)
        if null_mask is not None and null_mask.any():
            part[null_mask] = _SPILL_NULL_HASH
        h = part if h is None else (h * np.uint64(31) + part)
    return (h % np.uint64(nparts)).astype(np.int64)


def _note_scan(p: pn.ScanExec, hb: HostBatch, rows: int,
               fragment: str) -> None:
    """On the open ``op.ScanExec`` span: the fragment the scan runs on:
    its ``rows``, the ``capacity`` and ``bytes`` its batch takes on the
    device, whether this statement ``decoded`` it, found it in the
    fragment cache (``hit``) or attached to another statement's decode
    (``shared``), and how many ``runtime_conjuncts`` its key carries."""
    from .. import tracing as tr
    tr.set_attribute("rows", rows)
    tr.set_attribute("capacity", hb.device.capacity)
    tr.set_attribute("bytes", hb.device.nbytes)
    tr.set_attribute("fragment", fragment)
    tr.set_attribute("runtime_conjuncts", len(p.runtime_predicates))


def _direct_domains(p: pn.AggregateExec, in_schema,
                    top_dicts: Dict[str, pa.Array]) -> Optional[List[int]]:
    """The group keys' domains where every key's is small and known
    (dictionary codes, booleans) and the bins they make number at most
    4096: the input of direct binning (``aggk.group_rows_direct``).
    None where the aggregate groups by sorting, or has no key."""
    domains = []
    for gi in p.group_indices:
        name = _col_name(gi)
        if name in top_dicts:
            domains.append(len(top_dicts[name]))
        elif isinstance(in_schema[gi].dtype, dt.BooleanType):
            domains.append(2)
        else:
            return None
    bins = 1
    for d in domains:
        bins *= d + 1
    return domains if domains and bins <= 4096 else None


def _note_join_output(rows: int, capacity: int, *, expanded: bool) -> None:
    """On the open ``op.JoinExec`` span: ``out_rows``, the inner matches
    the ``join_phase`` sync fetched anyway (no sync of its own),
    ``out_capacity``, the rows the join's output batch is allocated for:
    the expansion's bucket, or the probe's capacity where no build key
    repeats, and ``expanded``, whether ``join_expand`` wrote that batch. A
    plan that expands shows here and not only as time."""
    from .. import tracing as tr
    tr.set_attribute("out_rows", rows)
    tr.set_attribute("out_capacity", capacity)
    tr.set_attribute("expanded", expanded)


def _footer_cut_share(scan: pn.ScanExec, field: pn.Field,
                      lo: int, hi: int) -> Optional[float]:
    """The share of a scan column's range that the closed bounds
    [``lo``, ``hi``] (raw physical values: days for a date) cut off:
    1 - overlap / (max - min + 1), the column's min and max read from
    the footers of EVERY file the Parquet scan reads
    (``METADATA_CACHE.column_stats``; no column decoded). None where
    that cannot be said: a memory source, another format, a file whose
    footer gives the column no min and max."""
    if scan.source is not None or scan.format != "parquet":
        return None
    try:
        from ..io.cache import METADATA_CACHE
        from ..io.formats import expand_paths
        stats = [METADATA_CACHE.column_stats(f, field.name)
                 for f in expand_paths(scan.paths)]
    except Exception:  # noqa: BLE001 — the statistics are advisory
        return None
    if not stats or any(st is None or st.lo is None for st in stats):
        return None

    def raw(v):
        return (v - datetime.date(1970, 1, 1)).days \
            if isinstance(v, datetime.date) else int(v)

    fmin = min(raw(st.lo) for st in stats)
    fmax = max(raw(st.hi) for st in stats)
    overlap = max(0, min(hi, fmax) - max(lo, fmin) + 1)
    return 1.0 - overlap / (fmax - fmin + 1)


def _rtf_est_rows(p: pn.PlanNode) -> float:
    """Runtime-filter direction estimate: join_reorder's cardinality
    model, except cross joins count as the cartesian PRODUCT (GOO's max
    is fine for ordering decisions but makes a 250k-row cross product
    look like its 2.5k-row side, steering the filter the wrong way).
    Observed cardinalities from completed cluster stages (the adaptive
    stats-feedback loop) take precedence over the static model."""
    from ..plan import join_reorder as jr

    obs = jr.observed_rows(p)
    if obs is not None:
        return obs
    if isinstance(p, pn.JoinExec):
        lr, rr = _rtf_est_rows(p.left), _rtf_est_rows(p.right)
        if p.join_type in ("semi", "anti"):
            return lr * 0.5
        if p.join_type == "cross" or not p.left_keys:
            return lr * rr
        return max(lr, rr)
    if isinstance(p, pn.FilterExec):
        return _rtf_est_rows(p.input) * jr._conjunct_selectivity(
            p.condition)
    if isinstance(p, pn.AggregateExec):
        return max(_rtf_est_rows(p.input) * 0.1, 1.0)
    if isinstance(p, pn.UnionExec):
        return sum(_rtf_est_rows(c) for c in p.inputs)
    if isinstance(p, pn.ScanExec):
        return jr._scan_rows(p)
    child = getattr(p, "input", None)
    if isinstance(child, pn.PlanNode):
        return _rtf_est_rows(child)
    return jr._DEFAULT_ROWS


def _spill_probe_mask(lsub: "pa.Table", lidx, rsub: "pa.Table", ridx,
                      cap: int) -> "pa.Table":
    """Spill-join runtime filter: exact build-partition key membership
    applied to the probe partition before upload (inner/semi only).
    Multi-key joins intersect per-column membership — a superset of the
    true match set, so the mask is sound; NULL keys drop (they cannot
    equi-match). Skips columns whose distinct build keys exceed ``cap``
    and float keys (NaN set semantics differ from Spark's NaN ≡ NaN)."""
    import pyarrow.compute as pc

    mask = None
    for li, ri in zip(lidx, ridx):
        rcol = rsub.column(ri)
        t = rcol.type
        if not (pa.types.is_integer(t) or pa.types.is_boolean(t)
                or pa.types.is_string(t) or pa.types.is_large_string(t)
                or pa.types.is_date(t) or pa.types.is_decimal(t)):
            continue
        try:
            vals = pc.unique(rcol.combine_chunks())
            if len(vals) > cap:
                continue
            m = pc.is_in(lsub.column(li), value_set=vals)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError,
                pa.ArrowTypeError):
            continue
        mask = m if mask is None else pc.and_kleene(mask, m)
    if mask is None:
        return lsub
    before = lsub.num_rows
    out = lsub.filter(mask)  # null-mask rows drop with the non-members
    pruned = before - out.num_rows
    if pruned > 0:
        _record_metric("execution.runtime_filter.rows_pruned", pruned,
                       site="spill")
        _record_metric("execution.runtime_filter.pushed_count", 1,
                       site="spill")
    return out


def _apply_runtime_predicates(table: pa.Table, preds, schema):
    """Host-side application of runtime join-filter conjuncts to an
    in-memory Arrow table (order-preserving, so downstream results are
    bit-identical with filtering off). Returns (table, (before, after))
    or (table, None) when the conjuncts fail to convert."""
    from ..io.formats import rex_predicates_to_arrow

    expr = rex_predicates_to_arrow(preds, schema)
    if expr is None:
        return table, None
    before = table.num_rows
    try:
        table = table.filter(expr)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, TypeError):
        return table, None  # advisory: an unapplied filter is still sound
    return table, (before, table.num_rows)


def _drop_mem_scan_entry(table: pa.Table) -> None:
    """Evict one in-memory table's fragment-cache entries (chunk
    pipelines would otherwise pin every decoded chunk in HBM)."""
    from .result_cache import FRAGMENT_CACHE
    FRAGMENT_CACHE.drop_mem(id(table))


def _positional(hb: HostBatch) -> HostBatch:
    """Rename columns to positional keys c0..cn."""
    dev = hb.device
    cols = {}
    dicts = {}
    for i, (name, col) in enumerate(dev.columns.items()):
        cols[_col_name(i)] = col
        if name in hb.dicts:
            dicts[_col_name(i)] = hb.dicts[name]
    return HostBatch(DeviceBatch(cols, dev.sel), dicts)


def _scan_cap_key(p: pn.ScanExec):
    """Pinned-bucket identity of one scan's decoded batch: structural
    (name + shape of the projected output), never data identity — so a
    continuous stream scan keeps ONE pin across every pushed interval
    even though each interval attaches a fresh memory table. A scan
    that runtime filters prune pins apart from the unpruned one: a
    key list that keeps 476 of 6M rows is not padded to the whole
    table's bucket."""
    return ("scan-decode", p.table_name, p.format, p.projection,
            tuple((f.name, f.dtype) for f in p.out_schema),
            bool(p.runtime_predicates))


def _shrink(dev: DeviceBatch, n_live: int, bucket_key=None) -> DeviceBatch:
    """Slice a front-compacted batch down to a smaller padded capacity."""
    cap = bucket_capacity(max(n_live, 1), key=bucket_key)
    if cap >= dev.capacity:
        return dev
    cols = {n: Column(c.data[:cap],
                      None if c.validity is None else c.validity[:cap], c.dtype)
            for n, c in dev.columns.items()}
    return DeviceBatch(cols, dev.sel[:cap])


def _flip_residual(r: Optional[rx.Rex], n_left: int, n_right: int) -> Optional[rx.Rex]:
    if r is None:
        return None

    def flip(x: rx.Rex) -> rx.Rex:
        if isinstance(x, rx.BoundRef):
            if x.index < n_left:
                return dataclasses.replace(x, index=x.index + n_right)
            return dataclasses.replace(x, index=x.index - n_left)
        if isinstance(x, rx.RCall):
            return dataclasses.replace(x, args=tuple(flip(a) for a in x.args))
        if isinstance(x, rx.RCast):
            return dataclasses.replace(x, child=flip(x.child))
        if isinstance(x, rx.RCase):
            return dataclasses.replace(
                x, branches=tuple((flip(c), flip(v)) for c, v in x.branches),
                else_value=None if x.else_value is None else flip(x.else_value))
        return x

    return flip(r)


def _reorder_right(hb: HostBatch, n_right: int, n_left: int) -> HostBatch:
    """After executing a flipped right join (as left join with sides swapped),
    restore the original column order: right-output cols [0..n_right) move
    after the left cols."""
    dev = hb.device
    cols = {}
    dicts = {}
    for i in range(n_left):
        src = _col_name(n_right + i)
        cols[_col_name(i)] = dev.columns[src]
        if src in hb.dicts:
            dicts[_col_name(i)] = hb.dicts[src]
    for i in range(n_right):
        src = _col_name(i)
        cols[_col_name(n_left + i)] = dev.columns[src]
        if src in hb.dicts:
            dicts[_col_name(n_left + i)] = hb.dicts[src]
    return HostBatch(DeviceBatch(cols, dev.sel), dicts)


def _node_rex(p: pn.PlanNode):
    if isinstance(p, pn.FilterExec):
        yield p.condition
    elif isinstance(p, pn.ProjectExec):
        for _, e in p.exprs:
            yield e
    elif isinstance(p, pn.JoinExec):
        yield from p.left_keys
        yield from p.right_keys
        if p.residual is not None:
            yield p.residual
    elif isinstance(p, pn.SortExec):
        for k in p.keys:
            yield k.expr


def _walk_part_rex(part):
    """Yield Rex nodes reachable inside an _op_key part (tuples of exprs,
    SortKeys, bare Rex, …)."""
    if isinstance(part, rx.Rex):
        yield part
    elif isinstance(part, pn.SortKey):
        yield part.expr
    elif isinstance(part, tuple):
        for item in part:
            yield from _walk_part_rex(item)
