"""SparkSession-compatible entry point and DataFrame API.

Reference role: sail-session (SessionManager/session factory) plus the
PySpark-facing DataFrame surface that Spark Connect clients drive
(SURVEY.md §2.2). In-process v0: sql()/read/createDataFrame build spec
plans; actions resolve → optimize → execute on the local executor. The
protocol servers (Spark Connect gRPC, Flight SQL) layer on top of this
same session object.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import pyarrow as pa

from .catalog import CatalogManager, TableEntry
from .spec import data_type as dt
from .spec import expression as ex
from .spec import plan as sp
from .spec.literal import Literal as LV


class SparkSession:
    _active: Optional["SparkSession"] = None
    _lock = threading.Lock()

    class Builder:
        def __init__(self):
            self._conf: Dict[str, str] = {}

        def appName(self, name: str) -> "SparkSession.Builder":
            self._conf["spark.app.name"] = name
            return self

        def master(self, _: str) -> "SparkSession.Builder":
            return self

        def config(self, key: str, value=None) -> "SparkSession.Builder":
            self._conf[key] = str(value)
            return self

        def getOrCreate(self) -> "SparkSession":
            with SparkSession._lock:
                if SparkSession._active is None:
                    SparkSession._active = SparkSession(self._conf)
                return SparkSession._active

    builder = None  # replaced below by a property-like descriptor

    def __init__(self, conf: Optional[Dict[str, str]] = None,
                 catalog_manager: Optional[CatalogManager] = None):
        import uuid
        from collections import OrderedDict
        self.conf = SessionConf(conf or {})
        # ``catalog_manager`` is shared by sibling sessions created via
        # newSession(): tables/views/UDFs are engine-wide, the conf (and
        # with it the tenant tag) is strictly per session
        self.catalog_manager = catalog_manager or CatalogManager()
        from .exec.local import LocalExecutor
        self._executor_cls = LocalExecutor
        self.catalog = Catalog(self)
        self.udf = self.catalog_manager.udfs
        self.dataSource = _DataSourceRegistry(self.catalog_manager)
        self._session_id = uuid.uuid4().hex[:8]
        # SQL text + parse wall time per root plan, consumed when the
        # plan executes so the query profile can carry both
        self._parsed: "OrderedDict[int, tuple]" = OrderedDict()
        # pull-based ops endpoint (telemetry.http.enabled; one check +
        # at most one server per process)
        from . import obs_server
        obs_server.ensure_started()
        # jax's persistent compilation cache has its directory before
        # the first eager dispatch compiles anything
        from .exec import pcache
        pcache.place_jax_cache()

    def newSession(self) -> "SparkSession":
        """A sibling session: same catalog (tables, temp views, UDFs),
        fresh independent :class:`SessionConf` — conf keys and the
        ``spark.sail.tenant`` tag set on one session can never bleed
        into another session's queries or profiles."""
        return SparkSession({}, catalog_manager=self.catalog_manager)

    @property
    def tenant(self) -> str:
        """The admission-control tenant this session's queries bill to
        (``spark.sail.tenant``; ``admission.tenant`` config default)."""
        t = self.conf.get("spark.sail.tenant")
        if t:
            return str(t)
        from .config import get as config_get
        return str(config_get("admission.tenant", "default")
                   or "default")

    # -- plan execution ----------------------------------------------------
    def _resolve(self, plan: sp.QueryPlan):
        from . import profiler
        from .plan.optimizer import optimize
        from .plan.resolver import Resolver
        with profiler.maybe_phase("resolve"):
            node = Resolver(self.catalog_manager).resolve(plan)
        with profiler.maybe_phase("optimize"):
            return optimize(
                node,
                validate=self.conf.get("spark.sail.analysis.validatePlans"))

    def _note_parsed(self, plan: sp.QueryPlan, text: str,
                     parse_ms: float, exempt: bool = False) -> None:
        import weakref
        try:
            ref = weakref.ref(plan)
        except TypeError:
            return
        self._parsed[id(plan)] = (ref, text, parse_ms, exempt)
        while len(self._parsed) > 128:
            self._parsed.popitem(last=False)

    def _parsed_info(self, plan: sp.QueryPlan):
        entry = self._parsed.get(id(plan))
        if entry is not None and entry[0]() is plan:
            return entry[1], entry[2], entry[3]
        return "", 0.0, False

    def _execute_query(self, plan: sp.QueryPlan) -> pa.Table:
        from . import profiler
        from .exec import admission
        from .utils.tz import reset_session_timezone, set_session_timezone
        text, parse_ms, exempt = self._parsed_info(plan)
        tenant = self.tenant
        with profiler.profile_query(text, session=self._session_id,
                                    conf=self.conf, tenant=tenant,
                                    enabled=not exempt) as prof:
            if parse_ms and "parse" not in prof.phases:
                prof.add_phase("parse", parse_ms)
            # multi-tenant admission: acquire a per-tenant query slot
            # (weighted-fair wake order, bounded queue) BEFORE any
            # resolution/execution work; overflow/timeout raises a
            # typed retryable ResourceExhausted instead of hanging.
            # Nested _execute_query calls ride the outer ticket.
            # Enforcement is PROCESS-wide (admission.enabled app
            # config) — a tenant-controlled session conf must not be
            # able to opt out of the isolation layer.
            deadline = self.conf.get("spark.sail.query.deadlineMs")
            try:
                deadline_ms = float(deadline) if deadline else None
            except (TypeError, ValueError):
                deadline_ms = None
            from . import tracing as tr
            with tr.span("admission"):
                ticket = admission.session_gate().acquire(
                    tenant, query_id=prof.query_id,
                    deadline_ms=deadline_ms)
            token = set_session_timezone(
                self.conf.get("spark.sql.session.timeZone") or "UTC")
            try:
                node = self._resolve(plan)
                # the baseline/anomaly plane keys repeated executions by
                # structural plan fingerprint (analysis/anomaly.py)
                from .plan.stages import plan_fingerprint_hash
                profiler.note_plan_fingerprint(
                    plan_fingerprint_hash(node))
                # result cache: a fingerprint+version-vector hit serves
                # the stored table and skips execution entirely (local,
                # mesh and cluster paths alike); a miss measures the
                # build cost for the eviction policy and stores
                from .exec import result_cache as rc
                rc_probe = None
                if rc.result_cache_enabled(self.conf):
                    with tr.span("result_cache.probe"):
                        rc_probe = rc.probe(
                            node, self._result_cache_session_key())
                        cached = None if rc_probe is None \
                            else rc.RESULT_CACHE.lookup(rc_probe)
                    if rc_probe is not None:
                        if cached is not None:
                            prof.note_result_cache(
                                "hit", fragment=cached.fragment_id,
                                nbytes=cached.nbytes)
                            prof.rows_out = cached.table.num_rows
                            return cached.table
                        prof.note_result_cache(
                            "view" if self._reads_materialized_view(node)
                            else "miss")
                build_t0 = time.perf_counter()
                # the executors record their own execute/fetch phases
                # (LocalExecutor.execute); the mesh attempt is wrapped
                # here because it returns a finished table
                with profiler.maybe_phase("execute"):
                    table = self._try_mesh_execute(node)
                if table is None:
                    table = self._executor_cls(
                        dict(self.conf.items())).execute(node)
                if rc_probe is not None:
                    rc.RESULT_CACHE.store(
                        rc_probe, table,
                        (time.perf_counter() - build_t0) * 1000.0)
                prof.rows_out = table.num_rows
                return table
            finally:
                reset_session_timezone(token)
                ticket.release()

    def _result_cache_session_key(self) -> tuple:
        """Session knobs that change a query's OUTPUT for an identical
        plan — part of the result-cache key."""
        return (self.conf.get("spark.sql.session.timeZone") or "UTC",
                str(self.conf.get("spark.sql.ansi.enabled") or ""),
                str(self.conf.get("spark.sql.shuffle.partitions") or ""))

    @staticmethod
    def _reads_materialized_view(node) -> bool:
        from .exec.result_cache import VIEWS
        from .plan import nodes as pn
        if not VIEWS.names():
            return False
        return any(isinstance(n, pn.ScanExec)
                   and VIEWS.is_view(n.table_name)
                   for n in pn.walk_plan(node))

    def _table_mutated(self, entry, kind: str = "append",
                       delta: Optional[pa.Table] = None) -> None:
        """Post-write hook for every DML path: bumps the result-cache
        table version (which also clears file listings for the written
        root) and folds the change into dependent materialized views."""
        from .exec import result_cache as rc
        rc.table_mutated(self, entry, kind=kind, delta=delta)

    def _try_mesh_execute(self, node) -> Optional[pa.Table]:
        """SPMD path: when the plan splits into co-resident stages and the
        session mesh has >1 device, the whole job graph compiles into one
        shard_map program whose exchanges are XLA collectives (see
        parallel/mesh_exec.py). mode: off | auto (default) | force."""
        from .config import get as config_get
        self._last_mesh_executor = None
        mode = (self.conf.get("spark.sail.execution.mesh")
                or str(config_get("execution.mesh", "auto")))
        if mode == "off":
            return None
        import jax
        if len(jax.devices()) < 2 and mode != "force":
            return None
        # plan-level backend routing (exec/router.py): the SPMD mesh
        # program is only worth its fixed dispatch/compile cost above a
        # row-volume floor; `execution.backend.force` pins either way
        from .exec import router
        decision = router.decide_plan(
            node, nparts=len(jax.devices()),
            force=router.forced_backend(self.conf), mode=mode,
            slo_ctx=router.slo_context(self.conf))
        router.record_decisions([decision])
        if decision.backend != "mesh":
            return None
        from .parallel.mesh_exec import MeshExecutor, MeshUnsupported
        ex = MeshExecutor(config=dict(self.conf.items()))
        try:
            result = ex.execute(node)
        except MeshUnsupported:
            # the executor's declared "not this graph" signal; anything
            # else (a program the compiler refuses, a device fault) rises
            if mode == "force":
                raise
            return None
        if result is not None:
            self._last_mesh_executor = ex
        return result

    # -- entry points -------------------------------------------------------
    def sql(self, query: str) -> "DataFrame":
        import time as _t
        from . import profiler
        from .sql import parse_one
        t0 = _t.perf_counter()
        plan = parse_one(query)
        parse_ms = (_t.perf_counter() - t0) * 1000.0
        if isinstance(plan, sp.CommandPlan):
            # commands execute eagerly: the profile covers the whole
            # statement here; lazy queries profile at action time
            with profiler.profile_query(query, session=self._session_id,
                                        conf=self.conf) as prof:
                prof.add_phase("parse", parse_ms)
                table = self._execute_command(plan)
            # the command was profiled above; fetching its materialized
            # result must not record a second, anonymous profile
            result = sp.LocalRelation(table)
            self._note_parsed(result, query, 0.0, exempt=True)
            return DataFrame(result, self)
        self._note_parsed(plan, query, parse_ms)
        return DataFrame(plan, self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    @property
    def readStream(self):
        from .streaming import DataStreamReader
        return DataStreamReader(self)

    def createDataFrame(self, data, schema=None) -> "DataFrame":
        if isinstance(data, pa.Table):
            table = data
        elif type(data).__name__ == "DataFrame" and hasattr(data, "to_records"):
            import pandas as pd
            assert isinstance(data, pd.DataFrame)
            table = pa.Table.from_pandas(data, preserve_index=False)
        else:
            columns = list(schema) if isinstance(schema, (list, tuple)) else None
            rows = [tuple(r.values()) if isinstance(r, dict) else tuple(r)
                    for r in data]
            if columns is None:
                columns = [f"_{i + 1}" for i in range(len(rows[0]))] if rows else []
            arrays = [pa.array([r[i] for r in rows]) for i in range(len(columns))]
            table = pa.Table.from_arrays(arrays, names=columns)
        if isinstance(schema, dt.StructType):
            from .columnar.arrow_interop import spec_type_to_arrow
            target = pa.schema([(f.name, spec_type_to_arrow(f.data_type))
                                for f in schema.fields])
            table = table.rename_columns([f.name for f in schema.fields]).cast(target)
        return DataFrame(sp.LocalRelation(table), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              numPartitions: Optional[int] = None) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return DataFrame(sp.Range(start, end, step, numPartitions), self)

    def table(self, name: str) -> "DataFrame":
        return DataFrame(sp.ReadNamedTable(tuple(name.split("."))), self)

    def stop(self):
        with SparkSession._lock:
            if SparkSession._active is self:
                SparkSession._active = None

    @property
    def version(self) -> str:
        return "4.0.0-sail-tpu"

    # -- commands ------------------------------------------------------------
    def _execute_command(self, cmd: sp.CommandPlan) -> pa.Table:
        cm = self.catalog_manager
        empty = pa.table({})
        if isinstance(cmd, sp.CreateView):
            cm.register_temp_view(cmd.name[-1], cmd.query, replace=cmd.replace)
            return empty
        if isinstance(cmd, sp.CreateTable):
            if cmd.query is not None:  # CTAS
                table = self._execute_query(cmd.query)
                if cmd.location:
                    from .io.formats import write_table
                    write_table(table, cmd.format or "parquet", cmd.location,
                                mode="overwrite" if cmd.replace else "error",
                                partition_by=cmd.partition_by)
                    entry = self._file_table_entry(cmd)
                else:
                    entry = TableEntry(cmd.name, _schema_of(table), table,
                                       (), "memory")
                cm.register_table(entry, cmd.replace, cmd.if_not_exists)
                return empty
            if cmd.location:
                entry = self._file_table_entry(cmd)
            else:
                schema = cmd.schema or dt.StructType(())
                empty_tbl = _empty_table(schema)
                entry = TableEntry(cmd.name, schema, empty_tbl, (), "memory")
            cm.register_table(entry, cmd.replace, cmd.if_not_exists)
            return empty
        if isinstance(cmd, sp.DropTable):
            cm.drop_table(cmd.name, cmd.if_exists, cmd.is_view)
            return empty
        if isinstance(cmd, sp.CreateDatabase):
            cm.create_database(cmd.name[-1], cmd.if_not_exists, cmd.comment,
                               cmd.location)
            return empty
        if isinstance(cmd, sp.DropDatabase):
            cm.drop_database(cmd.name[-1], cmd.if_exists, cmd.cascade)
            return empty
        if isinstance(cmd, sp.UseDatabase):
            if cmd.name[-1].lower() not in cm.databases:
                raise ValueError(f"database {cmd.name[-1]!r} not found")
            cm.current_database = cmd.name[-1].lower()
            return empty
        if isinstance(cmd, sp.InsertInto):
            return self._insert_into(cmd)
        if isinstance(cmd, sp.WriteDataSource):
            if cmd.table and not cmd.path:
                if cmd.format == "delta":
                    # managed Delta table under the warehouse directory —
                    # a memory TableEntry would silently lose durability
                    from .io.formats import write_table
                    wh = self.conf.get("spark.sql.warehouse.dir") or \
                        os.path.join(os.getcwd(), "spark-warehouse")
                    location = os.path.join(wh, *cmd.table)
                    table = self._execute_query(cmd.query)
                    write_table(table, "delta", location, cmd.mode,
                                dict(cmd.options), cmd.partition_by)
                    entry = TableEntry(cmd.table, _schema_of(table), None,
                                       (location,), "delta", None,
                                       cmd.options, cmd.partition_by)
                    cm.register_table(entry, replace=True,
                                      if_not_exists=False)
                    return empty
                existing = cm.lookup_table(cmd.table)
                if existing is not None and cmd.mode == "append":
                    return self._insert_into(sp.InsertInto(cmd.table,
                                                           cmd.query))
                if existing is not None and cmd.mode == "ignore":
                    return empty
                table = self._execute_query(cmd.query)
                entry = TableEntry(cmd.table, _schema_of(table), table,
                                   (), "memory")
                cm.register_table(entry, replace=(cmd.mode == "overwrite"),
                                  if_not_exists=False)
                return empty
            if cmd.path:
                from .io.formats import write_table
                table = self._execute_query(cmd.query)
                write_table(table, cmd.format, cmd.path, cmd.mode,
                            dict(cmd.options), cmd.partition_by)
                return empty
            raise ValueError("write requires a path or table name")
        if isinstance(cmd, sp.ShowTables):
            entries = cm.list_tables(cmd.database[-1] if cmd.database else None)
            names = [e.name[-1] for e in entries]
            return pa.table({
                "namespace": pa.array([cm.current_database] * len(names)),
                "tableName": pa.array(names),
                "isTemporary": pa.array([e.view_plan is not None for e in entries]),
            })
        if isinstance(cmd, sp.ShowDatabases):
            return pa.table({"namespace": pa.array(cm.list_databases())})
        if isinstance(cmd, sp.ShowColumns):
            entry = cm.lookup_table(cmd.table)
            if entry is None:
                raise ValueError(f"table not found: {'.'.join(cmd.table)}")
            if entry.view_plan is not None:
                node = self._resolve(entry.view_plan)
                cols = [f.name for f in node.schema]
            else:
                cols = [f.name for f in entry.schema.fields]
            return pa.table({"col_name": pa.array(cols)})
        if isinstance(cmd, sp.DescribeTable):
            entry = cm.lookup_table(cmd.table)
            if entry is None:
                raise ValueError(f"table not found: {'.'.join(cmd.table)}")
            if entry.view_plan is not None:
                node = self._resolve(entry.view_plan)
                pairs = [(f.name, f.dtype.simple_string()) for f in node.schema]
            else:
                pairs = [(f.name, f.data_type.simple_string())
                         for f in entry.schema.fields]
            return pa.table({
                "col_name": pa.array([p[0] for p in pairs]),
                "data_type": pa.array([p[1] for p in pairs]),
                "comment": pa.array([None] * len(pairs), type=pa.string()),
            })
        if isinstance(cmd, sp.ShowFunctions):
            from .functions.registry import AGGREGATE_FUNCTIONS
            from .plan.compiler import _NUMERIC_BUILDERS, _STRING_TRANSFORMS
            names = sorted(set(_NUMERIC_BUILDERS) | set(_STRING_TRANSFORMS)
                           | AGGREGATE_FUNCTIONS)
            return pa.table({"function": pa.array(names)})
        if isinstance(cmd, sp.SetVariable):
            if cmd.name and cmd.value is not None:
                self.conf.set(cmd.name, cmd.value)
                if cmd.name in ("spark.sail.slo.targetMs",
                                "spark.sail.slo.objective"):
                    # register the session tenant's SLO objective with
                    # the burn-rate monitor: explicit session mirrors
                    # win over slo.tenants.* config and the global
                    # slo.{target_ms,objective} defaults
                    try:
                        from .analysis.anomaly import SLO_MONITOR
                        v = float(cmd.value)
                        if cmd.name.endswith("targetMs"):
                            SLO_MONITOR.set_objective(
                                self.tenant, target_ms=v)
                        else:
                            SLO_MONITOR.set_objective(
                                self.tenant, objective=v)
                    except (TypeError, ValueError):
                        pass
                return pa.table({"key": pa.array([cmd.name]),
                                 "value": pa.array([cmd.value])})
            if cmd.name:
                v = self.conf.get(cmd.name)
                return pa.table({"key": pa.array([cmd.name]),
                                 "value": pa.array([v])})
            items = sorted(self.conf.items())
            return pa.table({"key": pa.array([k for k, _ in items]),
                             "value": pa.array([v for _, v in items])})
        if isinstance(cmd, sp.ResetVariable):
            self.conf.reset(cmd.name)
            return empty
        if isinstance(cmd, sp.Delete):
            return self._delta_delete(cmd)
        if isinstance(cmd, sp.Update):
            return self._delta_update(cmd)
        if isinstance(cmd, sp.MergeInto):
            return self._delta_merge(cmd)
        if isinstance(cmd, sp.Explain):
            from .plan.nodes import explain
            node = self._resolve(cmd.query)
            stage_of = None
            n_stages = 0
            from .plan.stages import fusion_enabled
            fusion_on = fusion_enabled(self.conf.get(
                "spark.sail.execution.fusion.enabled"))
            backends = []
            if fusion_on:
                from .exec import router
                from .plan.stages import split_stages
                split = split_stages(node)
                stage_of = split.stage_of
                n_stages = len(split.stages)
                # the routing the executor would run under (same
                # deterministic decision function, no execution)
                backends = [d.to_dict() for d in router.decide_split(
                    split, force=router.forced_backend(self.conf),
                    slo_ctx=router.slo_context(self.conf))]
            from .exec import result_cache as rc
            rc_probe = None
            if rc.result_cache_enabled(self.conf):
                rc_probe = rc.probe(node,
                                    self._result_cache_session_key())
            if cmd.mode == "analyze":
                import time as _t
                from . import profiler
                from . import telemetry as tel
                prof = profiler.current_profile()
                # the analyzed plan is the one the baseline/anomaly
                # plane must key this profile under
                from .plan.stages import plan_fingerprint_hash
                profiler.note_plan_fingerprint(
                    plan_fingerprint_hash(node))
                t0 = _t.perf_counter()
                cached = rc.RESULT_CACHE.lookup(rc_probe) \
                    if rc_probe is not None else None
                if cached is not None:
                    # same contract as _execute_query: a hit serves the
                    # stored table — no operators ran, and the profile
                    # says so
                    result = cached.table
                    collector = []
                    if prof is not None:
                        prof.note_result_cache(
                            "hit", fragment=cached.fragment_id,
                            nbytes=cached.nbytes)
                else:
                    if prof is not None and rc_probe is not None:
                        prof.note_result_cache(
                            "view"
                            if self._reads_materialized_view(node)
                            else "miss")
                    with tel.collect_metrics() as collector:
                        # LocalExecutor.execute records execute/fetch
                        # phases
                        result = self._executor_cls(
                            dict(self.conf.items())).execute(node)
                    if rc_probe is not None:
                        rc.RESULT_CACHE.store(
                            rc_probe, result,
                            (_t.perf_counter() - t0) * 1000.0)
                total_ms = (_t.perf_counter() - t0) * 1000
                ops = [m.to_dict() for m in collector]
                if prof is not None:
                    prof.operators = ops
                    prof.rows_out = result.num_rows
                    try:
                        # classify now so the rendered payload carries
                        # the verdict the finalize pass will land (the
                        # baseline only observes at finalize, so both
                        # classify against the same state)
                        from .analysis import anomaly as _anomaly
                        _anomaly.preview(prof)
                    except Exception:  # noqa: BLE001
                        pass
                if cmd.format == "json":
                    import json as _json
                    payload = prof.to_dict() if prof is not None else \
                        {"total_ms": round(total_ms, 3), "operators": ops}
                    # the analyzed execution IS complete — the profile
                    # just hasn't closed yet (rendering happens inside it)
                    payload["status"] = "succeeded"
                    payload["plan"] = explain(node, stage_of=stage_of)
                    if stage_of is not None:
                        payload["fused_stages"] = n_stages
                    if backends:
                        payload["backends"] = backends
                    text = _json.dumps(payload, indent=2, default=str)
                else:
                    header = prof.render() if prof is not None else \
                        f"total: {total_ms:.1f}ms"
                    text = "\n".join(
                        [header] + [m.render() for m in collector])
                return pa.table({"plan": pa.array([text])})
            cache_info = None
            if rc_probe is not None:
                # non-counting peek: what WOULD happen if this ran now
                entry = rc.RESULT_CACHE.peek(rc_probe)
                if entry is not None:
                    cache_info = {"status": "hit",
                                  "fragments": [entry.fragment_id],
                                  "bytes_served": entry.nbytes}
                else:
                    cache_info = {
                        "status": "view"
                        if self._reads_materialized_view(node)
                        else "miss",
                        "fragments": [], "bytes_served": 0}
            if cmd.format == "json":
                import json as _json
                payload = {"plan": explain(node, stage_of=stage_of)}
                if stage_of is not None:
                    payload["fused_stages"] = n_stages
                if backends:
                    payload["backends"] = backends
                if cache_info is not None:
                    payload["result_cache"] = cache_info
                return pa.table({"plan": pa.array(
                    [_json.dumps(payload, indent=2)])})
            text = explain(node, stage_of=stage_of)
            if stage_of is not None:
                text += f"\nfused: {n_stages} stages"
            if backends:
                text += "\nbackend: " + " ".join(
                    f"s{b['stage']}={b['backend']}({b['reason']})"
                    for b in backends)
            if cache_info is not None:
                line = f"\ncache: {cache_info['status']}"
                if cache_info["fragments"]:
                    line += " fragments=" + ",".join(
                        cache_info["fragments"])
                if cache_info["bytes_served"]:
                    line += f" bytes={cache_info['bytes_served']}"
                text += line
            return pa.table({"plan": pa.array([text])})
        if isinstance(cmd, sp.CacheMaterialized):
            from .exec.result_cache import VIEWS
            VIEWS.create(self, cmd.name[-1], cmd.query)
            return empty
        if isinstance(cmd, sp.UncacheMaterialized):
            from .exec.result_cache import VIEWS
            VIEWS.drop(cm, cmd.name[-1], cmd.if_exists)
            return empty
        if isinstance(cmd, sp.CacheTable):
            if cmd.query is not None:
                cm.register_temp_view(cmd.name[-1], cmd.query)
            return empty
        if isinstance(cmd, sp.UncacheTable):
            return empty
        if isinstance(cmd, sp.ShowCatalogs):
            names = cm.list_catalogs() if hasattr(cm, "list_catalogs") \
                else sorted(cm.providers)
            if cmd.pattern:
                import fnmatch
                names = [n for n in names
                         if fnmatch.fnmatch(n, cmd.pattern)]
            return pa.table({"catalog": pa.array(names)})
        if isinstance(cmd, sp.TruncateTable):
            return self._truncate_table(cmd)
        if isinstance(cmd, sp.RefreshTable):
            from .io.cache import LISTING_CACHE, METADATA_CACHE
            LISTING_CACHE.clear()
            METADATA_CACHE.clear()
            entry = cm.lookup_table(cmd.name)
            if entry is not None:
                # external change declared: version the table so cached
                # results miss and dependent views recompute
                self._table_mutated(entry, "refresh")
            return empty
        if isinstance(cmd, sp.ClearCache):
            from .exec.local import clear_caches
            from .io.cache import LISTING_CACHE, METADATA_CACHE
            LISTING_CACHE.clear()
            METADATA_CACHE.clear()
            clear_caches()
            return empty
        if isinstance(cmd, sp.ShowCreateTable):
            entry = cm.lookup_table(cmd.name)
            if entry is None:
                raise ValueError(f"table not found: {'.'.join(cmd.name)}")
            cols = ",\n".join(
                f"  {f.name} {f.data_type.simple_string().upper()}"
                for f in entry.schema.fields) if entry.schema else ""
            ddl = f"CREATE TABLE {'.'.join(cmd.name)} (\n{cols})"
            if entry.format != "memory":
                ddl += f"\nUSING {entry.format}"
            if entry.paths:
                ddl += f"\nLOCATION '{entry.paths[0]}'"
            if entry.partition_by:
                ddl += f"\nPARTITIONED BY ({', '.join(entry.partition_by)})"
            return pa.table({"createtab_stmt": pa.array([ddl])})
        if isinstance(cmd, sp.AnalyzeTable):
            entry = cm.lookup_table(cmd.name)
            if entry is None:
                raise ValueError(f"table not found: {'.'.join(cmd.name)}")
            if cmd.columns:
                # parsed but column-level stats are not collected yet —
                # succeeding silently would let users believe ndv/min/max
                # stats exist when only numRows does
                raise NotImplementedError(
                    "ANALYZE TABLE ... FOR COLUMNS is not implemented; "
                    "use ANALYZE TABLE ... COMPUTE STATISTICS [NOSCAN]")
            if not cmd.noscan:
                n = self._execute_query(
                    sp.Aggregate(sp.ReadNamedTable(cmd.name), (),
                                 (ex.Alias(ex.Function(
                                     "count", (ex.Star(),)),
                                     ("cnt",)),))).column(0)[0].as_py()
                entry.options = tuple(
                    [(k, v) for k, v in entry.options if k != "numRows"]
                    + [("numRows", str(n))])
            return empty
        if isinstance(cmd, sp.AlterTable):
            return self._alter_table(cmd)
        if isinstance(cmd, sp.DescribeDatabase):
            db = cmd.name[-1]
            prov = cm.provider(cmd.name[-2]) if len(cmd.name) >= 2 \
                else cm.provider()
            info = prov.database_info(db) \
                if hasattr(prov, "database_info") else None
            if info is None:
                raise ValueError(f"database not found: {db}")
            rows = [("Namespace Name", db),
                    ("Comment", info.get("comment") or ""),
                    ("Location", info.get("location") or "")]
            return pa.table({
                "info_name": pa.array([r[0] for r in rows]),
                "info_value": pa.array([r[1] for r in rows])})
        if isinstance(cmd, sp.ShowTblProperties):
            entry = cm.lookup_table(cmd.name)
            if entry is None:
                raise ValueError(f"table not found: {'.'.join(cmd.name)}")
            props = dict(entry.options)
            if cmd.key is not None:
                props = {cmd.key: props.get(cmd.key)}
            return pa.table({
                "key": pa.array(sorted(props)),
                "value": pa.array([props[k] for k in sorted(props)])})
        if isinstance(cmd, sp.ShowPartitions):
            return self._show_partitions(cmd)
        if isinstance(cmd, sp.CommentOn):
            if cmd.kind == "database":
                prov = cm.provider(cmd.name[-2]) if len(cmd.name) >= 2 \
                    else cm.provider()
                # only the memory provider exposes a mutable database
                # dict; remote catalogs rebuild info per call, so a
                # write there would be silently lost
                dbs = getattr(prov, "databases", None)
                if not isinstance(dbs, dict) or \
                        cmd.name[-1].lower() not in dbs:
                    raise NotImplementedError(
                        "COMMENT ON DATABASE is supported for the "
                        "in-memory catalog only")
                dbs[cmd.name[-1].lower()]["comment"] = cmd.comment
            else:
                entry = cm.lookup_table(cmd.name)
                if entry is None:
                    raise ValueError(
                        f"table not found: {'.'.join(cmd.name)}")
                entry.comment = cmd.comment
            return empty
        raise NotImplementedError(f"command {type(cmd).__name__} not supported yet")

    def _truncate_table(self, cmd: sp.TruncateTable) -> pa.Table:
        cm = self.catalog_manager
        entry = cm.lookup_table(cmd.name)
        if entry is None:
            raise ValueError(f"table not found: {'.'.join(cmd.name)}")
        if entry.view_plan is not None:
            raise ValueError(
                f"cannot TRUNCATE a view: {'.'.join(cmd.name)}")
        if entry.format == "memory":
            if entry.data is not None:
                entry.data = entry.data.slice(0, 0)
            _drop_row_stats(entry)
            self._table_mutated(entry, "truncate")
            return pa.table({})
        if entry.format == "delta" and entry.paths:
            from .columnar.arrow_interop import spec_type_to_arrow
            from .lakehouse.delta import DeltaTable
            t = DeltaTable(entry.paths[0])
            # overwrite with an EMPTY table built from the schema — no
            # need to materialize the existing data
            schema = t.snapshot().schema
            t.overwrite(pa.table({
                f.name: pa.array([], type=spec_type_to_arrow(f.data_type))
                for f in schema.fields}))
            _drop_row_stats(entry)
            self._table_mutated(entry, "truncate")
            return pa.table({})
        raise NotImplementedError(
            f"TRUNCATE on format {entry.format!r} not supported")

    def _alter_table(self, cmd: sp.AlterTable) -> pa.Table:
        import pyarrow as pa_mod

        cm = self.catalog_manager
        entry = cm.lookup_table(cmd.name)
        if entry is None:
            raise ValueError(f"table not found: {'.'.join(cmd.name)}")
        if entry.view_plan is not None:
            raise ValueError(
                f"cannot ALTER a view: {'.'.join(cmd.name)}")
        empty = pa_mod.table({})
        if cmd.action == "rename":
            # an unqualified new name stays in the SOURCE database and
            # the SOURCE catalog — a fully-qualified rename of a table
            # in a non-current catalog must not migrate the entry into
            # cm.current_catalog; cross-catalog renames are rejected
            # outright (matching Spark)
            src_cat = cmd.name[-3].lower() if len(cmd.name) >= 3 else (
                str(entry.name[0]).lower() if len(entry.name) >= 3
                else cm.current_catalog)
            if len(cmd.new_name) >= 3 and \
                    cmd.new_name[-3].lower() != src_cat:
                raise ValueError(
                    f"cannot rename across catalogs: "
                    f"{'.'.join(cmd.name)} -> {'.'.join(cmd.new_name)}")
            src_db = cmd.name[-2] if len(cmd.name) >= 2 \
                else cm.current_database
            new_db = cmd.new_name[-2] if len(cmd.new_name) >= 2 \
                else src_db
            cm.drop_table(cmd.name)
            entry.name = (src_cat, new_db, cmd.new_name[-1])
            cm.register_table(entry)
            return empty
        if cmd.action in ("set_properties", "unset_properties"):
            props = dict(entry.options)
            for k, v in cmd.properties:
                if cmd.action == "set_properties":
                    props[k] = v
                else:
                    props.pop(k, None)
            entry.options = tuple(sorted(props.items()))
            return empty
        if entry.format != "memory" or entry.schema is None:
            raise NotImplementedError(
                f"ALTER TABLE {cmd.action} on format {entry.format!r} "
                "not supported")
        if cmd.action == "add_columns":
            from .columnar.arrow_interop import spec_type_to_arrow
            fields = list(entry.schema.fields)
            for cname, ctype in cmd.columns:
                fields.append(dt.StructField(cname, ctype, True))
                if entry.data is not None:
                    entry.data = entry.data.append_column(
                        cname, pa_mod.nulls(entry.data.num_rows,
                                            type=spec_type_to_arrow(ctype)))
            entry.schema = dt.StructType(tuple(fields))
            return empty
        if cmd.action == "drop_columns":
            drop = {c.lower() for c in cmd.column_names}
            if any(p.lower() in drop for p in entry.partition_by):
                raise ValueError("cannot drop a partition column")
            entry.schema = dt.StructType(tuple(
                f for f in entry.schema.fields
                if f.name.lower() not in drop))
            if entry.data is not None:
                keep = [c for c in entry.data.column_names
                        if c.lower() not in drop]
                entry.data = entry.data.select(keep)
            return empty
        if cmd.action == "rename_column":
            old, new = cmd.column_names
            entry.schema = dt.StructType(tuple(
                dt.StructField(new if f.name.lower() == old.lower()
                               else f.name, f.data_type, f.nullable)
                for f in entry.schema.fields))
            if entry.data is not None:
                entry.data = entry.data.rename_columns(
                    [new if c.lower() == old.lower() else c
                     for c in entry.data.column_names])
            entry.partition_by = tuple(
                new if p.lower() == old.lower() else p
                for p in entry.partition_by)
            return empty
        raise NotImplementedError(f"ALTER TABLE action {cmd.action!r}")

    def _show_partitions(self, cmd: sp.ShowPartitions) -> pa.Table:
        cm = self.catalog_manager
        entry = cm.lookup_table(cmd.name)
        if entry is None:
            raise ValueError(f"table not found: {'.'.join(cmd.name)}")
        if not entry.partition_by:
            raise ValueError(
                f"table {'.'.join(cmd.name)} is not partitioned")
        pcols = [c.lower() for c in entry.partition_by]
        parts = set()
        if entry.format == "delta" and entry.paths:
            from .lakehouse.delta import DeltaTable
            snap = DeltaTable(entry.paths[0]).snapshot()
            for add in snap.files.values():
                pv = dict(add.partition_values)
                parts.add("/".join(
                    f"{c}={snap.partition_raw(pv, c)}"
                    for c in entry.partition_by))
        elif entry.paths:
            # hive-style directory layout: k=v path segments
            from .io.formats import expand_paths
            for f in expand_paths(entry.paths):
                segs = [s for s in f.split(os.sep)
                        if "=" in s and s.split("=", 1)[0].lower()
                        in pcols]
                if segs:
                    parts.add("/".join(segs))
        else:
            table = self._execute_query(sp.ReadNamedTable(cmd.name))
            combos = table.select(list(entry.partition_by)) \
                .group_by(list(entry.partition_by)).aggregate([]) \
                .to_pylist()
            parts = {"/".join(f"{k}={v}" for k, v in c.items())
                     for c in combos}
        return pa.table({"partition": pa.array(sorted(parts))})

    @staticmethod
    def _generated_columns(entry) -> set:
        """Delta generated columns for an INSERT target (these must stay
        absent from the insert batch so the writer computes them)."""
        if entry.format != "delta" or not entry.paths:
            return set()
        try:
            from .lakehouse.delta import DeltaTable
            return set(DeltaTable(entry.paths[0]).snapshot()
                       .generation_expressions)
        except Exception:  # noqa: BLE001 — best-effort metadata probe
            return set()

    def _delta_entry(self, table_name):
        entry = self.catalog_manager.lookup_table(table_name)
        if entry is None:
            raise ValueError(f"table not found: {'.'.join(table_name)}")
        if entry.format != "delta" or not entry.paths:
            raise NotImplementedError(
                "DELETE/UPDATE/MERGE are supported on Delta tables "
                f"(table {'.'.join(table_name)} has format "
                f"{entry.format!r})")
        from .lakehouse.delta import DeltaTable
        return entry, DeltaTable(entry.paths[0])

    def _eval_predicate(self, table: pa.Table, cond: sp.Expr) -> pa.Table:
        """Evaluate a predicate over an arrow table → bool column."""
        import sail_tpu.spec.expression as ex
        plan = sp.Project(sp.LocalRelation(table),
                          (ex.Alias(cond, ("__pred__",)),))
        return self._execute_query(plan)

    def _delta_delete(self, cmd: sp.Delete) -> pa.Table:
        entry = self.catalog_manager.lookup_table(cmd.table)
        if entry is not None and entry.format == "iceberg" and entry.paths:
            return self._iceberg_delete(entry, cmd)
        from .lakehouse.delta.dml import DeltaDml
        out = DeltaDml(self, cmd.table).delete(cmd.condition)
        if entry is not None:
            _drop_row_stats(entry)
            self._table_mutated(entry, "mutate")
        return out

    def _iceberg_delete(self, entry, cmd: sp.Delete) -> pa.Table:
        """DELETE on an Iceberg table → merge-on-read position-delete
        files (reference: sail-iceberg row-level operations)."""
        import numpy as np

        from .lakehouse.iceberg import IcebergTable

        t = IcebergTable(entry.paths[0])

        def mask_fn(tab):
            if cmd.condition is None:
                return np.ones(tab.num_rows, dtype=bool)
            pred = self._eval_predicate(tab, cmd.condition)
            vals = pred.column(0).to_pylist()
            return np.asarray([bool(v) for v in vals], dtype=bool)

        t.delete_where(mask_fn)
        _drop_row_stats(entry)
        self._table_mutated(entry, "mutate")
        return pa.table({})

    def _delta_update(self, cmd: sp.Update) -> pa.Table:
        from .lakehouse.delta.dml import DeltaDml
        out = DeltaDml(self, cmd.table).update(cmd)
        entry = self.catalog_manager.lookup_table(cmd.table)
        if entry is not None:
            _drop_row_stats(entry)
            self._table_mutated(entry, "mutate")
        return out

    def _delta_merge(self, cmd: sp.MergeInto) -> pa.Table:
        """MERGE INTO on a Delta table — planned and executed by the
        engine DML pipeline with targeted file rewrites
        (lakehouse/delta/dml.py; reference:
        crates/sail-delta-lake/src/physical_plan/planner/op_merge.rs)."""
        from .lakehouse.delta.dml import DeltaDml
        out = DeltaDml(self, cmd.target).merge(cmd)
        entry = self.catalog_manager.lookup_table(cmd.target)
        if entry is not None:
            _drop_row_stats(entry)
            self._table_mutated(entry, "mutate")
        return out

    def _file_table_entry(self, cmd: sp.CreateTable) -> TableEntry:
        from .io.formats import infer_schema
        fmt = cmd.format or "parquet"
        schema = cmd.schema or infer_schema(fmt, (cmd.location,), dict(cmd.options))
        return TableEntry(cmd.name, schema, None, (cmd.location,), fmt,
                          None, cmd.options, cmd.partition_by)

    def _insert_into(self, cmd: sp.InsertInto) -> pa.Table:
        cm = self.catalog_manager
        entry = cm.lookup_table(cmd.table)
        if entry is None:
            raise ValueError(f"table not found: {'.'.join(cmd.table)}")
        new_data = self._execute_query(cmd.query)
        if cmd.columns and new_data.num_columns != len(cmd.columns):
            raise ValueError(
                f"INSERT column list has {len(cmd.columns)} columns but "
                f"query produced {new_data.num_columns}")
        if entry.format == "memory":
            from .columnar.arrow_interop import spec_type_to_arrow
            existing = entry.data
            if existing is not None:
                target = existing.column_names
                ttype = {n: existing.schema.field(n).type for n in target}
            elif entry.schema is not None:
                target = [f.name for f in entry.schema.fields]
                ttype = {f.name: spec_type_to_arrow(f.data_type)
                         for f in entry.schema.fields}
            else:
                target = None
                ttype = {}
            if cmd.columns:
                # explicit column list: map by NAME onto the target
                # shape, null-filling unlisted columns
                new_data = new_data.rename_columns(list(cmd.columns))
                listed = {c.lower(): c for c in new_data.column_names}
                cols = {}
                for name in (target or list(cmd.columns)):
                    src = listed.get(name.lower())
                    if src is not None:
                        cols[name] = new_data.column(src)
                    else:
                        cols[name] = pa.nulls(new_data.num_rows,
                                              type=ttype[name])
                new_data = pa.table(cols)
            elif target is not None:
                # positional semantics against the declared shape
                if new_data.num_columns != len(target):
                    raise ValueError(
                        f"INSERT query produced {new_data.num_columns} "
                        f"columns but table has {len(target)}")
                new_data = new_data.rename_columns(target)
            if cmd.overwrite or existing is None or existing.num_rows == 0:
                merged = new_data
            else:
                merged = pa.concat_tables([existing, new_data],
                                          promote_options="permissive")
            entry.data = merged
            entry.schema = _schema_of(merged)
        else:
            from .io.formats import write_table
            # positional insert semantics: a VALUES/SELECT output maps to
            # the target columns by position (or by the explicit INSERT
            # column list), not by its own generated names (col1, …)
            if cmd.columns:
                new_data = new_data.rename_columns(list(cmd.columns))
                if entry.schema is not None:
                    # null-fill unlisted target columns so every data
                    # file carries the full schema (generated Delta
                    # columns stay absent — the writer computes them)
                    from .columnar.arrow_interop import spec_type_to_arrow
                    gen = self._generated_columns(entry)
                    listed = {c.lower(): c for c in new_data.column_names}
                    cols = {}
                    for f in entry.schema.fields:
                        src = listed.get(f.name.lower())
                        if src is not None:
                            cols[f.name] = new_data.column(src)
                        elif f.name not in gen:
                            cols[f.name] = pa.nulls(
                                new_data.num_rows,
                                type=spec_type_to_arrow(f.data_type))
                    new_data = pa.table(cols)
            elif entry.schema is not None and \
                    new_data.num_columns == len(entry.schema.fields):
                new_data = new_data.rename_columns(
                    [f.name for f in entry.schema.fields])
            write_table(new_data, entry.format, entry.paths[0],
                        mode="overwrite" if cmd.overwrite else "append",
                        partition_by=entry.partition_by)
        _drop_row_stats(entry)
        self._table_mutated(entry,
                            "overwrite" if cmd.overwrite else "append",
                            delta=None if cmd.overwrite else new_data)
        return pa.table({})


def _drop_row_stats(entry) -> None:
    """ANALYZE-time row counts are stale after any data mutation
    (INSERT, TRUNCATE, overwrite); drop them so the join reorderer falls
    back to exact footer counts instead of costing the table at its
    pre-mutation size."""
    entry.options = tuple(
        (k, v) for k, v in entry.options if k != "numRows")


class _BuilderDescriptor:
    def __get__(self, obj, objtype=None):
        return SparkSession.Builder()


SparkSession.builder = _BuilderDescriptor()


class SessionConf:
    _DEFAULTS = {
        "spark.sql.session.timeZone": "UTC",
        "spark.sql.shuffle.partitions": "8",
        "sail.execution.batch_capacity": "16777216",
    }

    def __init__(self, conf: Dict[str, str]):
        # layering (low → high): class defaults, YAML session.timezone,
        # YAML/env spark.* keys, then the per-session conf dict
        from .config import app_config
        app = app_config()
        base = dict(self._DEFAULTS)
        tz = app.get("session.timezone")
        if tz:
            base["spark.sql.session.timeZone"] = str(tz)
        for key, value in app.items():
            if key.startswith("spark."):
                base[key] = str(value)
        chunk = app.get("execution.scan_chunk_rows")
        if chunk:
            base["spark.sail.scan.chunkRows"] = str(chunk)
        pf_depth = app.get("execution.scan_prefetch_depth")
        if pf_depth is not None:  # 0 is meaningful: disables pipelining
            base["spark.sail.scan.prefetchDepth"] = str(pf_depth)
        slow_ms = app.get("telemetry.slow_query_ms")
        if slow_ms is not None:  # 0 is meaningful: disables the slow log
            base["spark.sail.telemetry.slowQueryMs"] = str(slow_ms)
        # cluster fault-tolerance knobs (YAML cluster.{rpc_retry,
        # speculation, quarantine}.* → spark.sail.cluster.* camelCase)
        for yaml_key, conf_key in (
                ("cluster.rpc_retry.max_attempts",
                 "spark.sail.cluster.rpcRetry.maxAttempts"),
                ("cluster.rpc_retry.base_ms",
                 "spark.sail.cluster.rpcRetry.baseMs"),
                ("cluster.rpc_retry.cap_ms",
                 "spark.sail.cluster.rpcRetry.capMs"),
                ("cluster.speculation.enabled",
                 "spark.sail.cluster.speculation.enabled"),
                ("cluster.speculation.stage_fraction",
                 "spark.sail.cluster.speculation.stageFraction"),
                ("cluster.speculation.latency_multiplier",
                 "spark.sail.cluster.speculation.latencyMultiplier"),
                ("cluster.speculation.min_runtime_ms",
                 "spark.sail.cluster.speculation.minRuntimeMs"),
                ("cluster.quarantine.enabled",
                 "spark.sail.cluster.quarantine.enabled"),
                ("cluster.quarantine.max_failures",
                 "spark.sail.cluster.quarantine.maxFailures"),
                ("cluster.quarantine.window_secs",
                 "spark.sail.cluster.quarantine.windowSecs"),
                ("cluster.quarantine.duration_secs",
                 "spark.sail.cluster.quarantine.durationSecs"),
                ("shuffle.compression",
                 "spark.sail.shuffle.compression"),
                ("shuffle.fetch_concurrency",
                 "spark.sail.shuffle.fetchConcurrency"),
                ("cluster.memory_budget_mb",
                 "spark.sail.cluster.memoryBudgetMb"),
                ("adaptive.enabled", "spark.sail.adaptive.enabled"),
                ("adaptive.coalesce.target_mb",
                 "spark.sail.adaptive.coalesce.targetMb"),
                ("adaptive.skew.factor",
                 "spark.sail.adaptive.skew.factor"),
                ("adaptive.broadcast.threshold_mb",
                 "spark.sail.adaptive.broadcast.thresholdMb"),
                ("telemetry.events_enabled",
                 "spark.sail.telemetry.eventsEnabled"),
                ("telemetry.event_log.enabled",
                 "spark.sail.telemetry.eventLog.enabled"),
                ("telemetry.event_log.dir",
                 "spark.sail.telemetry.eventLog.dir"),
                ("telemetry.event_log.max_mb",
                 "spark.sail.telemetry.eventLog.maxMb"),
                ("faults.spec", "spark.sail.faults.spec"),
                ("faults.seed", "spark.sail.faults.seed"),
                ("analysis.validate_plans",
                 "spark.sail.analysis.validatePlans"),
                # multi-tenant admission control (exec/admission.py):
                # only the keys _execute_query actually reads per
                # session mirror here — enforcement (enabled) and all
                # caps/weights/quotas are process-wide (admission.*
                # app config / SAIL_ADMISSION env), never per-session,
                # so a tenant cannot opt itself out
                ("admission.tenant", "spark.sail.tenant"),
                ("admission.default_deadline_ms",
                 "spark.sail.query.deadlineMs")):
            value = app.get(yaml_key)
            if value is not None:
                base[conf_key] = str(value)
        self._DEFAULTS = base
        self._conf = dict(conf)

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        return self._conf.get(key, self._DEFAULTS.get(key, default))

    def set(self, key: str, value: str):
        self._conf[key] = str(value)

    def reset(self, key: Optional[str] = None):
        if key is None:
            self._conf.clear()
        else:
            self._conf.pop(key, None)

    def items(self):
        merged = dict(self._DEFAULTS)
        merged.update(self._conf)
        return merged.items()


class _DataSourceRegistry:
    """spark.dataSource — user-defined Python data sources (reference:
    sail-data-source formats/python; API mirrors pyspark.sql.datasource)."""

    def __init__(self, catalog_manager):
        self._cm = catalog_manager
        if not hasattr(catalog_manager, "data_sources"):
            catalog_manager.data_sources = {}

    def register(self, cls, name: str = None) -> None:
        self._cm.data_sources[(name or cls.name()).lower()] = cls

    def get(self, name: str):
        return self._cm.data_sources.get(name.lower())


class Catalog:
    """spark.catalog surface (subset)."""

    def __init__(self, session: SparkSession):
        self._session = session

    def listTables(self, dbName: Optional[str] = None):
        return self._session.catalog_manager.list_tables(dbName)

    def listDatabases(self):
        return self._session.catalog_manager.list_databases()

    def currentDatabase(self) -> str:
        return self._session.catalog_manager.current_database

    def setCurrentDatabase(self, name: str):
        self._session.catalog_manager.current_database = name.lower()

    def tableExists(self, name: str) -> bool:
        return self._session.catalog_manager.lookup_table(tuple(name.split("."))) is not None

    def dropTempView(self, name: str) -> bool:
        cm = self._session.catalog_manager
        if name.lower() in cm.temp_views:
            del cm.temp_views[name.lower()]
            return True
        return False


class Column:
    """Expression wrapper for the DataFrame API."""

    def __init__(self, expr: ex.Expr):
        self._expr = expr

    # arithmetic / comparison operators
    def _bin(self, other, op) -> "Column":
        return Column(ex.Function(op, (self._expr, _to_expr(other))))

    def __add__(self, o):
        return self._bin(o, "+")

    def __sub__(self, o):
        return self._bin(o, "-")

    def __mul__(self, o):
        return self._bin(o, "*")

    def __truediv__(self, o):
        return self._bin(o, "/")

    def __mod__(self, o):
        return self._bin(o, "%")

    def __radd__(self, o):
        return Column(ex.Function("+", (_to_expr(o), self._expr)))

    def __rsub__(self, o):
        return Column(ex.Function("-", (_to_expr(o), self._expr)))

    def __rmul__(self, o):
        return Column(ex.Function("*", (_to_expr(o), self._expr)))

    def __eq__(self, o):  # type: ignore[override]
        return self._bin(o, "==")

    def __ne__(self, o):  # type: ignore[override]
        return self._bin(o, "!=")

    def __lt__(self, o):
        return self._bin(o, "<")

    def __le__(self, o):
        return self._bin(o, "<=")

    def __gt__(self, o):
        return self._bin(o, ">")

    def __ge__(self, o):
        return self._bin(o, ">=")

    def __and__(self, o):
        return self._bin(o, "and")

    def __or__(self, o):
        return self._bin(o, "or")

    def __invert__(self):
        return Column(ex.Function("not", (self._expr,)))

    def __neg__(self):
        return Column(ex.Function("negative", (self._expr,)))

    def alias(self, name: str) -> "Column":
        return Column(ex.Alias(self._expr, (name,)))

    name = alias

    def cast(self, to) -> "Column":
        target = to if isinstance(to, dt.DataType) else _parse_type(to)
        return Column(ex.Cast(self._expr, target))

    def asc(self) -> "Column":
        return Column(ex.SortOrder(self._expr, True))

    def desc(self) -> "Column":
        return Column(ex.SortOrder(self._expr, False))

    def isNull(self) -> "Column":
        return Column(ex.Function("isnull", (self._expr,)))

    def isNotNull(self) -> "Column":
        return Column(ex.Function("isnotnull", (self._expr,)))

    def isin(self, *values) -> "Column":
        vals = values[0] if len(values) == 1 and isinstance(values[0], (list, tuple)) \
            else values
        return Column(ex.InList(self._expr, tuple(_to_expr(v) for v in vals)))

    def between(self, low, high) -> "Column":
        return Column(ex.Between(self._expr, _to_expr(low), _to_expr(high)))

    def like(self, pattern: str) -> "Column":
        return Column(ex.Like(self._expr, ex.lit(pattern)))

    def startswith(self, s) -> "Column":
        return Column(ex.Function("startswith", (self._expr, _to_expr(s))))

    def endswith(self, s) -> "Column":
        return Column(ex.Function("endswith", (self._expr, _to_expr(s))))

    def contains(self, s) -> "Column":
        return Column(ex.Function("contains", (self._expr, _to_expr(s))))

    def substr(self, start, length) -> "Column":
        return Column(ex.Function("substring",
                                  (self._expr, _to_expr(start), _to_expr(length))))

    def __hash__(self):
        return hash(self._expr)


def _to_expr(v) -> ex.Expr:
    if isinstance(v, Column):
        return v._expr
    if isinstance(v, ex.Expr):
        return v
    return ex.lit(v)


def _parse_type(s: str) -> dt.DataType:
    from .sql import parse_data_type
    return parse_data_type(s)


def _parse_ddl_schema(ddl: str) -> dt.StructType:
    """Parse 'a INT, b DECIMAL(10,2), c STRUCT<x: INT>' (comma split at
    depth 0 only, honoring () and <> nesting)."""
    from .sql import parse_data_type
    parts = []
    depth = 0
    start = 0
    for i, ch in enumerate(ddl):
        if ch in "(<":
            depth += 1
        elif ch in ")>":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(ddl[start:i])
            start = i + 1
    parts.append(ddl[start:])
    fields = []
    for part in parts:
        part = part.strip()
        if not part:
            continue
        name, _, typ = part.partition(" ")
        if not typ and ":" in part:
            name, _, typ = part.partition(":")
        fields.append(dt.StructField(name.strip(), _parse_type(typ.strip())))
    return dt.StructType(tuple(fields))


def col(name: str) -> Column:
    return Column(ex.Attribute(tuple(name.split("."))) if name != "*" else ex.Star())


def lit(v) -> Column:
    return Column(ex.lit(v))


class GroupedData:
    def __init__(self, df: "DataFrame", group_cols: Sequence[Column]):
        self._df = df
        self._group = tuple(_to_expr(c) for c in group_cols)

    def agg(self, *exprs) -> "DataFrame":
        items = tuple(self._group) + tuple(_to_expr(e) for e in exprs)
        plan = sp.Aggregate(self._df._plan, self._group, items)
        return DataFrame(plan, self._df._session)

    def _simple(self, fn: str, *cols) -> "DataFrame":
        targets = list(cols)
        if not targets:
            # PySpark default: aggregate every numeric non-group column
            group_names = {a.name[-1].lower() for a in self._group
                           if isinstance(a, ex.Attribute)}
            targets = [f.name for f in self._df.schema.fields
                       if f.data_type.is_numeric
                       and f.name.lower() not in group_names]
        aggs = [Column(ex.Alias(ex.Function(fn, (ex.Attribute((c,)),)),
                                (f"{fn}({c})",))) for c in targets]
        return self.agg(*aggs)

    def count(self) -> "DataFrame":
        return self.agg(Column(ex.Alias(ex.Function("count", (ex.Star(),)), ("count",))))

    def sum(self, *cols) -> "DataFrame":
        return self._simple("sum", *cols)

    def avg(self, *cols) -> "DataFrame":
        return self._simple("avg", *cols)

    def min(self, *cols) -> "DataFrame":
        return self._simple("min", *cols)

    def max(self, *cols) -> "DataFrame":
        return self._simple("max", *cols)

    def applyInPandas(self, func, schema) -> "DataFrame":
        """groupBy(...).applyInPandas — reference: sail-python-udf
        grouped-map kind (pyspark_udf.rs:19-27)."""
        from .functions.udf import UserDefinedFunction
        udf = UserDefinedFunction(func, _parse_ddl_struct(schema),
                                  "grouped_map", getattr(func, "__name__",
                                                         "applyInPandas"))
        return DataFrame(sp.GroupMap(self._df._plan, self._group, udf),
                         self._df._session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        return CoGroupedData(self, other)


class CoGroupedData:
    def __init__(self, left: GroupedData, right: GroupedData):
        self._left = left
        self._right = right

    def applyInPandas(self, func, schema) -> "DataFrame":
        from .functions.udf import UserDefinedFunction
        udf = UserDefinedFunction(func, _parse_ddl_struct(schema),
                                  "cogrouped_map",
                                  getattr(func, "__name__", "cogroup"))
        plan = sp.CoGroupMap(self._left._df._plan, self._right._df._plan,
                             self._left._group, self._right._group, udf)
        return DataFrame(plan, self._left._df._session)


def _parse_ddl_struct(schema):
    if isinstance(schema, dt.StructType):
        return schema
    return _parse_ddl_schema(str(schema))


class DataFrame:
    def __init__(self, plan: sp.QueryPlan, session: SparkSession):
        self._plan = plan
        self._session = session

    # -- transformations -------------------------------------------------
    def select(self, *cols) -> "DataFrame":
        exprs = tuple(_to_expr(c) if not isinstance(c, str)
                      else (ex.Star() if c == "*" else ex.Attribute(tuple(c.split("."))))
                      for c in cols)
        return DataFrame(sp.Project(self._plan, exprs), self._session)

    def selectExpr(self, *exprs: str) -> "DataFrame":
        from .sql.parser import Parser
        items = []
        for s in exprs:
            p = Parser(s)
            items.append(p.parse_select_item())
        return DataFrame(sp.Project(self._plan, tuple(items)), self._session)

    def filter(self, condition) -> "DataFrame":
        if isinstance(condition, str):
            from .sql import parse_expression
            cond = parse_expression(condition)
        else:
            cond = _to_expr(condition)
        return DataFrame(sp.Filter(self._plan, cond), self._session)

    where = filter

    def withColumn(self, name: str, c: Column) -> "DataFrame":
        alias = ex.Alias(_to_expr(c), (name,))
        return DataFrame(sp.WithColumns(self._plan, (alias,)), self._session)

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        return DataFrame(sp.WithColumnsRenamed(self._plan, ((old, new),)),
                         self._session)

    def drop(self, *cols: str) -> "DataFrame":
        return DataFrame(sp.Drop(self._plan, tuple(cols)), self._session)

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        how = {"outer": "full", "leftouter": "left", "rightouter": "right",
               "left_outer": "left", "right_outer": "right", "fullouter": "full",
               "leftsemi": "semi", "left_semi": "semi", "leftanti": "anti",
               "left_anti": "anti"}.get(how.lower(), how.lower())
        using: Tuple[str, ...] = ()
        condition = None
        if isinstance(on, str):
            using = (on,)
        elif isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            using = tuple(on)
        elif on is not None:
            condition = _to_expr(on)
        return DataFrame(sp.Join(self._plan, other._plan, how, condition, using),
                         self._session)

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(sp.Join(self._plan, other._plan, "cross"), self._session)

    def groupBy(self, *cols) -> GroupedData:
        gcols = [col(c) if isinstance(c, str) else c for c in cols]
        return GroupedData(self, gcols)

    groupby = groupBy

    def agg(self, *exprs) -> "DataFrame":
        return GroupedData(self, []).agg(*exprs)

    def orderBy(self, *cols) -> "DataFrame":
        keys = []
        for c in cols:
            e = _to_expr(col(c) if isinstance(c, str) else c)
            if not isinstance(e, ex.SortOrder):
                e = ex.SortOrder(e, True)
            keys.append(e)
        return DataFrame(sp.Sort(self._plan, tuple(keys)), self._session)

    sort = orderBy

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(sp.Limit(self._plan, n), self._session)

    def offset(self, n: int) -> "DataFrame":
        return DataFrame(sp.Offset(self._plan, n), self._session)

    def distinct(self) -> "DataFrame":
        return DataFrame(sp.Deduplicate(self._plan), self._session)

    def dropDuplicates(self, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        return DataFrame(sp.Deduplicate(self._plan, tuple(subset or ())),
                         self._session)

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(sp.SetOperation(self._plan, other._plan, "union", True),
                         self._session)

    unionAll = union

    def intersect(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(sp.SetOperation(self._plan, other._plan, "intersect", False),
                         self._session)

    def exceptAll(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(sp.SetOperation(self._plan, other._plan, "except", True),
                         self._session)

    def subtract(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(sp.SetOperation(self._plan, other._plan, "except", False),
                         self._session)

    def alias(self, name: str) -> "DataFrame":
        return DataFrame(sp.SubqueryAlias(self._plan, name), self._session)

    def repartition(self, n: int, *cols) -> "DataFrame":
        exprs = tuple(_to_expr(col(c) if isinstance(c, str) else c) for c in cols)
        return DataFrame(sp.Repartition(self._plan, n, exprs), self._session)

    def sample(self, withReplacement=None, fraction=None, seed=None) -> "DataFrame":
        # PySpark signature juggling: sample(fraction), sample(fraction, seed),
        # sample(withReplacement, fraction[, seed])
        if isinstance(withReplacement, float):
            withReplacement, fraction, seed = False, withReplacement, fraction
        if fraction is None:
            raise ValueError("sample() requires a fraction")
        return DataFrame(sp.Sample(self._plan, 0.0, float(fraction),
                                   bool(withReplacement), seed), self._session)

    def __getitem__(self, name: str) -> Column:
        return col(name)

    def __getattr__(self, name: str) -> Column:
        if name.startswith("_"):
            raise AttributeError(name)
        return col(name)

    def mapInPandas(self, func, schema, barrier: bool = False) -> "DataFrame":
        """mapInPandas — iterator-of-DataFrames UDF (reference:
        pyspark_map_iter_udf.rs)."""
        from .functions.udf import UserDefinedFunction
        udf = UserDefinedFunction(func, _parse_ddl_struct(schema),
                                  "map_pandas",
                                  getattr(func, "__name__", "mapInPandas"))
        return DataFrame(sp.MapPartitions(self._plan, udf, barrier),
                         self._session)

    def mapInArrow(self, func, schema, barrier: bool = False) -> "DataFrame":
        from .functions.udf import UserDefinedFunction
        udf = UserDefinedFunction(func, _parse_ddl_struct(schema),
                                  "map_arrow",
                                  getattr(func, "__name__", "mapInArrow"))
        return DataFrame(sp.MapPartitions(self._plan, udf, barrier),
                         self._session)

    # -- actions ------------------------------------------------------------
    def toArrow(self) -> pa.Table:
        return self._session._execute_query(self._plan)

    def toPandas(self):
        return self.toArrow().to_pandas()

    def collect(self) -> List[tuple]:
        table = self.toArrow()
        cols = [c.to_pylist() for c in table.columns]
        return [Row(zip(table.column_names, vals)) for vals in zip(*cols)] \
            if cols else []

    def count(self) -> int:
        plan = sp.Aggregate(self._plan, (),
                            (ex.Alias(ex.Function("count", (ex.Star(),)), ("count",)),))
        table = self._session._execute_query(plan)
        return int(table.column(0)[0].as_py())

    def first(self):
        rows = self.limit(1).collect()
        return rows[0] if rows else None

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        return rows[0] if n == 1 and rows else rows

    def take(self, n: int):
        return self.limit(n).collect()

    def show(self, n: int = 20, truncate: bool = True):
        print(self._show_string(n, truncate))

    def _show_string(self, n: int = 20, truncate: bool = True) -> str:
        table = self.limit(n).toArrow()
        names = table.column_names
        rows = [[_fmt_cell(v, truncate) for v in col.to_pylist()]
                for col in table.columns]
        widths = [max([len(nm)] + [len(r) for r in rs]) for nm, rs in zip(names, rows)]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        out = [sep, "|" + "|".join(f" {nm:<{w}} " for nm, w in zip(names, widths)) + "|", sep]
        for i in range(table.num_rows):
            out.append("|" + "|".join(
                f" {rows[j][i]:<{widths[j]}} " for j in range(len(names))) + "|")
        out.append(sep)
        return "\n".join(out)

    @property
    def schema(self) -> dt.StructType:
        node = self._session._resolve(self._plan)
        return dt.StructType(tuple(dt.StructField(f.name, f.dtype, f.nullable)
                                   for f in node.schema))

    @property
    def columns(self) -> List[str]:
        return [f.name for f in self.schema.fields]

    @property
    def dtypes(self) -> List[Tuple[str, str]]:
        return [(f.name, f.data_type.simple_string()) for f in self.schema.fields]

    def explain(self, extended: bool = False):
        from .plan.nodes import explain
        print(explain(self._session._resolve(self._plan)))

    def createOrReplaceTempView(self, name: str):
        self._session.catalog_manager.register_temp_view(name, self._plan)

    def createTempView(self, name: str):
        self._session.catalog_manager.register_temp_view(name, self._plan,
                                                         replace=False)

    def cache(self) -> "DataFrame":
        return self

    def persist(self, *_) -> "DataFrame":
        return self

    def unpersist(self) -> "DataFrame":
        return self

    def withWatermark(self, eventTime: str,
                      delayThreshold: str) -> "DataFrame":
        from .streaming import parse_delay
        return DataFrame(sp.WithWatermark(self._plan, eventTime,
                                          parse_delay(delayThreshold)),
                         self._session)

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    @property
    def writeStream(self):
        from .streaming import DataStreamWriter
        return DataStreamWriter(self)

    @property
    def isStreaming(self) -> bool:
        from .streaming import _find_stream_read
        return _find_stream_read(self._plan) is not None

    @property
    def sparkSession(self) -> SparkSession:
        return self._session


class Row(dict):
    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError as e:
            raise AttributeError(name) from e

    def __getitem__(self, key):
        if isinstance(key, int):
            return list(self.values())[key]
        return super().__getitem__(key)

    def __repr__(self):
        inner = ", ".join(f"{k}={v!r}" for k, v in self.items())
        return f"Row({inner})"


def _fmt_cell(v, truncate: bool) -> str:
    if v is None:
        return "NULL"
    s = str(v)
    if truncate and len(s) > 20:
        s = s[:17] + "..."
    return s


class DataFrameReader:
    def __init__(self, session: SparkSession):
        self._session = session
        self._format = "parquet"
        self._options: Dict[str, str] = {}
        self._schema: Optional[dt.StructType] = None

    def format(self, fmt: str) -> "DataFrameReader":
        self._format = fmt.lower()
        return self

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key.lower()] = str(value)
        return self

    def options(self, **opts) -> "DataFrameReader":
        for k, v in opts.items():
            self.option(k, v)
        return self

    def schema(self, schema) -> "DataFrameReader":
        if isinstance(schema, str):
            self._schema = _parse_ddl_schema(schema)
        else:
            self._schema = schema
        return self

    def load(self, path: Optional[Union[str, List[str]]] = None) -> DataFrame:
        paths = (path,) if isinstance(path, str) else tuple(path or ())
        plan = sp.ReadDataSource(self._format, paths, self._schema,
                                 tuple(self._options.items()))
        return DataFrame(plan, self._session)

    def parquet(self, *paths: str) -> DataFrame:
        return self.format("parquet").load(list(paths))

    def csv(self, path, header=None, sep=None, inferSchema=None, **kw) -> DataFrame:
        if header is not None:
            self.option("header", str(header).lower())
        if sep is not None:
            self.option("sep", sep)
        return self.format("csv").load(path)

    def json(self, path) -> DataFrame:
        return self.format("json").load(path)

    def table(self, name: str) -> DataFrame:
        return self._session.table(name)


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self._df = df
        self._format = "parquet"
        self._mode = "error"
        self._options: Dict[str, str] = {}
        self._partition_by: Tuple[str, ...] = ()

    def format(self, fmt: str) -> "DataFrameWriter":
        self._format = fmt.lower()
        return self

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = {"errorifexists": "error"}.get(m.lower(), m.lower())
        return self

    def option(self, key: str, value) -> "DataFrameWriter":
        self._options[key.lower()] = str(value)
        return self

    def partitionBy(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = tuple(cols)
        return self

    def save(self, path: str):
        from .io.formats import write_table
        table = self._df.toArrow()
        write_table(table, self._format, path, self._mode, self._options,
                    self._partition_by)

    def parquet(self, path: str):
        self.format("parquet").save(path)

    def csv(self, path: str, header=None):
        if header is not None:
            self.option("header", str(header).lower())
        self.format("csv").save(path)

    def json(self, path: str):
        self.format("json").save(path)

    def saveAsTable(self, name: str):
        session = self._df._session
        table = self._df.toArrow()
        from .spec.data_type import StructType
        entry = TableEntry(tuple(name.split(".")), _schema_of(table), table,
                           (), "memory")
        session.catalog_manager.register_table(
            entry, replace=(self._mode == "overwrite"),
            if_not_exists=(self._mode == "ignore"))

    def insertInto(self, name: str, overwrite: bool = False):
        session = self._df._session
        cmd = sp.InsertInto(tuple(name.split(".")), self._df._plan,
                            overwrite or self._mode == "overwrite")
        session._execute_command(cmd)


def _schema_of(table: pa.Table) -> dt.StructType:
    from .columnar.arrow_interop import arrow_type_to_spec
    return dt.StructType(tuple(
        dt.StructField(n, arrow_type_to_spec(c.type), True)
        for n, c in zip(table.column_names, table.columns)))


def _empty_table(schema: dt.StructType) -> pa.Table:
    from .columnar.arrow_interop import spec_type_to_arrow
    arrays = [pa.array([], type=spec_type_to_arrow(f.data_type))
              for f in schema.fields]
    return pa.Table.from_arrays(arrays, names=[f.name for f in schema.fields])
