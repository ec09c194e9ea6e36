"""Spark Connect gRPC service.

Reference role: crates/sail-spark-connect/src/server.rs:119-487 (the 11
SparkConnectService RPCs), src/executor.rs (reattachable result buffering),
src/service/plan_analyzer.rs (AnalyzePlan operations), and
src/config_manager.rs (Config). Served via grpc generic method handlers on
the vendored `spark.connect` protos so stock Spark Connect clients attach.
"""

from __future__ import annotations

import threading
import uuid
from concurrent import futures
from typing import Dict, List

import grpc

from . import convert  # noqa: F401  (ensures gen/ is importable first)

from spark.connect import base_pb2 as bpb
from spark.connect import commands_pb2 as cpb
from spark.connect import relations_pb2 as rpb

from ..spec import plan as sp
from .convert import (
    ConvertError,
    data_type_to_proto,
    relation_from_proto,
    schema_from_string,
)

_SERVICE = "spark.connect.SparkConnectService"
_SPARK_VERSION = "4.0.0"


def _ipc_chunks(table, chunk_rows: int = 65536) -> List[bytes]:
    import pyarrow as pa

    from .. import tracing as tr
    out = []
    n = max(table.num_rows, 0)
    with tr.span("rpc.encode", {"rows": n}) as sp:
        for start in range(0, max(n, 1), chunk_rows):
            chunk = table.slice(start, chunk_rows)
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, table.schema) as w:
                w.write_table(chunk)
            out.append((chunk.num_rows, sink.getvalue().to_pybytes()))
            if n == 0:
                break
        sp.attributes["bytes"] = sum(len(blob) for _rows, blob in out)
    return out


class _Operation:
    """A buffered operation for reattachable execution (reference:
    crates/sail-spark-connect/src/executor.rs:30-97)."""

    def __init__(self, operation_id: str):
        self.operation_id = operation_id
        self.responses: List[bpb.ExecutePlanResponse] = []
        self.complete = False
        self.released_until = -1  # highest response index released


class SparkConnectServer:
    """gRPC server speaking the Spark Connect protocol."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 session_timeout_s: float = 3600.0):
        from ..server import SessionManager

        self.sessions = SessionManager(session_timeout_s)
        self.server_side_session_ids: Dict[str, str] = {}
        self._operations: Dict[str, _Operation] = {}
        self._lock = threading.Lock()
        self._server = grpc.server(futures.ThreadPoolExecutor(max_workers=16))
        self._server.add_generic_rpc_handlers((self._handlers(),))
        self.port = self._server.add_insecure_port(f"{host}:{port}")
        self.host = host

    def start(self):
        self._server.start()
        return self

    def stop(self, grace: float = 1.0):
        self._server.stop(grace=grace)

    def wait(self):
        self._server.wait_for_termination()

    # ------------------------------------------------------------------
    # session helpers
    # ------------------------------------------------------------------
    def _session(self, session_id: str):
        session = self.sessions.get_or_create(session_id)
        with self._lock:
            if session_id not in self.server_side_session_ids:
                self.server_side_session_ids[session_id] = uuid.uuid4().hex
        return session

    def _server_session_id(self, session_id: str) -> str:
        return self.server_side_session_ids.get(session_id, "")

    @staticmethod
    def _abort(context, e: Exception):
        from ..exec.admission import DeadlineExceeded, ResourceExhausted
        if isinstance(e, ResourceExhausted):
            # typed, retryable load shed: the client backs off and
            # resubmits (nothing executed — no partial side effects)
            code = grpc.StatusCode.RESOURCE_EXHAUSTED
        elif isinstance(e, DeadlineExceeded):
            code = grpc.StatusCode.DEADLINE_EXCEEDED
        elif isinstance(e, (ConvertError, ValueError,
                            NotImplementedError)):
            code = grpc.StatusCode.INVALID_ARGUMENT
        else:
            code = grpc.StatusCode.INTERNAL
        context.abort(code, f"{type(e).__name__}: {e}")

    # ------------------------------------------------------------------
    # ExecutePlan
    # ------------------------------------------------------------------
    def _execute_plan(self, request: bpb.ExecutePlanRequest, context):
        from .. import tracing as tr
        parent = tr.extract_context(context.invocation_metadata())
        with tr.span("spark_connect:execute_plan",
                     {"session_id": request.session_id}, parent=parent):
            yield from self._execute_plan_traced(request, context)

    def _execute_plan_traced(self, request: bpb.ExecutePlanRequest, context):
        session = self._session(request.session_id)
        op_id = request.operation_id or str(uuid.uuid4())
        reattachable = any(
            o.HasField("reattach_options") and o.reattach_options.reattachable
            for o in request.request_options)
        op = _Operation(op_id)

        def mk(**kwargs):
            resp = bpb.ExecutePlanResponse(
                session_id=request.session_id,
                server_side_session_id=self._server_session_id(
                    request.session_id),
                operation_id=op_id,
                response_id=str(uuid.uuid4()), **kwargs)
            return resp

        try:
            which = request.plan.WhichOneof("op_type")
            if which == "root":
                from .. import tracing as tr
                with tr.span("rpc.decode"):
                    plan = relation_from_proto(request.plan.root)
                table = session._execute_query(plan)
                for rows, blob in _ipc_chunks(table):
                    op.responses.append(mk(
                        arrow_batch=bpb.ExecutePlanResponse.ArrowBatch(
                            row_count=rows, data=blob)))
            elif which == "command":
                for resp_kwargs in self._run_command(
                        session, request.plan.command):
                    op.responses.append(mk(**resp_kwargs))
            else:
                raise ConvertError(f"unsupported plan op_type: {which}")
        except Exception as e:  # noqa: BLE001 — errors cross the wire
            self._abort(context, e)
            return
        op.responses.append(mk(
            result_complete=bpb.ExecutePlanResponse.ResultComplete()))
        op.complete = True
        if reattachable:
            with self._lock:
                self._operations[(request.session_id, op_id)] = op
        for r in op.responses:
            yield r

    # ------------------------------------------------------------------
    # Commands (reference: src/service/plan_executor.rs:162-616)
    # ------------------------------------------------------------------
    def _run_command(self, session, command: cpb.Command):
        import pyarrow as pa

        which = command.WhichOneof("command_type")
        if which == "sql_command":
            sql = command.sql_command
            query = None
            if sql.HasField("input"):
                # Spark 4 wraps the SQL relation; older clients send `sql`
                rel = sql.input
                if rel.WhichOneof("rel_type") == "sql":
                    query = rel.sql.query
                else:
                    # non-SQL relation: execute eagerly, return the rows
                    table = session._execute_query(relation_from_proto(rel))
                    sink = pa.BufferOutputStream()
                    with pa.ipc.new_stream(sink, table.schema) as w:
                        w.write_table(table)
                    out = rpb.Relation()
                    out.local_relation.data = sink.getvalue().to_pybytes()
                    yield {"sql_command_result":
                           bpb.ExecutePlanResponse.SqlCommandResult(relation=out)}
                    return
            else:
                query = sql.sql
            from ..sql import parse_one
            plan = parse_one(query)
            if isinstance(plan, sp.CommandPlan):
                table = session._execute_command(plan)
                sink = pa.BufferOutputStream()
                with pa.ipc.new_stream(sink, table.schema) as w:
                    w.write_table(table)
                rel = rpb.Relation()
                rel.local_relation.data = sink.getvalue().to_pybytes()
                yield {"sql_command_result":
                       bpb.ExecutePlanResponse.SqlCommandResult(relation=rel)}
            else:
                # a query: hand the relation back for lazy execution
                rel = rpb.Relation()
                rel.sql.query = query
                yield {"sql_command_result":
                       bpb.ExecutePlanResponse.SqlCommandResult(relation=rel)}
            return
        if which == "create_dataframe_view":
            v = command.create_dataframe_view
            plan = relation_from_proto(v.input)
            session.catalog_manager.register_temp_view(
                v.name, plan, replace=v.replace)
            return
        if which == "write_operation":
            w = command.write_operation
            self._write_v1(session, w)
            return
        if which == "write_operation_v2":
            w2 = command.write_operation_v2
            self._write_v2(session, w2)
            return
        if which == "register_function":
            # cloudpickled UDF registration for SQL use (reference:
            # plan_executor.rs handle_register_user_defined_function)
            from .wire_udf import udf_from_proto
            cif = command.register_function
            session.udf.register(cif.function_name, udf_from_proto(cif))
            return
        if which == "register_data_source":
            # cloudpickled user DataSource class (reference:
            # formats/python/mod.rs registration path)
            import cloudpickle
            from .wire_udf import _install_pyspark_shim
            _install_pyspark_shim()
            rds = command.register_data_source
            obj = cloudpickle.loads(rds.python_data_source.command)
            cls = obj if isinstance(obj, type) else next(
                (x for x in obj if isinstance(x, type)), None)
            if cls is None:
                raise ValueError("data source payload contains no class")
            session.dataSource.register(cls, name=rds.name or None)
            return
        if which == "register_table_function":
            # cloudpickled UDTF handler class for SQL FROM-position use
            # (reference: plan_executor.rs register_user_defined_table_
            # function + pyspark_udtf.rs)
            from .wire_udf import udtf_from_proto
            tf = command.register_table_function
            handler, rt = udtf_from_proto(tf)
            session.udf.register_udtf(tf.function_name, handler, rt)
            return
        raise NotImplementedError(f"command {which} not supported yet")

    _SAVE_MODES = {
        cpb.WriteOperation.SAVE_MODE_APPEND: "append",
        cpb.WriteOperation.SAVE_MODE_OVERWRITE: "overwrite",
        cpb.WriteOperation.SAVE_MODE_ERROR_IF_EXISTS: "error",
        cpb.WriteOperation.SAVE_MODE_IGNORE: "ignore",
    }

    def _write_v1(self, session, w: cpb.WriteOperation):
        plan = relation_from_proto(w.input)
        fmt = w.source if w.HasField("source") else "parquet"
        mode = self._SAVE_MODES.get(w.mode, "error")
        save_type = w.WhichOneof("save_type")
        if save_type == "path":
            cmd = sp.WriteDataSource(
                plan, fmt, w.path, mode, tuple(w.partitioning_columns),
                tuple(sorted(w.options.items())))
        elif save_type == "table":
            name = tuple(w.table.table_name.split("."))
            if w.table.save_method == \
                    cpb.WriteOperation.SaveTable.TABLE_SAVE_METHOD_INSERT_INTO:
                cmd = sp.InsertInto(name, plan, overwrite=(mode == "overwrite"))
            else:
                cmd = sp.WriteDataSource(
                    plan, fmt, None, mode, tuple(w.partitioning_columns),
                    tuple(sorted(w.options.items())), name)
        else:
            raise ConvertError("write operation requires a path or table")
        session._execute_command(cmd)

    def _write_v2(self, session, w: cpb.WriteOperationV2):
        plan = relation_from_proto(w.input)
        name = tuple(w.table_name.split("."))
        mode_map = {
            cpb.WriteOperationV2.MODE_CREATE: "error",
            cpb.WriteOperationV2.MODE_OVERWRITE: "overwrite",
            cpb.WriteOperationV2.MODE_APPEND: "append",
            cpb.WriteOperationV2.MODE_REPLACE: "overwrite",
            cpb.WriteOperationV2.MODE_CREATE_OR_REPLACE: "overwrite",
        }
        mode = mode_map.get(w.mode, "error")
        fmt = w.provider if w.HasField("provider") else "parquet"
        session._execute_command(sp.WriteDataSource(
            plan, fmt, None, mode, (),
            tuple(sorted(w.options.items())), name))

    # ------------------------------------------------------------------
    # AnalyzePlan (reference: src/service/plan_analyzer.rs)
    # ------------------------------------------------------------------
    def _analyze_plan(self, request: bpb.AnalyzePlanRequest, context):
        session = self._session(request.session_id)
        resp = bpb.AnalyzePlanResponse(
            session_id=request.session_id,
            server_side_session_id=self._server_session_id(
                request.session_id))
        which = request.WhichOneof("analyze")
        try:
            if which == "schema":
                node = session._resolve(
                    relation_from_proto(request.schema.plan.root))
                from ..spec import data_type as dt
                st = dt.StructType(tuple(
                    dt.StructField(f.name, f.dtype, f.nullable)
                    for f in node.schema))
                resp.schema.schema.CopyFrom(data_type_to_proto(st))
            elif which == "explain":
                from ..plan.nodes import explain
                node = session._resolve(
                    relation_from_proto(request.explain.plan.root))
                resp.explain.explain_string = explain(node)
            elif which == "tree_string":
                from ..plan.nodes import explain
                node = session._resolve(
                    relation_from_proto(request.tree_string.plan.root))
                resp.tree_string.tree_string = explain(node)
            elif which == "is_local":
                resp.is_local.is_local = True
            elif which == "is_streaming":
                resp.is_streaming.is_streaming = False
            elif which == "input_files":
                plan = relation_from_proto(request.input_files.plan.root)
                resp.input_files.files.extend(_input_files(plan))
            elif which == "spark_version":
                resp.spark_version.version = _SPARK_VERSION
            elif which == "ddl_parse":
                st = schema_from_string(request.ddl_parse.ddl_string)
                resp.ddl_parse.parsed.CopyFrom(data_type_to_proto(st))
            elif which == "same_semantics":
                a = relation_from_proto(request.same_semantics.target_plan.root)
                b = relation_from_proto(request.same_semantics.other_plan.root)
                resp.same_semantics.result = (a == b)
            elif which == "semantic_hash":
                plan = relation_from_proto(request.semantic_hash.plan.root)
                resp.semantic_hash.result = hash(plan) & 0x7FFFFFFF
            elif which == "persist":
                resp.persist.SetInParent()  # no-op, as in the reference
            elif which == "unpersist":
                resp.unpersist.SetInParent()
            elif which == "get_storage_level":
                resp.get_storage_level.storage_level.use_memory = True
            elif which == "json_to_ddl":
                import json as _json
                from ..spec.schema_json import schema_from_json
                st = schema_from_json(_json.loads(
                    request.json_to_ddl.json_string))
                resp.json_to_ddl.ddl_string = ", ".join(
                    f"{f.name} {f.data_type.simple_string()}"
                    for f in st.fields)
            else:
                raise NotImplementedError(f"analyze op {which}")
        except Exception as e:  # noqa: BLE001
            self._abort(context, e)
        return resp

    # ------------------------------------------------------------------
    # Config (reference: src/config_manager.rs)
    # ------------------------------------------------------------------
    def _config(self, request: bpb.ConfigRequest, context):
        session = self._session(request.session_id)
        resp = bpb.ConfigResponse(
            session_id=request.session_id,
            server_side_session_id=self._server_session_id(
                request.session_id))
        op = request.operation
        which = op.WhichOneof("op_type")
        conf = session.conf
        if which == "set":
            for kv in op.set.pairs:
                conf.set(kv.key, kv.value)
        elif which == "get":
            for k in op.get.keys:
                v = conf.get(k)
                resp.pairs.add(key=k, value=v if v is not None else "")
        elif which == "get_with_default":
            for kv in op.get_with_default.pairs:
                v = conf.get(kv.key)
                pair = resp.pairs.add(key=kv.key)
                pair.value = v if v is not None else kv.value
        elif which == "get_option":
            for k in op.get_option.keys:
                v = conf.get(k)
                pair = resp.pairs.add(key=k)
                if v is not None:
                    pair.value = v
        elif which == "get_all":
            prefix = op.get_all.prefix if op.get_all.HasField("prefix") else ""
            for k, v in sorted(conf.items()):
                if k.startswith(prefix):
                    resp.pairs.add(key=k, value=v)
        elif which == "unset":
            for k in op.unset.keys:
                conf.reset(k)
        elif which == "is_modifiable":
            for k in op.is_modifiable.keys:
                resp.pairs.add(key=k, value="true")
        return resp

    # ------------------------------------------------------------------
    # Reattach / release / session lifecycle
    # ------------------------------------------------------------------
    def _reattach_execute(self, request: bpb.ReattachExecuteRequest, context):
        key = (request.session_id, request.operation_id)
        with self._lock:
            op = self._operations.get(key)
        if op is None:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          f"unknown operation {request.operation_id}")
            return
        start = 0
        if request.HasField("last_response_id") and request.last_response_id:
            for i, r in enumerate(op.responses):
                if r.response_id == request.last_response_id:
                    start = i + 1
                    break
        for r in op.responses[start:]:
            yield r

    def _release_execute(self, request: bpb.ReleaseExecuteRequest, context):
        key = (request.session_id, request.operation_id)
        if request.WhichOneof("release") == "release_all":
            with self._lock:
                self._operations.pop(key, None)
        return bpb.ReleaseExecuteResponse(
            session_id=request.session_id,
            server_side_session_id=self._server_session_id(
                request.session_id),
            operation_id=request.operation_id)

    def _release_session(self, request: bpb.ReleaseSessionRequest, context):
        self.sessions.release(request.session_id)
        with self._lock:
            self.server_side_session_ids.pop(request.session_id, None)
            for key in [k for k in self._operations
                        if k[0] == request.session_id]:
                del self._operations[key]
        return bpb.ReleaseSessionResponse(session_id=request.session_id)

    def _interrupt(self, request: bpb.InterruptRequest, context):
        return bpb.InterruptResponse(
            session_id=request.session_id,
            server_side_session_id=self._server_session_id(
                request.session_id))

    def _fetch_error_details(self, request, context):
        return bpb.FetchErrorDetailsResponse(
            session_id=request.session_id,
            server_side_session_id=self._server_session_id(
                request.session_id))

    def _add_artifacts(self, request_iterator, context):
        # Reference parity: artifacts are unsupported (reference returns a
        # todo error — src/service/artifact_manager.rs:12-24); drain and ack.
        names = []
        for req in request_iterator:
            if req.HasField("batch"):
                names.extend(a.name for a in req.batch.artifacts)
        resp = bpb.AddArtifactsResponse()
        for n in names:
            resp.artifacts.add(name=n, successful=False)
        return resp

    def _artifact_status(self, request, context):
        out = bpb.ArtifactStatusesResponse()
        for name in request.names:
            out.statuses[name].exists = False
        return out

    def _clone_session(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED,
                      "clone_session is not implemented")

    # ------------------------------------------------------------------
    # handler table
    # ------------------------------------------------------------------
    def _handlers(self):
        def u(fn, req_cls):
            return grpc.unary_unary_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=lambda m: m.SerializeToString())

        def us(fn, req_cls):
            return grpc.unary_stream_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=lambda m: m.SerializeToString())

        def su(fn, req_cls):
            return grpc.stream_unary_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=lambda m: m.SerializeToString())

        return grpc.method_handlers_generic_handler(_SERVICE, {
            "ExecutePlan": us(self._execute_plan, bpb.ExecutePlanRequest),
            "AnalyzePlan": u(self._analyze_plan, bpb.AnalyzePlanRequest),
            "Config": u(self._config, bpb.ConfigRequest),
            "AddArtifacts": su(self._add_artifacts, bpb.AddArtifactsRequest),
            "ArtifactStatus": u(self._artifact_status,
                                bpb.ArtifactStatusesRequest),
            "Interrupt": u(self._interrupt, bpb.InterruptRequest),
            "ReattachExecute": us(self._reattach_execute,
                                  bpb.ReattachExecuteRequest),
            "ReleaseExecute": u(self._release_execute,
                                bpb.ReleaseExecuteRequest),
            "ReleaseSession": u(self._release_session,
                                bpb.ReleaseSessionRequest),
            "FetchErrorDetails": u(self._fetch_error_details,
                                   bpb.FetchErrorDetailsRequest),
            "CloneSession": u(self._clone_session, bpb.CloneSessionRequest),
        })


def _input_files(plan: sp.QueryPlan) -> List[str]:
    files: List[str] = []

    def walk(p):
        if isinstance(p, sp.ReadDataSource):
            files.extend(p.paths)
        for f in getattr(p, "__dataclass_fields__", {}):
            v = getattr(p, f)
            if isinstance(v, sp.QueryPlan):
                walk(v)
            elif isinstance(v, tuple):
                for x in v:
                    if isinstance(x, sp.QueryPlan):
                        walk(x)

    walk(plan)
    return files
