"""One span tree per statement (PR 26): ``tracing.span`` is the one
recorder; a ``QueryProfile`` keeps the spans opened beneath its ``query``
span, phases are spans, the executor's operators, program dispatches,
compiles, host syncs, uploads and prefetch waits lie under ``execute``,
and the OTLP exporter receives the same tree."""

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from sail_tpu import SparkSession, profiler
from sail_tpu import tracing as tr
from sail_tpu.exec.local import clear_caches
from test_tracing import _Collector


def _session(**conf):
    base = {"spark.sail.execution.mesh": "off",
            "spark.sail.cache.result.enabled": "false",
            "spark.sail.execution.backend.force": "xla"}
    base.update(conf)
    return SparkSession(base)


def _lineitem(n=4000):
    return pa.table({
        "l_quantity": pa.array([float(i % 50 + 1) for i in range(n)]),
        "l_extendedprice": pa.array([100.0 + i for i in range(n)]),
        "l_discount": pa.array([(i % 11) / 100.0 for i in range(n)]),
        "l_orderkey": pa.array([i // 4 for i in range(n)], pa.int64()),
    })


def _orders(n=1000):
    return pa.table({
        "o_orderkey": pa.array(list(range(n)), pa.int64()),
        "o_custkey": pa.array([i % 97 for i in range(n)], pa.int64()),
    })


Q6 = ("SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem "
      "WHERE l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")
JOIN = ("SELECT o_custkey, sum(l_extendedprice) AS s FROM lineitem "
        "JOIN orders ON l_orderkey = o_orderkey "
        "GROUP BY o_custkey ORDER BY s DESC LIMIT 5")


@pytest.fixture()
def spark(tmp_path):
    clear_caches()
    s = _session()
    for name, table in (("lineitem", _lineitem()), ("orders", _orders())):
        path = str(tmp_path / name)
        pq.write_to_dataset(table, path)
        s.read.parquet(path).createOrReplaceTempView(name)
    yield s
    clear_caches()


def _tree(profile):
    spans = list(profile.spans)
    return spans, {s.span_id: s for s in spans}


# -- the tree ---------------------------------------------------------------

def test_a_statement_leaves_one_rooted_span_tree(spark):
    spark.sql(JOIN).toArrow()
    p = profiler.last_profile()
    spans, by_id = _tree(p)
    roots = [s for s in spans if s.parent_id is None]
    assert [s.name for s in roots] == ["query"]
    assert len({s.trace_id for s in spans}) == 1
    assert p.trace_id == roots[0].trace_id
    assert p.spans_dropped == 0
    for s in spans:
        if s is roots[0]:
            continue
        parent = by_id[s.parent_id]          # every parent is present
        if s.thread_id == parent.thread_id:  # and holds its children
            assert parent.start_ns <= s.start_ns, (s.name, parent.name)
            assert s.end_ns <= parent.end_ns, (s.name, parent.name)
    names = {s.name for s in spans}
    assert {"admission", "resolve", "resolve.read_source", "optimize",
            "execute", "fetch", "op.JoinExec", "op.ScanExec", "dispatch",
            "sync", "upload"} <= names
    assert roots[0].attributes["query.id"] == p.query_id
    assert roots[0].attributes["rss_mb_end"] > 0
    read = [s for s in spans if s.name == "resolve.read_source"]
    assert len(read) == 2
    # the footer's bytes, not the file's: no view is decoded to resolve
    assert all(s.attributes["format"] == "parquet"
               and s.attributes["schema_source"] == "footer"
               and 0 < s.attributes["bytes_read"] < 4096
               and s.attributes["files"] >= 1 for s in read)
    assert [s["name"] for s in p.to_dict()["spans"]] == \
        [s.name for s in spans]


def test_phases_are_what_they_were_and_equal_their_spans(spark):
    spark.sql(Q6).toArrow()
    p = profiler.last_profile()
    assert list(p.phases) == ["parse", "resolve", "optimize", "execute",
                              "compile", "fetch"]
    for name in ("resolve", "optimize", "execute", "fetch"):
        assert p.phases[name] == pytest.approx(p.span_ms(name), abs=1e-6)
    spans, _by_id = _tree(p)
    execute = max((s for s in spans if s.name == "execute"),
                  key=lambda s: s.ms)
    kids = [s for s in spans if s.parent_id == execute.span_id]
    assert kids and p.phases["execute"] >= sum(s.ms for s in kids)
    # compile is still accounted inside execute, by note_compile_time
    assert p.phases["compile"] == pytest.approx(p.compile_ms)
    assert p.phases["compile"] == pytest.approx(p.span_ms("compile"),
                                                rel=0.05, abs=1.0)
    spark.sql(Q6).toArrow()                     # warm: nothing compiles
    warm = profiler.last_profile()
    assert "compile" not in warm.phases
    assert warm.compiled_programs == 0 and warm.span_count("compile") == 0


def test_self_ms_on_a_hand_built_tree():
    def span(name, sid, parent, start, end, thread=1):
        return tr.Span(trace_id="t" * 32, span_id=sid, parent_id=parent,
                       name=name, start_ns=start * 10**6,
                       end_ns=end * 10**6, thread_id=thread)

    p = profiler.QueryProfile(query_id="q")
    for s in (span("query", "a", None, 0, 100),
              span("execute", "b", "a", 10, 90),
              span("sync", "c", "b", 20, 40),
              span("sync", "d", "b", 30, 50),       # overlaps c by 10
              span("scan.decode", "e", "b", 10, 90, thread=2),
              span("fetch", "f", "a", 90, 95),
              span("sync", "g", "f", 91, 94)):
        p.add_span(s)
    assert p.span_ms("sync") == 43 and p.span_count("sync") == 3
    assert p.span_ms("sync", under="execute") == 40
    assert p.span_count("sync", under="execute") == 2
    assert p.span_count("sync", under="fetch") == 1
    # children union 20..50 on the execute thread; the decode on
    # another thread runs beside execute, not in it
    assert p.self_ms("execute") == 80 - 30
    assert p.self_ms("query") == 100 - 80 - 5
    assert p.self_ms("fetch") == 2
    assert p.self_ms("absent") == 0 and p.span_ms("absent") == 0


def test_spans_past_the_bound_are_counted_not_kept(monkeypatch):
    monkeypatch.setattr(profiler, "_SPANS_MAX", 10)
    with profiler.profile_query("bounded") as p:
        for _ in range(8):
            with tr.span("outer"):
                with tr.span("inner"):
                    pass
    spans, by_id = _tree(p)
    assert len(spans) == 11                 # the bound, and the root
    assert p.spans_dropped == 16 + 1 - 10   # + the closing finalize
    assert sum(1 for s in spans if s.name == "query") == 1
    for s in spans:                         # no span kept without its parent
        assert s.parent_id is None or s.parent_id in by_id
    assert p.to_dict()["spans_dropped"] == 7


def test_an_rpc_span_around_the_query_lands_in_its_profile():
    with tr.span("spark_connect:execute_plan") as rpc:
        with tr.span("rpc.decode"):
            pass
        with profiler.profile_query("inside") as p:
            pass
        with tr.span("rpc.encode"):
            pass
    spans, by_id = _tree(p)
    assert [s.name for s in spans] == [
        "rpc.decode", "finalize", "query", "rpc.encode",
        "spark_connect:execute_plan"]
    assert all(s.parent_id == rpc.span_id for s in spans
               if s.name in ("rpc.decode", "query", "rpc.encode"))
    assert p.span_ms("spark_connect:execute_plan") >= p.span_ms("query")


# -- the executor's spans ------------------------------------------------------

def test_a_join_counts_its_host_syncs(spark):
    spark.sql(JOIN).toArrow()
    spark.sql(JOIN).toArrow()
    p = profiler.last_profile()
    syncs = [s for s in p.spans if s.name == "sync"]
    assert p.host_syncs == len(syncs) >= 2
    assert p.span_count("sync", under="execute") >= 1
    assert "join_phase" in {s.attributes["site"] for s in syncs}
    assert all(s.attributes["bytes"] > 0 for s in syncs)
    assert p.sync_wait_ms == pytest.approx(p.span_ms("sync"))
    assert p.to_dict()["host_syncs"] == p.host_syncs


def test_a_resident_q6_shaped_scan_syncs_as_often_as_the_code_says(spark):
    spark.sql(Q6).toArrow()
    spark.sql(Q6).toArrow()
    p = profiler.last_profile()
    # _agg_with_chain fetches (n_groups, overflow) once; no hint, so no
    # second pass; to_arrow fetches the answer once, under fetch
    assert [s.attributes["site"] for s in p.spans if s.name == "sync"] == \
        ["agg.n_groups", "to_arrow"]
    assert p.span_count("sync", under="execute") == 1
    assert p.span_count("sync", under="fetch") == 1
    assert p.host_syncs == 2
    # the scan is resident: the second execution uploads nothing
    assert p.span_count("upload") == 0 and p.transfer_bytes == 0


def test_dispatch_spans_carry_the_program_name(spark):
    spark.sql(Q6).toArrow()
    p = profiler.last_profile()
    dispatches = [s for s in p.spans if s.name == "dispatch"]
    assert dispatches
    for s in dispatches:
        assert s.attributes["program"].startswith("sail_")
    compiles = [s for s in p.spans if s.name == "compile"]
    assert len(compiles) == p.compiled_programs >= 1
    by_id = {s.span_id: s for s in p.spans}
    for s in compiles:
        assert by_id[s.parent_id].name == "dispatch"
        assert by_id[s.parent_id].attributes["program"] == \
            s.attributes["program"]
        assert s.attributes["source"] == "trace"
        assert s.attributes["cause"] in p.retrace_causes


def test_scan_decode_is_parented_to_the_consuming_operator(tmp_path):
    clear_caches()
    s = _session(**{"spark.sail.scan.chunkRows": "1000"})
    path = str(tmp_path / "lineitem")
    pq.write_to_dataset(_lineitem(), path)
    s.read.parquet(path).createOrReplaceTempView("lineitem")
    s.sql(Q6).toArrow()
    p = profiler.last_profile()
    spans, by_id = _tree(p)
    decodes = [sp for sp in spans if sp.name == "scan.decode"]
    waits = [sp for sp in spans if sp.name == "scan.wait"]
    assert len(decodes) >= 4 and len(waits) >= 4
    (aggregate,) = {sp.parent_id for sp in waits}
    assert by_id[aggregate].name == "op.AggregateExec"
    for sp in decodes:
        assert sp.parent_id == aggregate
        assert sp.thread_id != by_id[aggregate].thread_id
        assert sp.trace_id == p.trace_id
    # the decode runs beside the operator: not taken off its self time
    assert p.self_ms("op.AggregateExec") <= p.span_ms("op.AggregateExec")
    assert p.span_ms("scan.wait") > 0
    clear_caches()


def test_explain_analyze_prefetch_line_is_fed_from_the_wait_spans(tmp_path):
    clear_caches()
    s = _session(**{"spark.sail.scan.chunkRows": "1000"})
    path = str(tmp_path / "lineitem")
    pq.write_to_dataset(_lineitem(), path)
    s.read.parquet(path).createOrReplaceTempView("lineitem")
    text = s.sql("EXPLAIN ANALYZE " + Q6).toArrow().column(0)[0].as_py()
    line = [ln for ln in text.splitlines() if "ScanPrefetch" in ln][0]
    assert "prefetched=4" in line and "consumer_wait=" in line
    # the operator tree is still there, rows and all, from the one wrapper
    assert "AggregateExec" in text and "rows=" in text
    clear_caches()


# -- OTLP ------------------------------------------------------------------------

def test_the_exported_query_span_is_the_parent_of_the_phase_spans(spark):
    c = _Collector()
    tr.configure_exporter(c.endpoint)
    try:
        spark.sql(Q6).toArrow()
        p = profiler.last_profile()
        tr.flush()
    finally:
        tr.configure_exporter(None)
        c.stop()
    mine = [s for s in c.spans if s["traceId"] == p.trace_id]
    queries = [s for s in mine if s["name"] == "query"]
    assert len(queries) == 1
    query = queries[0]
    assert "parentSpanId" not in query
    attrs = {a["key"]: a["value"] for a in query["attributes"]}
    assert attrs["query.id"]["stringValue"] == p.query_id
    assert attrs["query.status"]["stringValue"] == "succeeded"
    assert float(attrs["query.phase.execute_ms"]["doubleValue"]) == \
        pytest.approx(p.phases["execute"], abs=0.01)
    phases = [s for s in mine if s["name"] in
              ("resolve", "optimize", "execute", "fetch")]
    assert {s["name"] for s in phases} == {"resolve", "optimize",
                                           "execute", "fetch"}
    assert all(s["parentSpanId"] == query["spanId"] for s in phases)
    # what the exporter got is what the profile kept
    assert {s["spanId"] for s in mine} == {s.span_id for s in p.spans}
