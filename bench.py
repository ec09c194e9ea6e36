"""Benchmark driver: TPC-H Q1 at SF1 through the full engine
(SQL parse → plan → optimize → device execution).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference's published run completes Q1 at SF100 in 5.554 s on
a 16-vCPU r8g.4xlarge (docs/introduction/benchmark-results/_data/
events-sail.json); linearly scaled to SF1 → 0.0555 s. vs_baseline =
baseline_seconds / our_seconds (>1 = faster than the reference).

Timing is steady-state (best of 3 after a compile-warming run): XLA traces
the query's kernels on first execution; the cache is keyed by batch
capacity buckets, so repeated queries of similar size skip compilation.
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import time

import numpy as np

BASELINE_Q1_SF1_S = 5.554 / 100.0


def generate_lineitem_sf(sf: float, seed: int = 0):
    """Vectorized lineitem generator (full schema, fast string columns)."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    n_order = int(1_500_000 * sf)
    lines_per = rng.integers(1, 8, n_order)
    n = int(lines_per.sum())
    epoch = datetime.date(1970, 1, 1)
    start = (datetime.date(1992, 1, 1) - epoch).days
    end = (datetime.date(1998, 8, 2) - epoch).days
    okey = np.repeat(np.arange(1, n_order + 1) * 4 - 3, lines_per)
    odate = np.repeat(rng.integers(start, end - 151, n_order), lines_per)
    qty = rng.integers(1, 51, n)
    part = rng.integers(1, int(200_000 * max(sf, 0.005)) + 1, n)
    price = np.round(qty * ((90000 + (part % 200001) / 10 + 100 * (part % 1000)) / 100), 2)
    disc = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    ship = odate + rng.integers(1, 122, n)
    commit = odate + rng.integers(30, 92, n)
    receipt = ship + rng.integers(1, 31, n)
    cutoff = (datetime.date(1995, 6, 17) - epoch).days
    returnflag = np.where(receipt <= cutoff, rng.choice(["R", "A"], n), "N")
    linestatus = np.where(ship > cutoff, "O", "F")
    comments = rng.choice(np.array([
        "carefully final deposits", "quickly regular packages",
        "slyly special requests", "blithely even theodolites",
        "furiously bold accounts", "pending unusual ideas",
    ]), n)

    def dec(v):
        return pa.array(v).cast(pa.float64()).cast(pa.decimal128(15, 2), safe=False)

    return pa.table({
        "l_orderkey": pa.array(okey, type=pa.int64()),
        "l_partkey": pa.array(part, type=pa.int64()),
        "l_suppkey": pa.array(part % 10_000 + 1, type=pa.int64()),
        "l_linenumber": pa.array(np.concatenate(
            [np.arange(1, c + 1) for c in lines_per]), type=pa.int32()),
        "l_quantity": dec(qty.astype(np.float64)),
        "l_extendedprice": dec(price),
        "l_discount": dec(disc),
        "l_tax": dec(tax),
        "l_returnflag": pa.array(returnflag),
        "l_linestatus": pa.array(linestatus),
        "l_shipdate": pa.array(ship.astype("datetime64[D]")),
        "l_commitdate": pa.array(commit.astype("datetime64[D]")),
        "l_receiptdate": pa.array(receipt.astype("datetime64[D]")),
        "l_shipinstruct": pa.array(rng.choice(
            np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                      "TAKE BACK RETURN"]), n)),
        "l_shipmode": pa.array(rng.choice(
            np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                      "FOB"]), n)),
        "l_comment": pa.array(comments),
    })


def _profile_summary():
    """Compile/execute split of the most recent query profile — lets the
    bench artifact track the compile-vs-execute trend across rounds."""
    try:
        from sail_tpu import profiler
        prof = profiler.last_profile()
        if prof is None:
            return None
        phases = dict(prof.phases)
        out = {
            "compile_ms": round(prof.compile_ms, 2),
            "execute_ms": round(phases.get("execute", 0.0), 2),
            "cache_hits": prof.compile_cache_hits,
            "cache_misses": prof.compile_cache_misses,
        }
        if prof.rtf_built or prof.rtf_rows_pruned:
            out["runtime_filter"] = {
                "filters_built": prof.rtf_built,
                "filters_pushed": prof.rtf_pushed,
                "rows_pruned": prof.rtf_rows_pruned,
            }
        # critical-path category breakdown (flight-data recorder):
        # event-derived for cluster queries, phase-derived locally
        cp = prof.critical_path_summary()
        if cp is not None:
            out["critical_path"] = cp
        return out
    except Exception:  # noqa: BLE001 — profiling must never fail a bench
        return None


def _run_q1(spark, sf: float):
    """Generate lineitem at ``sf``, run Q1 to steady state; returns
    (best_seconds, rows, scanned_bytes, profile_summary)."""
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    from sail_tpu.exec.local import clear_caches

    clear_caches()
    table = generate_lineitem_sf(sf)
    spark.createDataFrame(table).createOrReplaceTempView("lineitem")
    q1 = QUERIES[1]
    spark.sql(q1).toArrow()  # warm-up: traces + compiles + uploads
    warm_profile = _profile_summary()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        spark.sql(q1).toArrow()
        times.append(time.perf_counter() - t0)
    # bytes the query touches per run (7 columns of the projected scan)
    cols = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate"]
    scanned = sum(table.column(c).nbytes for c in cols)
    steady_profile = _profile_summary()
    profile = {"warm": warm_profile, "steady": steady_profile}
    return min(times), table.num_rows, scanned, profile


def _run_suite(spark, sf: float, budget_s: float = 420.0):
    """All 22 TPC-H queries once (steady state); returns {q: seconds}.
    Stops recording (marks remaining as skipped) once the time budget is
    exhausted so the whole bench stays inside the driver's timeout."""
    from sail_tpu.benchmarks.tpch_data import register_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES

    register_tpch(spark, sf=sf)
    out = {}
    t_start = time.perf_counter()
    # q22 first: iterating in numeric order let it fall off the end of the
    # budget in every round, so the artifact never recorded it. The FIRST
    # query is exempt from the budget check entirely — a long headline
    # run must not zero out the whole suite (r05 recorded q22 as
    # "skipped: budget" even at position one).
    order = [22] + [q for q in sorted(QUERIES) if q != 22]
    for qi, q in enumerate(order):
        sql = QUERIES[q]
        if qi > 0 and time.perf_counter() - t_start > budget_s:
            out[q] = "skipped: budget"
            continue
        try:
            spark.sql(sql).toArrow()  # warm
            warm = _profile_summary()
            t0 = time.perf_counter()
            spark.sql(sql).toArrow()
            rec = {"seconds": round(time.perf_counter() - t0, 4)}
            steady = _profile_summary()
            if steady is not None:
                rec["profile"] = {"warm": warm, "steady": steady}
            out[q] = rec
        except Exception as e:  # noqa: BLE001 — a failed query is data
            out[q] = f"error: {type(e).__name__}"
        print(f"bench: q{q} = {out[q]}", file=sys.stderr, flush=True)
    return out


def _run_clickbench(spark, n_rows: int = 100_000, budget_s: float = 180.0):
    """The 43-query ClickBench suite over synthetic hits; {q: seconds}."""
    from sail_tpu.benchmarks.clickbench import load_queries, register_hits

    register_hits(spark, n_rows=n_rows)
    out = {}
    t_start = time.perf_counter()
    for i, sql in enumerate(load_queries(), 1):
        if time.perf_counter() - t_start > budget_s:
            out[i] = "skipped: budget"
            continue
        try:
            t0 = time.perf_counter()
            spark.sql(sql).toArrow()
            rec = {"seconds": round(time.perf_counter() - t0, 4)}
            prof = _profile_summary()
            if prof is not None:
                rec["profile"] = prof
            out[i] = rec
        except Exception as e:  # noqa: BLE001
            out[i] = f"error: {type(e).__name__}"
        print(f"bench: cb{i} = {out[i]}", file=sys.stderr, flush=True)
    return out


def _result_cache_summary(enabled: bool) -> dict:
    """Whole-run reuse-layer counters for the headline artifact."""
    from sail_tpu import metrics as gm

    def total(name):
        return int(sum(r["value"] for r in gm.REGISTRY.snapshot()
                       if r["name"] == name))

    hits = total("execution.result_cache.hit_count")
    misses = total("execution.result_cache.miss_count")
    return {
        "enabled": enabled,
        "hit_count": hits,
        "miss_count": misses,
        "hit_ratio": round(hits / (hits + misses), 3)
        if hits + misses else 0.0,
        "bytes_served": total("execution.result_cache.bytes_served"),
        "evicted_count": total("execution.result_cache.evicted_count"),
        "invalidated_count": total(
            "execution.result_cache.invalidated_count"),
        "scan_share_attached": total("execution.scan_share.attached_count"),
        "decode_passes_saved": total(
            "execution.scan_share.decode_passes_saved"),
    }


def _run_cache_bench(spark, k: int) -> dict:
    """SAIL_BENCH_CACHE=K: dashboard-replay artifact. The 43 ClickBench
    queries against one parquet-backed hits table, replayed by K
    concurrent sessions. Leg 1 (cold) is one session's first pass —
    real decode + compute. Leg 2 (warm) is all K sessions replaying the
    same pass concurrently, served from the result cache. Records the
    cold/warm wall-clock split, result-cache hit ratio, and decode
    passes saved by concurrent-scan sharing; acceptance is warm
    per-session latency roughly constant in K."""
    import shutil
    import tempfile
    import threading

    import pyarrow.parquet as pq

    from sail_tpu import SparkSession
    from sail_tpu import metrics as gm
    from sail_tpu.benchmarks.clickbench import generate_hits, load_queries

    def total(name):
        return sum(r["value"] for r in gm.REGISTRY.snapshot()
                   if r["name"] == name)

    n_rows = int(os.environ.get("SAIL_BENCH_CACHE_ROWS", "100000"))
    queries = load_queries()
    tmp = tempfile.mkdtemp(prefix="sail-cache-bench-")
    try:
        d = os.path.join(tmp, "hits")
        os.makedirs(d)
        pq.write_table(generate_hits(n_rows),
                       os.path.join(d, "part0.parquet"))
        # path-backed scans: every session fingerprints to the same
        # result keys, so the warm leg is cross-session reuse
        sessions = [SparkSession({}) for _ in range(k)]
        for s in sessions:
            s.read.parquet(d).createOrReplaceTempView("hits")

        def run_pass(s):
            t0 = time.perf_counter()
            errors = 0
            for sql_q in queries:
                try:
                    s.sql(sql_q).toArrow()
                except Exception:  # noqa: BLE001 — a failed query is data
                    errors += 1
            return time.perf_counter() - t0, errors

        h0, m0 = total("execution.result_cache.hit_count"), \
            total("execution.result_cache.miss_count")
        saved0 = total("execution.scan_share.decode_passes_saved")
        cold_s, cold_errors = run_pass(sessions[0])

        warm_s = [None] * k

        def warm(i):
            warm_s[i], _ = run_pass(sessions[i])

        threads = [threading.Thread(target=warm, args=(i,))
                   for i in range(k)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        warm_wall = time.perf_counter() - t0
        hits = total("execution.result_cache.hit_count") - h0
        misses = total("execution.result_cache.miss_count") - m0
        return {
            "sessions": k,
            "queries": len(queries),
            "rows": n_rows,
            "cold_seconds": round(cold_s, 4),
            "cold_errors": cold_errors,
            "warm_wall_seconds": round(warm_wall, 4),
            "warm_session_seconds": [round(s, 4) for s in warm_s],
            "warm_session_max": round(max(warm_s), 4),
            "hit_ratio": round(hits / (hits + misses), 3)
            if hits + misses else 0.0,
            "decode_passes_saved": int(
                total("execution.scan_share.decode_passes_saved")
                - saved0),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_chaos(spark) -> dict:
    """SAIL_BENCH_CHAOS=1: run one TPC-H query through the local
    cluster twice — clean, then under a fixed fault seed (one dropped
    shuffle fetch + one straggler task) — and record the recovery
    overhead and result equivalence in the artifact."""
    from sail_tpu import faults
    from sail_tpu.benchmarks.tpch_data import generate_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.sql import parse_one

    seed = int(os.environ.get("SAIL_BENCH_CHAOS_SEED", "1234"))
    q = int(os.environ.get("SAIL_BENCH_CHAOS_QUERY", "3"))
    tables = generate_tpch(0.01, seed=11)
    for name, t in tables.items():
        spark.createDataFrame(t).createOrReplaceTempView(name)
    plan = spark._resolve(parse_one(QUERIES[q]))

    def canon(table):
        return table.sort_by([(c, "ascending")
                              for c in table.column_names])

    def run():
        c = LocalCluster(num_workers=2)
        try:
            t0 = time.perf_counter()
            out = c.run_job(plan, num_partitions=4, timeout=120)
            return canon(out), time.perf_counter() - t0, c.last_job
        finally:
            c.stop()

    run()  # warm-up: JIT compilation must not masquerade as overhead
    clean, clean_s, _ = run()
    faults.configure(
        f"seed={seed};shuffle.fetch:*c[0-9]*=error(not_found)#1;"
        f"worker.task_exec:worker-1*=delay(1.5)#1")
    try:
        faulted, faulted_s, job = run()
        injected = dict(faults.injection_counts())
    finally:
        faults.reset()
    return {
        "query": q,
        "seed": seed,
        "clean_s": round(clean_s, 4),
        "faulted_s": round(faulted_s, 4),
        "recovery_overhead": round(faulted_s / clean_s, 3)
        if clean_s else None,
        "identical": clean.equals(faulted),
        "injected": injected,
        "task_retries": job.retry_count,
        "speculative": {"launched": job.spec_launched,
                        "won": job.spec_won},
    }


def _run_streaming_bench(spark) -> dict:
    """SAIL_BENCH_STREAMING=1: sustained-throughput streaming artifact.

    A stateful aggregate (groupBy sum over a replayable source) streams
    SAIL_BENCH_STREAMING_EPOCHS micro-batches of _ROWS rows each into a
    parquet file sink with a durable checkpoint, three ways:

    - clean, incremental keyed state (headline rows/s + epoch-commit
      latency p50/p99);
    - clean, legacy whole-buffer re-aggregation (the incremental-state
      A/B: same results, `state_speedup` = buffer wall / store wall);
    - chaos on (seeded streaming.sink/checkpoint/source injections):
      every failure kills the query, which restarts from the
      checkpoint — recovery overhead plus a final-output equivalence
      check against the clean run ride the artifact.
    """
    import glob
    import shutil
    import statistics
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from sail_tpu import faults
    from sail_tpu.session import DataFrame
    from sail_tpu.streaming import (ReplayableMemorySource,
                                    StreamingQueryException, _StreamRead)

    epochs = int(os.environ.get("SAIL_BENCH_STREAMING_EPOCHS", "30"))
    rows = int(os.environ.get("SAIL_BENCH_STREAMING_ROWS", "20000"))
    seed = int(os.environ.get("SAIL_BENCH_STREAMING_SEED", "1234"))
    rng = np.random.default_rng(7)
    batches = [pa.table({
        "k": pa.array(rng.integers(0, 64, rows), type=pa.int64()),
        "v": pa.array(rng.integers(0, 1000, rows), type=pa.int64()),
    }) for _ in range(epochs)]
    schema = batches[0].schema
    tmp_roots = []

    def run(tag: str, incremental: bool, spec=None) -> dict:
        out_dir = tempfile.mkdtemp(prefix=f"sail_sbench_{tag}_out_")
        ckpt = tempfile.mkdtemp(prefix=f"sail_sbench_{tag}_cp_")
        tmp_roots.extend((out_dir, ckpt))
        prev_inc = os.environ.get("SAIL_STREAMING__INCREMENTAL_STATE")
        os.environ["SAIL_STREAMING__INCREMENTAL_STATE"] = \
            "1" if incremental else "0"
        if spec:
            faults.configure(spec)
        restarts = 0
        commit_ms = []
        seen_batches = set()

        def start_query(fed_batches):
            src = ReplayableMemorySource(schema)
            for b in fed_batches:
                src.add(b)
            df = DataFrame(_StreamRead("sbench", src), spark)
            return src, (df.groupBy("k").sum("v").writeStream
                         .outputMode("complete").format("parquet")
                         .option("checkpointLocation", ckpt)
                         .start(out_dir))

        t0 = time.perf_counter()
        src, q = start_query(())
        try:
            fed = 0
            while True:
                try:
                    q.processAllAvailable()
                except StreamingQueryException:
                    restarts += 1
                    src, q = start_query(batches[:fed])
                    continue
                for entry in q.recent_progress:
                    if entry.get("status") == "committed" and \
                            entry["batchId"] not in seen_batches:
                        seen_batches.add(entry["batchId"])
                        commit_ms.append(entry["commitMs"])
                if fed >= epochs:
                    break
                src.add(batches[fed])
                fed += 1
            wall = time.perf_counter() - t0
            injected = dict(faults.injection_counts()) if spec else {}
        finally:
            q.stop()
            if spec:
                faults.reset()
            if prev_inc is None:
                os.environ.pop("SAIL_STREAMING__INCREMENTAL_STATE", None)
            else:
                os.environ["SAIL_STREAMING__INCREMENTAL_STATE"] = prev_inc
        parts = sorted(glob.glob(os.path.join(out_dir, "part-*.parquet")))
        final = pq.read_table(parts[-1]).sort_by("k") if parts else None
        qs = statistics.quantiles(commit_ms, n=100) if \
            len(commit_ms) >= 2 else [commit_ms[0] if commit_ms else 0] * 99
        return {
            "wall_s": round(wall, 4),
            "rows_per_s": round(epochs * rows / wall, 1),
            "commit_p50_ms": round(qs[49], 3),
            "commit_p99_ms": round(qs[98], 3),
            "restarts": restarts,
            "parts": len(parts),
            "state_mode": q._state_mode,
            "_final": final,
            "_injected": injected,
        }

    try:
        store = run("store", incremental=True)
        buffer = run("buffer", incremental=False)
        chaos = run("chaos", incremental=True, spec=(
            f"seed={seed};streaming.sink=error@0.05#2;"
            f"streaming.checkpoint=error@0.04#2;"
            f"streaming.source=delay(0.02)@0.1"))
        injected = dict(chaos.pop("_injected", {}))
        out = {
            "epochs": epochs,
            "rows_per_epoch": rows,
            "seed": seed,
            "incremental": {k: v for k, v in store.items()
                            if not k.startswith("_")},
            "whole_buffer": {k: v for k, v in buffer.items()
                             if not k.startswith("_")},
            "chaos": {k: v for k, v in chaos.items()
                      if not k.startswith("_")},
            "state_speedup": round(buffer["wall_s"] / store["wall_s"], 3)
            if store["wall_s"] else None,
            "recovery_overhead": round(chaos["wall_s"] / store["wall_s"],
                                       3) if store["wall_s"] else None,
            "identical_store_vs_buffer": store["_final"] is not None
            and store["_final"].equals(buffer["_final"]),
            "identical_chaos_vs_clean": store["_final"] is not None
            and chaos["_final"] is not None
            and store["_final"].equals(chaos["_final"]),
        }
        if injected:
            out["injected"] = injected
        return out
    finally:
        for root in tmp_roots:
            shutil.rmtree(root, ignore_errors=True)


def _run_continuous_bench(spark) -> dict:
    """SAIL_BENCH_STREAMING=1: the continuous record-at-a-time CDC
    artifact (ISSUE 15 acceptance). A change stream joins a dimension
    table and lands in a parquet sink with a durable checkpoint, run
    twice on a 2-worker local cluster:

    - continuous mode on (long-lived resident tasks, markers aligned
      mid-flight, credit backpressure): headline rows/s + end-to-end
      per-interval p50/p99 (marker inject → commit);
    - continuous off (the epoch path: one job dispatch per trigger) —
      the SAIL_BENCH_DISABLE_CONTINUOUS=1 knob forces this leg only.

    Both legs' total sink output is equivalence-checked row-for-row.
    """
    import glob
    import shutil
    import statistics
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.session import DataFrame
    from sail_tpu.streaming import ReplayableMemorySource, _StreamRead

    intervals = int(os.environ.get("SAIL_BENCH_CONTINUOUS_INTERVALS",
                                   "20"))
    rows = int(os.environ.get("SAIL_BENCH_CONTINUOUS_ROWS", "10000"))
    disabled = os.environ.get("SAIL_BENCH_DISABLE_CONTINUOUS",
                              "0").strip().lower() in ("1", "true",
                                                       "yes")
    import pandas as pd

    rng = np.random.default_rng(17)
    schema = pa.schema([("k", pa.int64()), ("v", pa.int64())])
    batches = [pa.table({
        "k": pa.array(rng.integers(0, 256, rows), type=pa.int64()),
        "v": pa.array(rng.integers(0, 10_000, rows), type=pa.int64()),
    }, schema=schema) for _ in range(intervals)]
    dim = pd.DataFrame({"k": np.arange(256, dtype=np.int64),
                        "w": np.arange(256, dtype=np.int64) * 7})
    spark.createDataFrame(dim).createOrReplaceTempView("cont_dim")
    shapes = {
        "filter": lambda df: df.filter("v % 3 != 0"),
        "filter_join": lambda df: df.filter("v % 3 != 0").join(
            spark.sql("SELECT * FROM cont_dim"), on="k", how="inner"),
    }
    tmp_roots = []

    def run(tag: str, shape, continuous: bool) -> dict:
        out_dir = tempfile.mkdtemp(prefix=f"sail_cbench_{tag}_out_")
        ckpt = tempfile.mkdtemp(prefix=f"sail_cbench_{tag}_cp_")
        tmp_roots.extend((out_dir, ckpt))
        prev = os.environ.get("SAIL_STREAMING__CONTINUOUS__ENABLED")
        os.environ["SAIL_STREAMING__CONTINUOUS__ENABLED"] = \
            "1" if continuous else "0"
        cluster = LocalCluster(num_workers=2)
        interval_ms = []
        try:
            src = ReplayableMemorySource(schema)
            shaped = shape(DataFrame(_StreamRead("cbench", src),
                                     spark))
            q = (shaped.writeStream.format("parquet")
                 .option("checkpointLocation", ckpt).cluster(cluster)
                 .start(out_dir))
            try:
                # warmup: the first intervals pay pipeline start +
                # stage compiles on both paths; steady state is what
                # the latency contract is about
                for b in batches[:2]:
                    src.add(b)
                    q.processAllAvailable()
                t0 = time.perf_counter()
                for b in batches[2:]:
                    src.add(b)
                    ti = time.perf_counter()
                    q.processAllAvailable()
                    interval_ms.append(
                        (time.perf_counter() - ti) * 1000.0)
                wall = time.perf_counter() - t0
                engaged = q._cont_runner is not None
            finally:
                q.stop()
        finally:
            cluster.stop()
            if prev is None:
                os.environ.pop("SAIL_STREAMING__CONTINUOUS__ENABLED",
                               None)
            else:
                os.environ["SAIL_STREAMING__CONTINUOUS__ENABLED"] = prev
        parts = sorted(glob.glob(os.path.join(out_dir,
                                              "part-*.parquet")))
        total = pa.concat_tables([pq.read_table(p) for p in parts]) \
            if parts else None
        qs = statistics.quantiles(interval_ms, n=100) \
            if len(interval_ms) >= 2 else [0.0] * 99
        measured = max(1, intervals - 2)
        return {
            "wall_s": round(wall, 4),
            "rows_per_s": round(measured * rows / wall, 1),
            "interval_p50_ms": round(qs[49], 3),
            "interval_p99_ms": round(qs[98], 3),
            "continuous_engaged": engaged,
            "parts": len(parts),
            "_total": total,
        }

    try:
        out = {"intervals": intervals, "rows_per_interval": rows,
               "disabled_knob": disabled}
        for name, shape in shapes.items():
            leg = {}
            epoch = run(f"{name}_epoch", shape, continuous=False)
            leg["epoch"] = {k: v for k, v in epoch.items()
                            if not k.startswith("_")}
            if not disabled:
                cont = run(f"{name}_cont", shape, continuous=True)
                leg["continuous"] = {k: v for k, v in cont.items()
                                     if not k.startswith("_")}
                leg["speedup"] = round(
                    epoch["wall_s"] / cont["wall_s"], 3) \
                    if cont["wall_s"] else None
                if cont["_total"] is not None and \
                        epoch["_total"] is not None:
                    sort_keys = [(c, "ascending")
                                 for c in cont["_total"].column_names]
                    leg["identical_vs_epoch"] = cont["_total"].sort_by(
                        sort_keys).equals(
                        epoch["_total"].sort_by(sort_keys))
            out[name] = leg
        return out
    finally:
        for root in tmp_roots:
            shutil.rmtree(root, ignore_errors=True)


def _run_tail_latency(spark) -> dict:
    """Tail-latency forensics artifact (retrace attribution + anomaly
    verdicts, analysis/anomaly.py). A warmed continuous CDC join leg
    runs on a 2-worker cluster with the durable event log on; after
    the per-fingerprint baseline warms, periodic intervals carry a
    batch in a NEW padded row-capacity bucket, so the join programs
    retrace (cause=capacity-bucket) and those intervals land in the
    p99 tail. The artifact records interval p50/p99, retraces-per-
    minute by cause, every anomaly verdict the live ring held, whether
    each tail outlier carries a non-``unexplained`` verdict naming the
    join retrace, and whether ``replay_verdicts`` AND the offline
    ``scripts/sail_timeline.py --anomalies`` (a fresh process) re-
    derive the identical verdict list from the durable log alone.

    ``SAIL_BENCH_DISABLE_ANOMALY=1`` (applied in main as
    SAIL_TELEMETRY__ANOMALY__ENABLED=0) records the same run with the
    classifier off — latencies only, no verdicts — for overhead A/B.

    The run is two-phase. Phase A (warmup) warms the per-fingerprint
    baseline on steady base-size intervals, then delivers
    ``grow_streak``+1 consecutive max-size intervals so the pinned
    capacity buckets (exec/capacity.py) grow to the envelope maximum —
    sustained occupancy, not a single spike, grows a pin. Phase B
    (measured) oscillates batch sizes around padded-capacity bucket
    boundaries WITHIN the warmed envelope: with pinning on every warmed
    program already covers the envelope, so the steady state pays ZERO
    retraces (``retraces_after_warmup`` in the artifact);
    ``SAIL_BENCH_DISABLE_PINNING=1`` (applied in main as
    SAIL_EXECUTION__CAPACITY__PINNING=0) restores per-call rounding and
    every fresh bucket crossing retraces (cause=capacity-bucket) into
    the p99 tail — the on/off pair is the zero-retrace steady-state
    acceptance artifact.
    """
    import glob as _glob
    import shutil
    import statistics
    import subprocess
    import tempfile

    import pandas as pd
    import pyarrow as pa

    from sail_tpu import events as _events
    from sail_tpu.analysis import anomaly as _anomaly
    from sail_tpu.exec import capacity as _capacity
    from sail_tpu.exec import retrace as _retrace
    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.session import DataFrame
    from sail_tpu.streaming import ReplayableMemorySource, _StreamRead

    intervals = int(os.environ.get("SAIL_BENCH_TAIL_INTERVALS", "24"))
    base_rows = int(os.environ.get("SAIL_BENCH_TAIL_ROWS", "2000"))
    anomaly_on = os.environ.get(
        "SAIL_TELEMETRY__ANOMALY__ENABLED", "1").strip().lower() \
        not in ("0", "false", "no", "off")

    log_dir = tempfile.mkdtemp(prefix="sail_tail_events_")
    out_dir = tempfile.mkdtemp(prefix="sail_tail_out_")
    ckpt = tempfile.mkdtemp(prefix="sail_tail_cp_")
    saved = {k: os.environ.get(k) for k in (
        "SAIL_TELEMETRY__EVENT_LOG__ENABLED",
        "SAIL_TELEMETRY__EVENT_LOG__DIR",
        "SAIL_STREAMING__CONTINUOUS__ENABLED")}
    os.environ["SAIL_TELEMETRY__EVENT_LOG__ENABLED"] = "1"
    os.environ["SAIL_TELEMETRY__EVENT_LOG__DIR"] = log_dir
    os.environ["SAIL_STREAMING__CONTINUOUS__ENABLED"] = "1"
    _events.reload()
    _anomaly.reset()
    _retrace.clear()
    _capacity.reload()  # fresh pins: warmup trains them from zero

    pinning_on = _capacity.enabled()
    rng = np.random.default_rng(23)
    schema = pa.schema([("k", pa.int64()), ("v", pa.int64())])
    # Phase A: 8 base-size intervals warm the baseline, then
    # grow_streak+1 max-size intervals train the pins up to the
    # envelope; each max-size interval crosses capacity buckets the
    # join programs never compiled, so warmup pays the typed retraces
    # the verdict pipeline explains. Phase B: sizes oscillate around
    # bucket boundaries inside the warmed envelope — the steady state.
    grow_streak = int(_capacity.snapshot().get("grow_streak", 3))
    max_rows = base_rows * 8
    # the 2 settle intervals matter: program VARIANTS picked by live
    # row count (e.g. the no-runtime-filter join) must compile once at
    # the GROWN pins before the measured phase, or they'd pay it there
    warm_sizes = ([base_rows] * 8 + [max_rows] * (grow_streak + 1)
                  + [base_rows] * 2)
    cycle = [base_rows, base_rows * 2, base_rows, base_rows * 4,
             base_rows * 6, base_rows]
    sizes = warm_sizes + [cycle[i % len(cycle)]
                          for i in range(intervals)]

    def batch(n):
        return pa.table({
            "k": pa.array(rng.integers(0, 256, n), type=pa.int64()),
            "v": pa.array(rng.integers(0, 10_000, n),
                          type=pa.int64()),
        }, schema=schema)

    dim = pd.DataFrame({"k": np.arange(256, dtype=np.int64),
                        "w": np.arange(256, dtype=np.int64) * 7})
    spark.createDataFrame(dim).createOrReplaceTempView("tail_dim")
    cluster = LocalCluster(num_workers=2)
    warm_ms, interval_ms = [], []
    t0 = time.perf_counter()
    try:
        src = ReplayableMemorySource(schema)
        shaped = DataFrame(_StreamRead("tailbench", src), spark) \
            .filter("v % 3 != 0").join(
                spark.sql("SELECT * FROM tail_dim"), on="k",
                how="inner")
        q = (shaped.writeStream.format("parquet")
             .option("checkpointLocation", ckpt).cluster(cluster)
             .start(out_dir))
        try:
            totals_warm: dict = {}
            for i, n in enumerate(sizes):
                src.add(batch(n))
                ti = time.perf_counter()
                q.processAllAvailable()
                dt_ms = (time.perf_counter() - ti) * 1000.0
                if i < len(warm_sizes):
                    warm_ms.append(dt_ms)
                    if i == len(warm_sizes) - 1:
                        # the warmup boundary: retraces recorded past
                        # this snapshot are steady-state failures
                        totals_warm = dict(_retrace.LEDGER.totals())
                else:
                    interval_ms.append(dt_ms)
            engaged = q._cont_runner is not None
        finally:
            q.stop()
    finally:
        cluster.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    wall = time.perf_counter() - t0
    # percentiles over the MEASURED phase only: warmup compiles are the
    # price paid once, the steady state is what the SLO sees
    qs = statistics.quantiles(interval_ms, n=100) \
        if len(interval_ms) >= 2 else [0.0] * 99
    minutes = max(wall / 60.0, 1e-9)
    totals = _retrace.LEDGER.totals()
    after = {c: n - totals_warm.get(c, 0)
             for c, n in sorted(totals.items())
             if n - totals_warm.get(c, 0) > 0}
    cap_snap = _capacity.snapshot()
    out = {
        "warmup_intervals": len(warm_sizes),
        "measured_intervals": intervals,
        "rows_per_interval": base_rows,
        "envelope_max_rows": max_rows,
        "continuous_engaged": engaged,
        "wall_s": round(wall, 4),
        "interval_p50_ms": round(qs[49], 3),
        "interval_p99_ms": round(qs[98], 3),
        "warmup_p99_ms": round(
            statistics.quantiles(warm_ms, n=100)[98], 3) \
        if len(warm_ms) >= 2 else 0.0,
        "anomaly_detection": "enabled" if anomaly_on else
        "disabled(SAIL_BENCH_DISABLE_ANOMALY)",
        "capacity_pinning": "enabled" if pinning_on else
        "disabled(SAIL_BENCH_DISABLE_PINNING)",
        "capacity": {"pinned_count": cap_snap.get("pinned_count", 0),
                     "grow_count": cap_snap.get("grow_count", 0)},
        # the zero-retrace steady-state acceptance number: compiles the
        # measured phase paid that were NOT a program's first ever
        "retraces_after_warmup": sum(
            n for c, n in after.items() if c != "first-ever"),
        "retraces_after_warmup_by_cause": after,
        "retraces": {
            "totals": dict(sorted(totals.items())),
            "per_minute": {c: round(n / minutes, 3)
                           for c, n in sorted(totals.items())},
        },
    }
    log_path = _events.EVENT_LOG.path
    _events.reload()  # close the bench log segment before replaying
    try:
        if anomaly_on:
            ring = _anomaly.anomalies()
            verdicts = [{k: v[k] for k in
                         ("query_id", "fingerprint", "total_ms",
                          "baseline_p50_ms", "excess_ms", "verdict")}
                        for v in ring]
            named = sorted({c for v in ring
                            for e in v["evidence"]
                            if e["category"] == "retrace"
                            for c in e.get("causes", {})})
            out["anomalies"] = verdicts
            out["outliers"] = len(ring)
            out["outliers_explained"] = sum(
                1 for v in ring if v["verdict"] != "unexplained")
            out["retrace_causes_named"] = named
            replay = _anomaly.replay_verdicts(
                _events.load_event_log(log_path)) if log_path else []
            out["replay_identical"] = json.dumps(
                replay, sort_keys=True) == json.dumps(
                ring, sort_keys=True)
            timeline_script = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "scripts", "sail_timeline.py")
            try:
                proc = subprocess.run(
                    [sys.executable, timeline_script, log_path,
                     "--anomalies", "--json"],
                    capture_output=True, text=True, timeout=120)
                offline = json.loads(proc.stdout)["anomalies"]
                out["offline_replay_identical"] = json.dumps(
                    offline, sort_keys=True) == json.dumps(
                    ring, sort_keys=True)
            except Exception as e:  # noqa: BLE001
                out["offline_replay_error"] = \
                    f"{type(e).__name__}: {e}"
            out["headline"] = (
                f"p99 {out['interval_p99_ms']}ms, "
                f"retraces_after_warmup="
                f"{out['retraces_after_warmup']} "
                f"({out['outliers_explained']}/{out['outliers']} tail "
                f"outliers explained, causes={named}, "
                f"replay_identical={out.get('replay_identical')})")
        else:
            out["headline"] = (
                f"p99 {out['interval_p99_ms']}ms, "
                f"retraces_after_warmup="
                f"{out['retraces_after_warmup']} "
                f"(anomaly detection disabled)")
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
        shutil.rmtree(out_dir, ignore_errors=True)
        shutil.rmtree(ckpt, ignore_errors=True)
    return out


def _run_shuffle_bench(spark) -> dict:
    """Cluster-path shuffle artifact: the join/agg-heavy queries where
    data movement dominates (q5/q18/q21) run through the local cluster,
    and the execution.shuffle.* / cluster.governor.* registry deltas
    record wire+spill bytes (raw vs compressed), fetch-overlap wait, and
    governor admissions. Run twice with the
    SAIL_BENCH_DISABLE_SHUFFLE_COMPRESSION=1 A/B knob for the on/off
    comparison."""
    from sail_tpu.benchmarks.tpch_data import generate_tpch
    from sail_tpu.benchmarks.tpch_queries import QUERIES
    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.metrics import REGISTRY
    from sail_tpu.sql import parse_one

    def snap():
        out = {}
        for row in REGISTRY.snapshot():
            name = row["name"]
            if name.startswith(("execution.shuffle.",
                                "cluster.governor.")):
                out[name] = out.get(name, 0.0) + row["value"]
        return out

    sf = float(os.environ.get("SAIL_BENCH_SHUFFLE_SF", "0.02"))
    tables = generate_tpch(sf, seed=7)
    for name, t in tables.items():
        spark.createDataFrame(t).createOrReplaceTempView(name)
    out = {
        "sf": sf,
        "compression": os.environ.get("SAIL_SHUFFLE__COMPRESSION", "lz4"),
        "fetch_concurrency": os.environ.get(
            "SAIL_SHUFFLE__FETCH_CONCURRENCY", "4"),
        "queries": {},
    }
    base = snap()
    c = LocalCluster(num_workers=2)
    try:
        for q in (5, 18, 21):
            plan = spark._resolve(parse_one(QUERIES[q]))
            c.run_job(plan, num_partitions=4, timeout=240)  # warm
            t0 = time.perf_counter()
            c.run_job(plan, num_partitions=4, timeout=240)
            out["queries"][q] = round(time.perf_counter() - t0, 4)
            # event-derived critical-path categories for the cluster
            # run (which fetch/task/compile actually gated the query)
            prof = _profile_summary()
            if prof and prof.get("critical_path"):
                out.setdefault("critical_path", {})[q] = \
                    prof["critical_path"]
            print(f"bench: shuffle q{q} = {out['queries'][q]}",
                  file=sys.stderr, flush=True)
        # fetch-overlap A/B: the same warm queries with sequential
        # (concurrency 0) stage-input fetch, so the wall-clock win from
        # overlapped fetch is recorded in the same artifact
        prev = os.environ.get("SAIL_SHUFFLE__FETCH_CONCURRENCY")
        os.environ["SAIL_SHUFFLE__FETCH_CONCURRENCY"] = "0"
        try:
            out["queries_sequential_fetch"] = {}
            for q in (18, 21):
                plan = spark._resolve(parse_one(QUERIES[q]))
                t0 = time.perf_counter()
                c.run_job(plan, num_partitions=4, timeout=240)
                out["queries_sequential_fetch"][q] = round(
                    time.perf_counter() - t0, 4)
                print(f"bench: shuffle q{q} (sequential fetch) = "
                      f"{out['queries_sequential_fetch'][q]}",
                      file=sys.stderr, flush=True)
        finally:
            if prev is None:
                os.environ.pop("SAIL_SHUFFLE__FETCH_CONCURRENCY", None)
            else:
                os.environ["SAIL_SHUFFLE__FETCH_CONCURRENCY"] = prev
    finally:
        c.stop()
    after = snap()
    delta = {k: v - base.get(k, 0.0) for k, v in after.items()}
    wire = int(delta.get("execution.shuffle.wire_bytes", 0))
    comp = int(delta.get("execution.shuffle.wire_bytes_compressed", 0))
    out["wire_bytes"] = wire
    out["wire_bytes_compressed"] = comp
    out["wire_ratio"] = round(wire / comp, 3) if comp else None
    out["spill_bytes_compressed"] = int(
        delta.get("execution.shuffle.spill_bytes_compressed", 0))
    out["fetch_wait_s"] = round(
        delta.get("execution.shuffle.fetch_wait_time", 0.0), 4)
    out["decode_s"] = round(
        delta.get("execution.shuffle.decode_time", 0.0), 4)
    out["governor"] = {
        "admitted": int(delta.get("cluster.governor.admitted_count", 0)),
        "deferred": int(delta.get("cluster.governor.deferred_count", 0)),
    }
    return out


def _run_skew_bench(spark) -> dict:
    """SAIL_BENCH_SKEW=1: a Zipf-skewed join workload through the local
    cluster, adaptive execution ON vs OFF interleaved. Records the
    coalesce/split/broadcast decision counts, the p50/max task-duration
    spread of the join stage (the number skew actually hurts), and
    result equivalence. Thresholds are scaled to the workload size and
    recorded in the artifact."""
    import numpy as np
    import pandas as pd

    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.sql import parse_one

    rows = int(os.environ.get("SAIL_BENCH_SKEW_ROWS", "600000"))
    n_dim = 150_000  # > the static broadcast limit: the join SHUFFLES
    rng = np.random.default_rng(5)
    # Zipf-flavored key draw: a handful of heavy hitters (60% of rows
    # on key 0) over a long uniform tail — one hot hash channel
    keys = np.where(rng.random(rows) < 0.6, 0,
                    rng.integers(0, n_dim, rows))
    fact = pd.DataFrame({"k": keys, "v": rng.integers(0, 1000, rows)})
    dim = pd.DataFrame({"k2": np.arange(n_dim),
                        "grp": np.arange(n_dim) % 16,
                        "flag": (np.arange(n_dim) % 1499 == 0)
                        .astype(np.int64)})
    spark.createDataFrame(fact).createOrReplaceTempView("skew_fact")
    spark.createDataFrame(dim).createOrReplaceTempView("skew_dim")
    q_skew = spark._resolve(parse_one(
        "SELECT d.grp AS grp, sum(f.v) AS s, count(*) AS c "
        "FROM skew_fact f JOIN skew_dim d ON f.k = d.k2 GROUP BY d.grp"))
    q_bcast = spark._resolve(parse_one(
        "SELECT count(*) AS c, sum(f.v) AS s FROM skew_fact f JOIN "
        "(SELECT k2 FROM skew_dim WHERE flag = 1) d ON f.k = d.k2"))
    knobs = {"SAIL_ADAPTIVE__SKEW__MIN_MB": "1",
             "SAIL_ADAPTIVE__SKEW__FACTOR": "2.0",
             "SAIL_ADAPTIVE__COALESCE__TARGET_MB": "8"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)

    def canon(table):
        return table.sort_by([(c, "ascending")
                              for c in table.column_names])

    def run(plan, aqe: bool, bcast_off: bool = False):
        # save/restore (not pop) so a whole-run SAIL_BENCH_DISABLE_AQE
        # setting applied in main survives the skew bench
        prior = {k: os.environ.get(k) for k in
                 ("SAIL_ADAPTIVE__ENABLED",
                  "SAIL_ADAPTIVE__BROADCAST__ENABLED")}
        os.environ["SAIL_ADAPTIVE__ENABLED"] = "1" if aqe else "0"
        if bcast_off:
            os.environ["SAIL_ADAPTIVE__BROADCAST__ENABLED"] = "0"
        c = LocalCluster(num_workers=2)
        try:
            t0 = time.perf_counter()
            out = c.run_job(plan, num_partitions=8, timeout=300)
            secs = time.perf_counter() - t0
            job = c.last_job
            # spread within the dominant stage (the one whose slowest
            # task gates the job — the shuffle join here): mixing stages
            # would report scan-vs-join differences as "skew"
            by_stage = [sorted(ds) for ds in job.durations.values() if ds]
            durs = max(by_stage, key=lambda ds: ds[-1]) if by_stage else []
            rec = {"seconds": round(secs, 4),
                   "decisions": job.adaptive.counts(),
                   "task_p50_s": round(durs[len(durs) // 2], 4)
                   if durs else None,
                   "task_max_s": round(durs[-1], 4) if durs else None,
                   "duration_spread": round(
                       durs[-1] / max(durs[len(durs) // 2], 1e-9), 3)
                   if durs else None,
                   "skew": job.adaptive.skew[:4]}
            return canon(out), rec
        finally:
            c.stop()
            for k, v in prior.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    try:
        out = {"rows": rows, "knobs": knobs, "queries": {}}
        # interleaved A/B per query: off, on, off, on
        for name, plan, bcast_off in (
                ("skew_join", q_skew, True),   # isolate the SPLIT path
                ("broadcast_join", q_bcast, False)):
            # warm BOTH paths: the rewrites produce new task shapes, so
            # an unwarmed AQE run would bill one-time XLA compiles as
            # adaptive overhead
            run(plan, aqe=False, bcast_off=bcast_off)
            run(plan, aqe=True, bcast_off=bcast_off)
            off1, off_rec = run(plan, aqe=False, bcast_off=bcast_off)
            on1, on_rec = run(plan, aqe=True, bcast_off=bcast_off)
            out["queries"][name] = {
                "aqe_off": off_rec, "aqe_on": on_rec,
                "identical": off1.equals(on1),
                "speedup": round(off_rec["seconds"]
                                 / on_rec["seconds"], 3)
                if on_rec["seconds"] else None,
            }
            print(f"bench: skew {name} off={off_rec['seconds']}s "
                  f"on={on_rec['seconds']}s "
                  f"decisions={on_rec['decisions']}",
                  file=sys.stderr, flush=True)
        decided = {}
        for rec in out["queries"].values():
            for k, v in rec["aqe_on"]["decisions"].items():
                decided[k] = decided.get(k, 0) + v
        out["decisions_total"] = decided
        return out
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _env_on(name: str) -> bool:
    return os.environ.get(name, "0").strip().lower() in ("1", "true",
                                                         "yes")


def _run_autoscale_bench(spark) -> dict:
    """SAIL_BENCH_AUTOSCALE=1: elastic load-ramp artifact.

    A two-thread query ramp drives a 1-worker elastic cluster (max 3)
    through grow → plateau → shrink, with a seeded straggler delay on
    the final-stage tasks so scale-down decisions land WHILE queries
    are still in flight (the graceful-drain race the policy must win).
    Two legs, identical workload and fault seed:

      drain     — autoscaler ON (aggressive shrink: occupancy veto
                  relaxed so drains fire mid-query); sealed channels
                  of live jobs MOVE to survivors (handoff_bytes > 0)
      hard_reap — SAME policy, cluster.autoscaler.hard_reap=1: each
                  scale-down decision hard-stops the victim instead of
                  draining it, so identical shrink decisions destroy
                  sealed channels and consumers pay producer re-runs

    Acceptance rides the artifact: zero failed queries in both legs,
    drain-leg p99 within SAIL_BENCH_AUTOSCALE_SLO_MS, pool grows past
    1 and returns to 1, every recorded autoscaler decision replays
    bit-identically from its detail, and the drain leg's task re-runs
    stay below the hard-reap leg's."""
    import threading

    import pyarrow as pa

    from sail_tpu import events, faults
    from sail_tpu import metrics as gm
    from sail_tpu.exec import autoscaler as asc
    from sail_tpu.exec.cluster import LocalCluster
    from sail_tpu.sql import parse_one

    n_queries = int(os.environ.get("SAIL_BENCH_AUTOSCALE_QUERIES",
                                   "8"))
    rows = int(os.environ.get("SAIL_BENCH_AUTOSCALE_ROWS", "120000"))
    slo_ms = float(os.environ.get("SAIL_BENCH_AUTOSCALE_SLO_MS",
                                  "15000"))
    rng = np.random.default_rng(7)
    t = pa.table({"k": rng.integers(0, 64, rows),
                  "v": rng.random(rows)})
    spark.createDataFrame(t).createOrReplaceTempView("asb")
    plan = spark._resolve(parse_one(
        "SELECT k, SUM(v), COUNT(*) FROM asb GROUP BY k"))

    def handoff_total():
        return sum(r["value"] for r in gm.REGISTRY.snapshot()
                   if r["name"] == "cluster.autoscaler.handoff_bytes")

    def leg(graceful: bool) -> dict:
        overrides = {
            "SAIL_CLUSTER__AUTOSCALER__ENABLED": "1",
            "SAIL_CLUSTER__AUTOSCALER__HARD_REAP":
                "0" if graceful else "1",
            "SAIL_CLUSTER__AUTOSCALER__TICK_SECS": "0.3",
            "SAIL_CLUSTER__AUTOSCALER__DOWN_IDLE_SECS": "0.4",
            "SAIL_CLUSTER__AUTOSCALER__DOWN_OCCUPANCY": "0.9",
            "SAIL_CLUSTER__AUTOSCALER__HYSTERESIS_TICKS": "1",
            "SAIL_CLUSTER__AUTOSCALER__COOLDOWN_TICKS": "1",
        }
        saved = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        t_leg = time.time()
        h0 = handoff_total()
        # the straggler window: final-stage partition 0 sleeps past the
        # idle threshold AND past the drain's begin→advance probe span,
        # so a freshly-grown worker goes idle holding sealed map output
        # of a still-live query — the shrink must then move (drain) or
        # destroy (hard reap) channels a running consumer still needs
        # the count cap keeps the chaos fair: first attempts straggle,
        # but a RELAUNCHED attempt (the re-run a hard stop forces, or a
        # retry through a handoff window) runs at full speed — re-run
        # cost shows up in the rerun counter, not as stacked sleeps
        faults.configure(
            f"seed=77;worker.task_exec:*s1p0*=delay(5.0)#{n_queries}",
            seed=77)
        cluster = LocalCluster(
            num_workers=1, task_slots=1,
            elastic={"min": 1, "max": 3, "idle_secs": 0.4})
        d = cluster.driver
        trace, stop = [], threading.Event()

        def sample():
            while not stop.wait(0.25):
                trace.append((round(time.time() - t_leg, 2),
                              len(d.workers), len(d.draining)))

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        latencies, reruns, failures = [], [], []
        lock = threading.Lock()
        pending = list(range(n_queries))

        def runner():
            while True:
                with lock:
                    if not pending:
                        return
                    pending.pop()
                t0 = time.perf_counter()
                try:
                    cluster.run_job(plan, num_partitions=4,
                                    timeout=120)
                    rc = cluster.last_job.retry_count
                except Exception as e:  # noqa: BLE001 — counted below
                    failures.append(f"{type(e).__name__}: {e}")
                    continue
                with lock:
                    latencies.append(time.perf_counter() - t0)
                    reruns.append(rc)

        try:
            threads = [threading.Thread(target=runner)
                       for _ in range(2)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            # ramp-down: the pool must return to min on its own
            deadline = time.time() + 45
            while time.time() < deadline:
                if len(d.workers) <= 1 and not d.draining:
                    break
                time.sleep(0.3)
            shrunk = len(d.workers) <= 1 and not d.draining
            peak = d.pool_peak
        finally:
            stop.set()
            sampler.join(timeout=5)
            cluster.stop()
            faults.reset()
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        decisions = [e for e in events.events()
                     if e["type"] == "autoscaler_decision"
                     and e["ts"] >= t_leg]
        lat_ms = sorted(x * 1000.0 for x in latencies)

        def pct(q):
            return round(lat_ms[min(len(lat_ms) - 1,
                                    int(q * len(lat_ms)))], 1) \
                if lat_ms else None

        return {
            "queries": len(latencies),
            "failed": len(failures),
            "failures": failures[:4],
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "pool_peak": peak,
            "shrunk_to_min": shrunk,
            "pool_trace": trace[:: max(1, len(trace) // 24)],
            "task_reruns": sum(reruns),
            "handoff_bytes": int(handoff_total() - h0),
            "decisions": {
                a: sum(1 for e in decisions if e["action"] == a)
                for a in (asc.SCALE_UP, asc.SCALE_DOWN, asc.HOLD)},
            "decisions_replay_identical": asc.replay_log(decisions)
            == [{"action": e["action"], "worker": e["worker"],
                 "reason": e["reason"]} for e in decisions],
        }

    drain = leg(graceful=True)
    hard = leg(graceful=False)
    out = {
        "slo_ms": slo_ms,
        "drain": drain,
        "hard_reap": hard,
        "zero_failed_queries": drain["failed"] == 0
        and hard["failed"] == 0,
        "p99_within_slo": drain["p99_ms"] is not None
        and drain["p99_ms"] <= slo_ms,
        "handoff_beats_rerun": drain["handoff_bytes"] > 0
        and drain["task_reruns"] < hard["task_reruns"],
    }
    print(f"bench: autoscale drain p99={drain['p99_ms']}ms "
          f"peak={drain['pool_peak']} "
          f"handoff={drain['handoff_bytes']}B "
          f"reruns={drain['task_reruns']} "
          f"vs hard_reap reruns={hard['task_reruns']}",
          file=sys.stderr, flush=True)
    return out


def _run_saturation(spark, n_tenants: int) -> dict:
    """SAIL_BENCH_CONCURRENCY=N: multi-tenant saturation artifact.

    N well-behaved tenants (one ``spark.newSession()`` each, tagged via
    ``spark.sail.tenant``) concurrently run a mixed workload — TPC-H q1
    + q6 over lineitem, a ClickBench-style aggregation over hits — while
    one streaming query (stateful groupBy-sum over a replayable source)
    runs for the whole phase. Three phases:

    - ``baseline``        admission on, no hostile tenant
    - ``hostile_admitted``  admission on, a hostile tenant flooding
      3× its concurrency cap with heavy group-bys
    - ``hostile_unbounded`` the same flood with admission OFF
      (SAIL_ADMISSION__ENABLED=0 + reload) — the control

    Per-tenant p50/p99 per phase plus isolation ratios
    (p99(hostile)/p99(baseline), worst tenant): acceptance is
    ``isolation_admitted ≤ 2x`` while ``hostile_unbounded`` shows what
    the flood does without the serving layer. Shed queries must all be
    typed retryable (``sheds_typed_retryable``). The whole-run
    SAIL_BENCH_DISABLE_ADMISSION=1 knob instead records one unbounded
    run for A/B."""
    import statistics
    import tempfile
    import threading

    import pyarrow as pa

    from sail_tpu.benchmarks.clickbench import register_hits
    from sail_tpu.exec import admission
    from sail_tpu.exec.admission import ResourceExhausted
    from sail_tpu.session import DataFrame
    from sail_tpu.streaming import ReplayableMemorySource, _StreamRead

    queries_per_tenant = int(os.environ.get(
        "SAIL_BENCH_SATURATION_QUERIES", "10"))
    lineitem = generate_lineitem_sf(float(os.environ.get(
        "SAIL_BENCH_SATURATION_SF", "0.01")))
    spark.createDataFrame(lineitem).createOrReplaceTempView("lineitem")
    register_hits(spark, n_rows=50_000)
    mixed = [
        # q1-shaped: wide aggregate over the fact table
        ("SELECT l_returnflag, l_linestatus, sum(l_quantity) qty, "
         "avg(l_extendedprice) p FROM lineitem "
         "WHERE l_shipdate <= DATE '1998-09-02' "
         "GROUP BY l_returnflag, l_linestatus "
         "ORDER BY l_returnflag, l_linestatus"),
        # q6-shaped: selective scan + agg
        ("SELECT sum(l_extendedprice * l_discount) rev FROM lineitem "
         "WHERE l_discount BETWEEN 0.05 AND 0.07 "
         "AND l_quantity < 24"),
        # ClickBench-shaped: top-k group-by over hits
        ("SELECT RegionID, count(*) c FROM hits "
         "GROUP BY RegionID ORDER BY c DESC LIMIT 10"),
    ]
    hostile_sql = ("SELECT l_orderkey, sum(l_extendedprice) s, "
                   "count(*) c FROM lineitem GROUP BY l_orderkey "
                   "ORDER BY s DESC LIMIT 5")
    # warm every query shape once BEFORE any phase: the baseline must
    # measure steady-state latency, not absorb the JIT compiles the
    # hostile phases would then run without
    for q in mixed + [hostile_sql]:
        spark.sql(q).toArrow()

    # caps tight enough that the flood actually queues: 2 concurrent
    # queries per tenant, fair-shared wake order across tenants
    knobs = {
        "SAIL_ADMISSION__MAX_CONCURRENT_QUERIES": "2",
        "SAIL_ADMISSION__MAX_CONCURRENT_TOTAL": str(2 * n_tenants + 2),
        "SAIL_ADMISSION__MAX_QUEUED_QUERIES": "64",
        "SAIL_ADMISSION__QUEUE_TIMEOUT_MS": "60000",
    }
    saved = {k: os.environ.get(k) for k in list(knobs)
             + ["SAIL_ADMISSION__ENABLED"]}
    os.environ.update(knobs)

    def phase(tag: str, hostile: bool, admission_on: bool) -> dict:
        from sail_tpu.metrics import REGISTRY as _REG

        os.environ["SAIL_ADMISSION__ENABLED"] = \
            "1" if admission_on else "0"
        admission.reload()
        stop = threading.Event()
        shed = {"count": 0, "typed": 0}
        # live-SLO window: per-tenant query.latency histogram snapshots
        # before the phase; the phase's percentiles are read from the
        # AFTER−BEFORE window — the same live instruments /metrics and
        # system.telemetry.tenant_slo serve — and checked against the
        # raw sample lists within bucket resolution
        tenant_names = [f"t{i}" for i in range(n_tenants)]
        hist_before = {name: _REG.histogram_state(
            "query.latency", tenant=name, phase="total")
            for name in tenant_names}

        # one streaming query rides the whole phase
        schema = pa.schema([("k", pa.int64()), ("v", pa.int64())])
        src = ReplayableMemorySource(schema)
        ckpt = tempfile.mkdtemp(prefix=f"sail_sat_{tag}_cp_")
        out_dir = tempfile.mkdtemp(prefix=f"sail_sat_{tag}_out_")
        sdf = DataFrame(_StreamRead(f"sat_{tag}", src), spark)
        sq = (sdf.groupBy("k").sum("v").writeStream
              .outputMode("complete").format("parquet")
              .option("checkpointLocation", ckpt).start(out_dir))
        epochs_fed = 0

        def feed_stream():
            nonlocal epochs_fed
            rng = np.random.default_rng(11)
            while not stop.is_set():
                src.add(pa.table({
                    "k": pa.array(rng.integers(0, 32, 2000),
                                  type=pa.int64()),
                    "v": pa.array(rng.integers(0, 100, 2000),
                                  type=pa.int64())}))
                epochs_fed += 1
                try:
                    sq.processAllAvailable()
                except Exception:  # noqa: BLE001 — phase stats survive
                    return

        def hostile_loop():
            hs = spark.newSession()
            hs.conf.set("spark.sail.tenant", "hostile")
            while not stop.is_set():
                try:
                    hs.sql(hostile_sql).toArrow()
                except ResourceExhausted as e:
                    shed["count"] += 1
                    if e.retryable:
                        shed["typed"] += 1
                    time.sleep(0.02)
                except Exception:  # noqa: BLE001
                    time.sleep(0.02)

        lat: dict = {}

        def tenant_loop(name: str):
            ts = spark.newSession()
            ts.conf.set("spark.sail.tenant", name)
            times = lat.setdefault(name, [])
            for i in range(queries_per_tenant):
                t0 = time.perf_counter()
                try:
                    ts.sql(mixed[i % len(mixed)]).toArrow()
                    times.append(time.perf_counter() - t0)
                except ResourceExhausted as e:
                    shed["count"] += 1
                    if e.retryable:
                        shed["typed"] += 1

        threads = [threading.Thread(target=feed_stream, daemon=True)]
        if hostile:
            # 3× the per-tenant concurrency cap: a real flood
            threads += [threading.Thread(target=hostile_loop,
                                         daemon=True)
                        for _ in range(6)]
        workers = [threading.Thread(target=tenant_loop, args=(f"t{i}",))
                   for i in range(n_tenants)]
        t0 = time.perf_counter()
        for t in threads + workers:
            t.start()
        for t in workers:
            t.join()
        stop.set()
        wall = time.perf_counter() - t0
        try:
            sq.stop()
        except Exception:  # noqa: BLE001
            pass
        for t in threads:
            t.join(10)
        import shutil
        for d in (ckpt, out_dir):
            shutil.rmtree(d, ignore_errors=True)

        def pct(vals, q):
            if not vals:
                return None
            s = sorted(vals)
            return round(s[min(len(s) - 1,
                               int(q * (len(s) - 1) + 0.999999))]
                         * 1000.0, 1)

        def hist_pct(name: str, q: float):
            after = _REG.histogram_state("query.latency", tenant=name,
                                         phase="total")
            if after is None:
                return None
            before = hist_before.get(name)
            window = after.subtract(before) if before is not None \
                else after
            v = window.quantile(q)
            return round(v * 1000.0, 1) if v is not None else None

        def tenant_rec(name: str, v: list) -> dict:
            # primary percentiles come from the LIVE histograms; the
            # raw sample list rides along as the offline ground truth
            # plus an agreement flag (within one exponential bucket)
            hp50, hp99 = hist_pct(name, 0.50), hist_pct(name, 0.99)
            sp50, sp99 = pct(v, 0.50), pct(v, 0.99)
            growth = 2.0  # the registry's bucket ladder
            agrees = all(
                h is None or s is None or s < 2.0
                or (s / growth) <= h <= (s * growth)
                for h, s in ((hp50, sp50), (hp99, sp99)))
            return {"n": len(v), "p50_ms": hp50, "p99_ms": hp99,
                    "sample_p50_ms": sp50, "sample_p99_ms": sp99,
                    "hist_agrees_within_bucket": agrees}

        return {
            "wall_s": round(wall, 3),
            "admission": admission_on,
            "hostile": hostile,
            "streaming_epochs": epochs_fed,
            "slo_source": "histogram(query.latency)",
            "tenants": {name: tenant_rec(name, v)
                        for name, v in sorted(lat.items())},
            "sheds": shed["count"],
            "sheds_typed_retryable": shed["count"] == shed["typed"],
        }

    def worst_ratio(base: dict, loaded: dict):
        # isolation ratios stay sample-sourced: bucket quantization
        # must not be able to flip the ≤2x acceptance either way
        ratios = []
        for name, rec in loaded["tenants"].items():
            b = base["tenants"].get(name, {}).get("sample_p99_ms")
            if b and rec.get("sample_p99_ms"):
                ratios.append(rec["sample_p99_ms"] / b)
        return round(max(ratios), 3) if ratios else None

    forced_off = _env_on("SAIL_BENCH_DISABLE_ADMISSION")
    try:
        # one unmeasured baseline-shaped pass: the first concurrent
        # phase pays one-off costs (thread pools, sink/checkpoint
        # setup, residual compiles) that would inflate whichever phase
        # ran first and skew the isolation ratios
        saved_q = queries_per_tenant
        queries_per_tenant = max(2, saved_q // 3)
        phase("warm", hostile=False, admission_on=not forced_off)
        queries_per_tenant = saved_q
        if forced_off:
            baseline = phase("baseline", hostile=False,
                             admission_on=False)
            unbounded = phase("hostile", hostile=True,
                              admission_on=False)
            return {
                "n_tenants": n_tenants,
                "queries_per_tenant": queries_per_tenant,
                "mode": "admission_disabled(SAIL_BENCH_DISABLE_"
                        "ADMISSION)",
                "baseline": baseline,
                "hostile_unbounded": unbounded,
                "isolation_unbounded": worst_ratio(baseline, unbounded),
            }
        baseline = phase("baseline", hostile=False, admission_on=True)
        admitted = phase("hostile_adm", hostile=True, admission_on=True)
        unbounded = phase("hostile_raw", hostile=True,
                          admission_on=False)
        return {
            "n_tenants": n_tenants,
            "queries_per_tenant": queries_per_tenant,
            "baseline": baseline,
            "hostile_admitted": admitted,
            "hostile_unbounded": unbounded,
            # worst well-behaved tenant's p99 movement vs baseline:
            # acceptance is admitted ≤ 2.0 (vs the unbounded control)
            "isolation_admitted": worst_ratio(baseline, admitted),
            "isolation_unbounded": worst_ratio(baseline, unbounded),
        }
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        admission.reload()


def _budget_skip_warnings(result: dict) -> list:
    """Self-check: no suite query may be silently budget-skipped — every
    skip surfaces as an artifact warning, and q22 (first-run,
    budget-exempt since PR 3) being skipped flags an ordering
    regression explicitly (r05 shipped exactly that silently)."""
    warnings = []
    for field, label in (("suite_seconds", "tpch"),
                         ("clickbench_seconds", "clickbench")):
        recs = result.get(field)
        if not isinstance(recs, dict):
            continue
        skipped = sorted((str(q) for q, v in recs.items()
                          if isinstance(v, str) and v.startswith("skipped")),
                         key=lambda s: (len(s), s))
        if skipped:
            warnings.append(
                f"{label}: {len(skipped)} queries budget-skipped: "
                + ",".join(skipped))
    suite = result.get("suite_seconds")
    if isinstance(suite, dict):
        q22 = suite.get(22, suite.get("22"))
        if isinstance(q22, str) and q22.startswith("skipped"):
            warnings.append(
                "tpch q22 was budget-skipped — it must run FIRST and "
                "exempt from the budget (ordering regression)")
    return warnings


def main():
    # Headline: TPC-H Q1 at SF10. BENCH_SF / argv override.
    t_bench_start = time.perf_counter()
    total_budget = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "700"))
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    sf = float(args[0]) if args else float(os.environ.get("BENCH_SF", "10"))
    suite = "--suite" in sys.argv
    import jax

    from sail_tpu import SparkSession

    devices = jax.devices()
    platform = devices[0].platform
    spark = SparkSession.builder.getOrCreate()
    # A/B knob: SAIL_BENCH_DISABLE_RTF=1 turns runtime join filters off
    # for the whole run, so on/off artifacts compare directly
    disable_rtf = os.environ.get("SAIL_BENCH_DISABLE_RTF", "0") \
        .strip().lower() in ("1", "true", "yes")
    if disable_rtf:
        spark.conf.set("spark.sail.join.runtimeFilter.enabled", "false")
        # app-config layer too: cluster-mode filter shipping and worker
        # executors read the YAML/env config, not the session conf
        os.environ["SAIL_JOIN__RUNTIME_FILTER__ENABLED"] = "false"
    # A/B knob: SAIL_BENCH_DISABLE_FUSION=1 turns whole-stage fused
    # compilation off (per-operator execution) for interleaved on/off
    # comparison runs
    disable_fusion = os.environ.get("SAIL_BENCH_DISABLE_FUSION", "0") \
        .strip().lower() in ("1", "true", "yes")
    if disable_fusion:
        spark.conf.set("spark.sail.execution.fusion.enabled", "false")
        os.environ["SAIL_EXECUTION__FUSION__ENABLED"] = "false"
    # A/B knob: SAIL_BENCH_DISABLE_RESULT_CACHE=1 turns the
    # result/fragment reuse layer and concurrent-scan sharing off for
    # the whole run, so warm dashboard-replay artifacts compare
    # directly against the recompute-everything control
    disable_result_cache = _env_on("SAIL_BENCH_DISABLE_RESULT_CACHE")
    if disable_result_cache:
        spark.conf.set("spark.sail.cache.result.enabled", "false")
        os.environ["SAIL_CACHE__RESULT__ENABLED"] = "false"
        os.environ["SAIL_CACHE__SCAN_SHARE__ENABLED"] = "false"
    # A/B knob: SAIL_BENCH_DISABLE_SHUFFLE_COMPRESSION=1 turns the
    # shuffle wire+spill codec off for the whole run (the cluster data
    # plane reads the app-config/env layer, not the session conf)
    disable_shuffle_comp = os.environ.get(
        "SAIL_BENCH_DISABLE_SHUFFLE_COMPRESSION", "0") \
        .strip().lower() in ("1", "true", "yes")
    if disable_shuffle_comp:
        os.environ["SAIL_SHUFFLE__COMPRESSION"] = "none"
    # A/B knob: SAIL_BENCH_DISABLE_AQE=1 turns adaptive execution off
    # for the whole run (the cluster driver reads the app-config/env
    # layer; skew telemetry still records)
    disable_aqe = os.environ.get("SAIL_BENCH_DISABLE_AQE", "0") \
        .strip().lower() in ("1", "true", "yes")
    if disable_aqe:
        os.environ["SAIL_ADAPTIVE__ENABLED"] = "false"
    # A/B knob: SAIL_BENCH_DISABLE_ANOMALY=1 turns the tail-latency
    # anomaly classifier (baselines + verdicts, analysis/anomaly.py)
    # off for the whole run; the tail_latency section then records
    # latencies only — the on/off pair measures classifier overhead
    disable_anomaly = _env_on("SAIL_BENCH_DISABLE_ANOMALY")
    if disable_anomaly:
        os.environ["SAIL_TELEMETRY__ANOMALY__ENABLED"] = "0"
    # A/B knob: SAIL_BENCH_DISABLE_PINNING=1 turns the pinned grow-only
    # capacity buckets (exec/capacity.py) off for the whole run —
    # per-call rounding returns, and the tail_latency section's
    # measured-phase oscillation pays a capacity-bucket retrace per
    # fresh bucket crossing; the on/off pair is the zero-retrace
    # steady-state comparison
    disable_pinning = _env_on("SAIL_BENCH_DISABLE_PINNING")
    if disable_pinning:
        os.environ["SAIL_EXECUTION__CAPACITY__PINNING"] = "0"
        from sail_tpu.exec import capacity as _capacity
        _capacity.reload()
    # A/B knob: SAIL_BENCH_DISABLE_EVENTS=1 turns the flight-data
    # recorder off for the whole run — the event-emission overhead
    # check (acceptance: ≤ 2% on q1/q6 wall-clock) compares this run
    # against the default
    disable_events = os.environ.get("SAIL_BENCH_DISABLE_EVENTS", "0") \
        .strip().lower() in ("1", "true", "yes")
    if disable_events:
        os.environ["SAIL_TELEMETRY__EVENTS_ENABLED"] = "0"
        from sail_tpu import events as _events
        _events.reload()
    # A/B knob: SAIL_BENCH_DISABLE_ADMISSION=1 turns multi-tenant
    # admission control off for the whole run (session gate + cluster
    # driver fair queue); the saturation section then records the
    # unbounded control only
    disable_admission = _env_on("SAIL_BENCH_DISABLE_ADMISSION")
    if disable_admission:
        os.environ["SAIL_ADMISSION__ENABLED"] = "0"
        from sail_tpu.exec import admission as _admission
        _admission.reload()
    result_admission = {"enabled": not disable_admission}
    # A/B knob: SAIL_BENCH_DISABLE_OBS_SERVER=1 leaves the pull-based
    # ops endpoint down for the whole run; the default run serves
    # /metrics and gets scraped every 2s by a background thread (a
    # stand-in Prometheus), so comparing the two artifacts measures
    # the telemetry plane's overhead (acceptance: ≤ 2% on q1)
    disable_obs = _env_on("SAIL_BENCH_DISABLE_OBS_SERVER")
    obs_info = {"enabled": not disable_obs}
    obs_stop = None
    if not disable_obs:
        import threading as _threading
        import urllib.request as _urlreq

        from sail_tpu import obs_server as _obs
        _srv = _obs.start()
        obs_info["url"] = _srv.url
        scrapes = {"count": 0, "bytes": 0, "errors": 0}
        obs_stop = _threading.Event()

        def _scrape_loop():
            while not obs_stop.wait(2.0):
                try:
                    body = _urlreq.urlopen(
                        _srv.url + "/metrics", timeout=5).read()
                    scrapes["count"] += 1
                    scrapes["bytes"] = len(body)
                except Exception:  # noqa: BLE001 — keep scraping
                    scrapes["errors"] += 1

        _threading.Thread(target=_scrape_loop, daemon=True).start()
        obs_info["scrapes"] = scrapes
    try:
        best, rows, scanned, q1_profile = _run_q1(spark, sf)
    except Exception as e:  # noqa: BLE001 — fall back to SF1 rather than die
        print(f"bench: SF{sf:g} failed ({type(e).__name__}: {e}); "
              f"retrying at SF1", file=sys.stderr)
        sf = 1.0
        best, rows, scanned, q1_profile = _run_q1(spark, sf)
    result = {
        "metric": f"tpch_q1_sf{sf:g}_seconds",
        "value": round(best, 4),
        "unit": "s",
        "vs_baseline": round(BASELINE_Q1_SF1_S * sf / best, 3),
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "rows": rows,
        "scan_gbps": round(scanned / best / 1e9, 2),
        "profile": q1_profile,
        "runtime_filters": "disabled" if disable_rtf else "enabled",
        "fusion": "disabled" if disable_fusion else "enabled",
        "shuffle_compression": "disabled" if disable_shuffle_comp
        else "enabled",
        "adaptive": "disabled" if disable_aqe else "enabled",
        "anomaly": "disabled" if disable_anomaly else "enabled",
        "events": "disabled" if disable_events else "enabled",
        "observability": obs_info,
    }
    # the 22-query and ClickBench artifacts always record, inside the
    # remaining share of the GLOBAL deadline (a bench that overruns the
    # driver's timeout records nothing) — BENCH_EXTRAS=0 skips
    extras = os.environ.get("BENCH_EXTRAS", "1") not in ("0", "false")
    remaining = total_budget - (time.perf_counter() - t_bench_start)
    print(f"bench: headline done at "
          f"{time.perf_counter() - t_bench_start:.0f}s; total budget "
          f"{total_budget:.0f}s, remaining {remaining:.0f}s",
          file=sys.stderr, flush=True)
    if (suite or extras) and remaining > 90:
        try:
            result["suite_sf"] = 0.05
            result["suite_seconds"] = _run_suite(spark, 0.05,
                                                 remaining * 0.6)
        except Exception as e:  # noqa: BLE001
            result["suite_error"] = f"{type(e).__name__}: {e}"
        remaining = total_budget - (time.perf_counter() - t_bench_start)
        try:
            if remaining > 45:
                result["clickbench_rows"] = 100_000
                result["clickbench_seconds"] = _run_clickbench(
                    spark, 100_000, remaining * 0.8)
        except Exception as e:  # noqa: BLE001
            result["clickbench_error"] = f"{type(e).__name__}: {e}"
    # shuffle data-plane artifact: cluster-path q5/q18/q21 wire/spill
    # bytes + fetch overlap (SAIL_BENCH_SKIP_SHUFFLE=1 skips)
    remaining = total_budget - (time.perf_counter() - t_bench_start)
    if remaining > 60 and os.environ.get(
            "SAIL_BENCH_SKIP_SHUFFLE", "0").strip().lower() not in (
            "1", "true", "yes"):
        try:
            result["shuffle"] = _run_shuffle_bench(spark)
        except Exception as e:  # noqa: BLE001
            result["shuffle_error"] = f"{type(e).__name__}: {e}"
    # skewed-join adaptive-execution artifact: Zipf workload, AQE on/off
    # interleaved with decision counts and task-duration spread (opt-in)
    if os.environ.get("SAIL_BENCH_SKEW", "0").strip().lower() in (
            "1", "true", "yes"):
        try:
            result["skew_bench"] = _run_skew_bench(spark)
        except Exception as e:  # noqa: BLE001
            result["skew_bench_error"] = f"{type(e).__name__}: {e}"
    # streaming sustained-throughput artifact: stateful aggregate into a
    # file sink, incremental-state A/B + seeded-chaos restart recovery
    if os.environ.get("SAIL_BENCH_STREAMING", "0").strip().lower() in (
            "1", "true", "yes"):
        try:
            result["streaming"] = _run_streaming_bench(spark)
        except Exception as e:  # noqa: BLE001
            result["streaming_error"] = f"{type(e).__name__}: {e}"
        # continuous record-at-a-time CDC artifact: resident-task
        # pipeline vs the epoch path over the same change stream
        # (SAIL_BENCH_DISABLE_CONTINUOUS=1 records the epoch leg only)
        try:
            result["continuous"] = _run_continuous_bench(spark)
        except Exception as e:  # noqa: BLE001
            result["continuous_error"] = f"{type(e).__name__}: {e}"
    # tail-latency forensics artifact: continuous CDC join leg driven
    # through capacity-bucket churn — retraces-per-minute by cause,
    # anomaly verdicts for every p99 outlier, durable-log replay
    # parity (rides SAIL_BENCH_STREAMING=1, or SAIL_BENCH_TAIL=1
    # alone; SAIL_BENCH_DISABLE_ANOMALY=1 records the classifier-off
    # control)
    if os.environ.get("SAIL_BENCH_STREAMING", "0").strip().lower() in (
            "1", "true", "yes") or _env_on("SAIL_BENCH_TAIL"):
        try:
            result["tail_latency"] = _run_tail_latency(spark)
        except Exception as e:  # noqa: BLE001
            result["tail_latency_error"] = f"{type(e).__name__}: {e}"
    # elastic autoscaling load-ramp: grow → plateau → graceful-drain
    # shrink, hard-reap A/B (opt-in: two extra cluster ramps)
    if _env_on("SAIL_BENCH_AUTOSCALE"):
        try:
            result["autoscale"] = _run_autoscale_bench(spark)
        except Exception as e:  # noqa: BLE001
            result["autoscale_error"] = f"{type(e).__name__}: {e}"
    # chaos mode: TPC-H under a fixed fault seed, recovery overhead in
    # the artifact (opt-in: the run costs two extra cluster executions)
    if os.environ.get("SAIL_BENCH_CHAOS", "0").strip().lower() in (
            "1", "true", "yes"):
        try:
            result["chaos"] = _run_chaos(spark)
        except Exception as e:  # noqa: BLE001
            result["chaos_error"] = f"{type(e).__name__}: {e}"
    # multi-tenant saturation: SAIL_BENCH_CONCURRENCY=N tenants, mixed
    # TPC-H + ClickBench + one streaming query, hostile tenant on/off,
    # per-tenant p50/p99 + isolation ratio (admission A/B above)
    result["admission"] = result_admission
    n_tenants = int(os.environ.get("SAIL_BENCH_CONCURRENCY", "0"))
    if n_tenants > 0:
        try:
            result["saturation"] = _run_saturation(spark, n_tenants)
        except Exception as e:  # noqa: BLE001
            result["saturation_error"] = f"{type(e).__name__}: {e}"
    # dashboard-replay cache artifact: SAIL_BENCH_CACHE=K sessions
    # replay the ClickBench suite warm vs cold (result-cache A/B via
    # SAIL_BENCH_DISABLE_RESULT_CACHE=1 above)
    n_cache_sessions = int(os.environ.get("SAIL_BENCH_CACHE", "0"))
    if n_cache_sessions > 0:
        try:
            result["cache_bench"] = _run_cache_bench(spark,
                                                     n_cache_sessions)
        except Exception as e:  # noqa: BLE001
            result["cache_bench_error"] = f"{type(e).__name__}: {e}"
    # whole-run reuse-layer counters ride every artifact
    result["result_cache"] = _result_cache_summary(
        not disable_result_cache)
    if obs_stop is not None:
        obs_stop.set()
        # final scrape sanity: the exposition must still parse as
        # key-value samples after the whole run (fleet view included)
        try:
            import urllib.request as _urlreq
            body = _urlreq.urlopen(
                obs_info["url"] + "/metrics", timeout=5).read().decode()
            samples = [ln for ln in body.splitlines()
                       if ln and not ln.startswith("#")]
            obs_info["final_scrape_samples"] = len(samples)
            obs_info["final_scrape_parse_ok"] = all(
                " " in ln for ln in samples)
        except Exception as e:  # noqa: BLE001
            obs_info["final_scrape_error"] = f"{type(e).__name__}: {e}"
    warnings = _budget_skip_warnings(result)
    if warnings:
        result["warnings"] = warnings
        for w in warnings:
            print(f"bench: WARNING: {w}", file=sys.stderr, flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
