#!/usr/bin/env python3
"""One run of one benchmark cell: TPC-H statements over Spark Connect
against a server in this process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Nothing here knows a cell by name. ``BENCHMARK.json`` maps the cell to
a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``: statements, streams, loop); statements are
``queries/<name>.json`` + ``.sql``; each per-layer metric is
``metrics/<name>.json`` naming a reader under ``readers/``. A later
cell, configuration, statement or metric is new files and new entries
in ``BENCHMARK.json``.

What the harness reads of a configuration: ``scale_factor`` and
``tables`` (the data), ``session_options`` (set on every client
session), ``process_environment`` (set before JAX starts), ``trace``
(``after_seconds``, ``seconds``: the traced part of a ``--trace 1``
window; 1 and 4 where absent), ``backends`` (the routes a stage may
take and still count as executed where the configuration says; ``["xla"]``
where absent), ``limits`` (one per number compared) and, through
``needed_bytes.py``, ``rows``, ``schema`` and ``logical_widths_bytes``.
The rest of the file states the deployment for its readers.

One process is the server (``SparkConnectServer``, threads) and its
clients (``SparkConnectClient`` over gRPC on localhost): a chip belongs
to one process. Set-up makes the data from ``--seed``, writes Parquet
under a temporary directory, registers the views per client session,
sends the first calls together (compile, or load from JAX's persistent
cache) and runs one warm cycle per stream; then the window. Once it has
closed, every answer it produced is held to the pandas reference
(``compare.py``).

Progress goes out as one JSON line per step; the last line of standard
output is the result the driver reads.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

#: JAX's event for one request to the backend compiler, persistent
#: cache hits included (jax/_src/dispatch.py BACKEND_COMPILE_EVENT)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def host_rss_gb() -> dict:
    """This process's resident memory now and at its peak (Linux)."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS:", "VmHWM:")):
                key, kb = line.split()[:2]
                out[key.rstrip(":")] = int(kb) / 1e6
    return out


def emit(step: str, **fields) -> None:
    print(json.dumps({"step": step, **fields, "host_rss_gb": host_rss_gb()},
                     default=str), flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell, from data files
# ---------------------------------------------------------------------------

class Cell:
    """Everything the files say about one cell."""

    def __init__(self, name: str, root: str = ROOT):
        bench_dir = os.path.join(root, "benchmark")
        self.benchmark = load_json(root, "BENCHMARK.json")
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in entries:
            raise SystemExit(f"run.py: no workload {name!r} in "
                             f"BENCHMARK.json; known: {sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = load_json(bench_dir, "configs",
                                self.entry["config"] + ".json")
        self.traffic = load_json(bench_dir, "traffic",
                                 self.entry["traffic"] + ".json")
        self.peaks = load_json(bench_dir, "peaks.json")
        self.queries = {}
        for q in dict.fromkeys(self.traffic["cycle"]):
            doc = load_json(bench_dir, "queries", q + ".json")
            with open(os.path.join(bench_dir, "queries",
                                   doc["sql_file"])) as f:
                doc["sql"] = f.read()
            self.queries[q] = doc
        self.bench_dir = bench_dir

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> list:
        return [m for m in self.benchmark["end_to_end"] if self.reports(m)]

    def per_layer(self) -> list:
        """The cell's per-layer metrics, each with its reader."""
        out = []
        for m in self.benchmark["per_layer"]:
            if self.reports(m):
                spec = load_json(self.bench_dir, "metrics",
                                 m["name"] + ".json")
                out.append({**m, "reader": spec["reader"]})
        return out

    def wanted_tables(self) -> dict:
        """{table: [columns the reference reads]} over the statements."""
        wanted = {}
        for q in self.queries.values():
            for table, cols in q["reads"].items():
                if table not in self.config["tables"]:
                    raise SystemExit(
                        f"run.py: {q['name']} reads {table}, which "
                        f"configuration {self.config['name']} has not")
                have = wanted.setdefault(table, [])
                have.extend(c for c in cols if c not in have)
        return wanted

    def stream_orders(self, seed: int) -> list:
        """Per stream, the cycle in an order shuffled from the seed."""
        import numpy as np
        cycle = list(self.traffic["cycle"])
        rng = np.random.default_rng([abs(int(seed)), 4242])
        return [[cycle[i] for i in rng.permutation(len(cycle))]
                for _ in range(int(self.traffic["streams"]))]


def load_reader(bench_dir: str, spec: str):
    """``readers/<file>.py:<function>``."""
    rel, _, function = spec.partition(":")
    path = os.path.join(bench_dir, rel)
    module_spec = importlib.util.spec_from_file_location(
        "bench_reader_" + os.path.basename(rel)[:-3], path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return getattr(module, function)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

class CompileCounter:
    """Backend compile requests and persistent-cache hits, with the
    wall-clock time of each request, through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring
        self.compile_times = []     # time.time() at the end of each
        self.cache_requests = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_REQUEST_EVENT:
            with self._lock:
                self.cache_requests += 1
        elif event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.compile_times.append((time.time(), duration,
                                           kw.get("fun_name")))

    def snapshot(self) -> dict:
        with self._lock:
            return {"backend_compiles": len(self.compile_times),
                    "compile_seconds": sum(c[1] for c in
                                           self.compile_times),
                    "cache_requests": self.cache_requests,
                    "cache_hits": self.cache_hits}

    def compiles_between(self, t0: float, t1: float) -> list:
        """Names of the programs whose compile request ended in
        [t0, t1] (wall clock)."""
        with self._lock:
            return [str(name) for t, _d, name in self.compile_times
                    if t0 <= t <= t1]


def device_step(cell: Cell, require_platform: str) -> dict:
    """The device JAX gives this process. Anything but
    ``require_platform`` x the cell's chips, or a kind with no row in
    ``peaks.json``, ends the run before any data is made."""
    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != require_platform:
        raise SystemExit(
            f"run.py: needs a {require_platform} device, JAX gave "
            f"{platform!r} ({kind} x{len(devices)})")
    if len(devices) < cell.chips:
        raise SystemExit(f"run.py: cell {cell.name} needs {cell.chips} "
                         f"chip(s), JAX gave {len(devices)}")
    if require_platform == "tpu" and kind not in cell.peaks["devices"]:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json")
    import sail_tpu  # noqa: F401 — x64 on, as every entry point has it
    from sail_tpu.exec import pcache
    device = {"platform": platform, "kind": kind, "count": len(devices)}
    emit("device", **device, jax=jax.__version__,
         jax_cache_dir=pcache.place_jax_cache())
    return device


# ---------------------------------------------------------------------------
# server + clients
# ---------------------------------------------------------------------------

def connect(server, paths: dict, conf: dict):
    """A client with a session of its own, the Parquet directories
    registered as ``spark.read.parquet(p).createOrReplaceTempView(n)``
    sends it: a CreateDataFrameViewCommand over a Read. The client
    carries ``server_session``, the id the server's profiles name."""
    from sail_tpu.spark_connect.client import SparkConnectClient
    from spark.connect import base_pb2 as bpb
    client = SparkConnectClient(f"127.0.0.1:{server.port}")
    if conf:
        client.config_set(conf)
    for name, path in paths.items():
        plan = bpb.Plan()
        view = plan.command.create_dataframe_view
        view.name = name
        view.replace = True
        view.input.read.data_source.format = "parquet"
        view.input.read.data_source.paths.append(path)
        list(client.execute_plan(plan))
    client.server_session = \
        server.sessions.get_or_create(client.session_id)._session_id
    return client


class Statement:
    """One client call: who sent what, when (host clock and wall
    clock), and what came back."""

    __slots__ = ("stream", "query", "t0", "t1", "wall0", "wall1", "table",
                 "error", "profile")

    def __init__(self, stream, query):
        self.stream, self.query = stream, query
        self.table = self.error = self.profile = None

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0


def call(client, cell: Cell, stream: int, query: str) -> Statement:
    """The timed path: ``client.sql(text)`` to the Arrow table in hand."""
    import jax.profiler
    st = Statement(stream, query)
    sql = cell.queries[query]["sql"]
    with jax.profiler.TraceAnnotation(f"bench:call:s{stream}:{query}"):
        st.wall0, st.t0 = time.time(), time.perf_counter()
        try:
            st.table = client.sql(sql)
        except Exception as e:  # noqa: BLE001 — a failed statement is a result
            st.error = f"{type(e).__name__}: {e}"[:300]
        st.t1, st.wall1 = time.perf_counter(), time.time()
    return st


def run_streams(clients: list, cell: Cell, orders: list, stop_at=None,
                cycles=None) -> list:
    """Closed loop: each stream sends its next statement when the last
    has answered, walking its order over and over, for ``cycles`` or
    until ``stop_at`` (host clock). The cycle in flight at ``stop_at``
    is finished and counts, time included: every window then holds
    whole cycles, so each statement of the mix as often as the others
    whatever order the seed drew."""
    results = [[] for _ in clients]

    def loop(i):
        order, n = orders[i], 0
        while (n < cycles * len(order)) if cycles is not None \
                else (n % len(order) or time.perf_counter() < stop_at):
            results[i].append(call(clients[i], cell, i, order[n % len(order)]))
            n += 1

    threads = [threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [st for per_stream in results for st in per_stream]


def first_calls(server, paths, cell: Cell) -> list:
    """Every statement of the cell once, all together, each from a
    session of its own: the compiles (or cache loads) overlap."""
    names = list(cell.queries)
    clients = [connect(server, paths, cell.config["session_options"])
               for _ in names]
    try:
        return run_streams(clients, cell, [[q] for q in names], cycles=1)
    finally:
        for c in clients:
            c.close()


def refuse_failed_setup(statements: list, what: str) -> None:
    """A statement that fails before the window ends the run: there is
    nothing warmed to measure."""
    errors = [f"{st.query}: {st.error}" for st in statements if st.error]
    if errors:
        raise SystemExit(f"run.py: {what} failed: " + "; ".join(errors))


def attach_profiles(statements: list, session_ids: list) -> None:
    """Give each statement the profile the server kept of it: the
    session's profile that started inside the client call and ran
    longest (a call can leave a second, short one for the fetch)."""
    from sail_tpu import profiler
    by_session = {}
    for p in profiler.FLIGHT_RECORDER.profiles():
        by_session.setdefault(p.session, []).append(p)
    for st in statements:
        inside = [p for p in by_session.get(session_ids[st.stream], [])
                  if st.wall0 - 0.002 <= p.start_time <= st.wall1]
        if inside:
            st.profile = max(inside, key=lambda p: p.end_time - p.start_time)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def traced(trace_dir: str, after_s: float, for_s: float) -> dict:
    """Trace ``for_s`` seconds of the running window, ``after_s`` in.
    The ``bench:window`` annotation marks the traced window on the
    trace's own clock; ``wall0`` is the wall clock at its start."""
    import jax.profiler
    time.sleep(after_s)
    jax.profiler.start_trace(trace_dir)
    try:
        wall0 = time.time()
        with jax.profiler.TraceAnnotation("bench:window"):
            time.sleep(for_s)
        wall1 = time.time()
    finally:
        jax.profiler.stop_trace()
    return {"wall0": wall0, "wall1": wall1}


def phase_lookup(statements: list, trace_t0_ns: float, wall0: float):
    """``phase_at(call name, trace ns)``: the profile phase that covered
    the moment, the phases laid end to end from the profile's start."""
    by_name = {}
    for st in statements:
        if st.profile is not None:
            by_name.setdefault(f"bench:call:s{st.stream}:{st.query}",
                               []).append(st)

    def phase_at(name, ns):
        wall = wall0 + (ns - trace_t0_ns) / 1e9
        for st in by_name.get(name, []):
            if st.wall0 <= wall <= st.wall1:
                at = st.profile.start_time
                if wall < at:
                    return "wire-in"
                for phase, ms in st.profile.phases.items():
                    at += ms / 1000.0
                    if wall <= at:
                        return phase
                return "wire-out"
        return None

    return phase_at


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def routes_off_the_backends(done: list, backends: list) -> int:
    """What of the answered statements did not run where the
    configuration says: every stage routed to a backend that is not one
    of ``backends``, every profile that names no route, every statement
    that left no profile."""
    profiles = [st.profile for st in done if st.profile is not None]
    return (sum(1 for p in profiles for r in p.backend_routes
                if r.get("backend") not in backends)
            + sum(1 for p in profiles if not p.backend_routes)
            + (len(done) - len(profiles)))


class Run:
    """What the readers read of one finished window: ``config``,
    ``queries`` (the cell's statements' documents), ``statements`` (each
    with its ``profile``), ``done`` (those that answered), ``setup``
    (seconds per step), ``trace`` (the reduced trace with ``wall``, the
    traced window on the wall clock, or None), ``device``, ``peaks`` and
    ``compiles_in_window``."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None, require_platform: str = "tpu", root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload, root)
    for key, value in cell.config.get("process_environment", {}).items():
        os.environ[key] = value
    device = device_step(cell, require_platform)
    counter = CompileCounter()
    tmp = tempfile.mkdtemp(prefix="sail_bench_")
    try:
        return measure(args, cell, device, counter, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(args, cell: Cell, device: dict, counter: CompileCounter,
            tmp: str) -> int:
    """Set-up, window, readings, comparison, result line."""
    import compare
    import datagen
    import tracered
    import jax
    from sail_tpu.spark_connect.service import SparkConnectServer

    setup = {}
    server, clients = None, []
    try:
        # -- set-up ---------------------------------------------------------
        t = time.perf_counter()
        paths, frames, rows, parquet_bytes = datagen.write_tables(
            cell.wanted_tables(), args.seed, cell.config["scale_factor"],
            tmp)
        setup["data_and_parquet_s"] = time.perf_counter() - t
        emit("data", seed=args.seed, sf=cell.config["scale_factor"],
             rows=rows, parquet_bytes=parquet_bytes,
             seconds=setup["data_and_parquet_s"])

        t = time.perf_counter()
        server = SparkConnectServer("127.0.0.1", 0).start()
        firsts = first_calls(server, paths, cell)
        setup["first_calls_s"] = time.perf_counter() - t
        after_first = counter.snapshot()
        emit("first_calls", seconds=setup["first_calls_s"], **after_first,
             each={st.query: st.ms / 1000.0 for st in firsts})
        refuse_failed_setup(firsts, "first call")

        t = time.perf_counter()
        clients = [connect(server, paths, cell.config["session_options"])
                   for _ in range(int(cell.traffic["streams"]))]
        orders = cell.stream_orders(args.seed)
        warm = run_streams(clients, cell, orders,
                           cycles=int(cell.traffic.get("warm_cycles", 1)))
        setup["warm_cycle_s"] = time.perf_counter() - t
        emit("warm_cycle", seconds=setup["warm_cycle_s"],
             **counter.snapshot(), orders=orders,
             each_ms=[[st.query, st.ms] for st in warm])
        refuse_failed_setup(warm, "warm cycle")

        # -- the window -----------------------------------------------------
        tracer, trace_walls = None, {}
        trace_dir = os.path.join(tmp, "trace")
        window_wall0, window_t0 = time.time(), time.perf_counter()
        setup_s = window_t0 - T_PROCESS_START
        if args.trace:
            plan = cell.config.get("trace", {})
            for_s = min(float(plan.get("seconds", 4.0)),
                        max(args.seconds - 1.0, 0.5))
            after_s = min(float(plan.get("after_seconds", 1.0)),
                          max(args.seconds - for_s, 0.0))
            tracer = threading.Thread(
                target=lambda: trace_walls.update(
                    traced(trace_dir, after_s, for_s)), daemon=True)
            tracer.start()
        statements = run_streams(clients, cell, orders,
                                 stop_at=window_t0 + args.seconds)
        window_t1 = max([st.t1 for st in statements] + [window_t0])
        window_wall1 = time.time()
        if tracer is not None:
            tracer.join()

        # -- after the window: read, then free, then compare ------------------
        stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
        device["memory_peak_bytes"] = max(
            s.get("peak_bytes_in_use", 0) for s in stats)
        session_ids = [c.server_session for c in clients]
        attach_profiles(statements, session_ids)
        compiles_in_window = counter.compiles_between(window_wall0,
                                                      window_wall1)
        from sail_tpu.native import native_active
        native = bool(native_active())
    finally:
        for c in clients:
            c.close()
        if server is not None:
            server.stop(grace=1.0)

    done = [st for st in statements if st.error is None]
    failed = [st for st in statements if st.error is not None]
    window_s = window_t1 - window_t0
    worst_ms = max(st.ms for st in statements)
    latencies = [st.ms if st.error is None else worst_ms
                 for st in statements]
    measured = {
        "query_ms_p50": statistics.median(latencies),
        "query_ms_p95": percentile(latencies, 95),
        "queries_per_hour": len(done) / window_s * 3600.0,
        "setup_s": setup_s,
    }
    per_query = {}
    for st in done:
        per_query.setdefault(st.query, []).append(st.ms)
    quarters = [[st.ms for st in statements
                 if i * window_s / 4 <= st.t0 - window_t0
                 < (i + 1) * window_s / 4] for i in range(4)]
    emit("window", seconds=window_s, attempted=len(statements),
         p50_ms_by_quarter=[statistics.median(q) if q else None
                            for q in quarters],
         failed=len(failed), errors=[st.error for st in failed][:3],
         per_query_ms={q: {"n": len(v), "p50": statistics.median(v),
                           "max": max(v)}
                       for q, v in per_query.items()},
         setup=setup, compiles_in_window=compiles_in_window,
         memory_stats={k: stats[0].get(k) for k in
                       ("peak_bytes_in_use", "bytes_in_use",
                        "bytes_limit")})

    trace = None
    if args.trace:
        planes = tracered.load_xplane(tracered.find_xplane(trace_dir))
        windows = tracered.host_spans(planes, tracered.WINDOW_SPAN)
        t0_ns = windows[0][1] if windows else 0.0
        wall0 = trace_walls.get("wall0", window_wall0)
        # the calls on the trace's clock, from the harness's own records:
        # a call that began before the trace has no annotation in it
        calls = [(f"{tracered.CALL_SPAN}s{st.stream}:{st.query}",
                  t0_ns + (st.wall0 - wall0) * 1e9,
                  t0_ns + (st.wall1 - wall0) * 1e9) for st in statements]
        trace = tracered.reduce_trace(
            planes, phase_lookup(statements, t0_ns, wall0), calls=calls)
        trace["wall"] = [trace_walls["wall0"], trace_walls["wall1"]]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]

    run = Run(config=cell.config, queries=cell.queries,
              statements=statements, done=done,
              setup=setup, trace=trace, device=device,
              peaks=cell.peaks["devices"].get(device["kind"]),
              compiles_in_window=len(compiles_in_window))
    if args.trace:
        metrics = {}
        for m in cell.per_layer():
            value = load_reader(cell.bench_dir, m["reader"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": measured[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end()}

    # -- correct ----------------------------------------------------------
    t = time.perf_counter()
    numbers = compare.compare_answers(
        [(st.query, st.table) for st in done], cell.queries, frames)
    numbers["failed_statements"] = len(failed)
    profiles = [st.profile for st in done if st.profile is not None]
    numbers["not_xla_routes"] = routes_off_the_backends(
        done, cell.config.get("backends", ["xla"]))
    numbers["result_cache_hits"] = sum(
        1 for p in profiles if p.cache_status == "hit")
    correct, checks = compare.verdict(numbers, cell.config["limits"])
    emit("compare", seconds=time.perf_counter() - t,
         answers=len(done), profiles=len(profiles),
         native_active=native)

    result = {"correct": correct, "attempted": len(statements),
              "failed": len(failed), "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
