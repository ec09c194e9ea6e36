"""CLI entry point: ``python -m sail_tpu <command>``.

Reference role: sail-cli (crates/sail-cli/src/runner.rs — spark server /
shell / worker subcommands).
"""

from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(prog="sail_tpu",
                                     description="TPU-native Spark-capable engine")
    sub = parser.add_subparsers(dest="command", required=True)

    p_server = sub.add_parser(
        "server", help="run the Spark Connect server (+ native SQL protocol)")
    p_server.add_argument("--host", default="127.0.0.1")
    p_server.add_argument("--port", type=int, default=50051,
                          help="Spark Connect port (15002 is Spark's default)")
    p_server.add_argument("--sql-port", type=int, default=0,
                          help="also serve the native SQL protocol here")

    p_shell = sub.add_parser("shell", help="interactive SQL shell")
    p_shell.add_argument("--remote", default=None,
                         help="host:port of a running server (default: in-process)")

    p_bench = sub.add_parser("bench", help="run the benchmark")
    p_bench.add_argument("sf", nargs="?", type=float, default=1.0)

    p_flight = sub.add_parser(
        "flight", help="run the Arrow Flight SQL server")
    p_flight.add_argument("--host", default="127.0.0.1")
    p_flight.add_argument("--port", type=int, default=32010)

    sub.add_parser(
        "mcp-server",
        help="run the MCP (Model Context Protocol) server over stdio "
             "(reference: sail spark mcp-server)")

    p_compat = sub.add_parser(
        "compat",
        help="scan Python files for PySpark API usage and report this "
             "engine's support status (reference: pysail compatibility "
             "check)")
    p_compat.add_argument("paths", nargs="+",
                          help="Python files or directories to scan")

    p_worker = sub.add_parser(
        "worker", help="run a standalone cluster worker process")
    p_worker.add_argument("--driver", required=True,
                          help="host:port of the driver control plane")
    p_worker.add_argument("--host", default="127.0.0.1",
                          help="address to bind")
    p_worker.add_argument("--advertise-host", default=None,
                          help="address the driver/peers dial (defaults to "
                               "--host; set to the pod IP when binding "
                               "0.0.0.0)")
    p_worker.add_argument("--task-slots", type=int, default=2)
    p_worker.add_argument("--worker-id", default=None)

    args = parser.parse_args(argv)
    if args.command == "compat":
        from .compat import check_paths, format_report
        print(format_report(check_paths(args.paths)))
        return 0

    if args.command == "mcp-server":
        from .mcp_server import McpSparkServer
        McpSparkServer().serve()
        return 0

    if args.command == "server":
        from .spark_connect import SparkConnectServer
        server = SparkConnectServer(args.host, args.port).start()
        print(f"sail-tpu Spark Connect server listening on "
              f"sc://{args.host}:{server.port}")
        sql_server = None
        try:
            if args.sql_port:
                from .server import SqlServer
                sql_server = SqlServer(args.host, args.sql_port).start()
                print(f"sail-tpu native SQL server listening on "
                      f"{args.host}:{sql_server.port}")
            server.wait()
        except KeyboardInterrupt:
            pass
        finally:
            server.stop()
            if sql_server is not None:
                sql_server.stop()
        return 0

    if args.command == "shell":
        return _shell(args.remote)

    if args.command == "bench":
        import subprocess
        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py")
        return subprocess.call([sys.executable, bench, str(args.sf)])

    if args.command == "flight":
        from .flight_sql import FlightSqlServer
        server = FlightSqlServer(args.host, args.port)
        print(f"sail-tpu Flight SQL server listening on "
              f"grpc://{args.host}:{server.port}")
        try:
            server.serve()
        except KeyboardInterrupt:
            pass
        finally:
            server.shutdown()
        return 0

    if args.command == "worker":
        import uuid as _uuid
        from .exec.cluster import WorkerActor
        worker_id = args.worker_id or f"worker-{_uuid.uuid4().hex[:8]}"
        w = WorkerActor(worker_id, args.driver, args.task_slots,
                        host=args.host,
                        advertise_host=(args.advertise_host or
                                        os.environ.get("SAIL_POD_IP")))
        w.start(worker_id)
        print(f"sail-tpu worker {worker_id} registered with {args.driver}")
        try:
            import time as _time
            while True:
                _time.sleep(3600)
        except KeyboardInterrupt:
            pass
        finally:
            w.stop()
        return 0

    return 1


def _shell(remote):
    if remote:
        # the server speaks Spark Connect; the shell does too
        from .spark_connect.client import SparkConnectClient
        client = SparkConnectClient(remote)
        run = client.sql
    else:
        from . import SparkSession
        spark = SparkSession.builder.getOrCreate()
        run = lambda q: spark.sql(q).toArrow()  # noqa: E731
    print("sail-tpu SQL shell — ';' to run, 'exit' to quit")
    buf = []
    while True:
        try:
            prompt = "sql> " if not buf else "...> "
            line = input(prompt)
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        if line.strip().lower() in ("exit", "quit"):
            return 0
        buf.append(line)
        if line.rstrip().endswith(";"):
            query = "\n".join(buf).rstrip().rstrip(";")
            buf = []
            try:
                table = run(query)
                print(table.to_pandas().to_string(index=False, max_rows=50))
            except Exception as e:  # noqa: BLE001 — REPL surfaces all errors
                print(f"error: {e}")


if __name__ == "__main__":
    sys.exit(main())
