"""Entry points: the server's own share of the wire: proto decode (and
SQL parse), Arrow IPC encode, response assembly."""

from span_metrics import median_per_statement


def _value(p):
    rpc = p.span_ms("spark_connect:execute_plan")
    return rpc - p.span_ms("query") if rpc else 0.0


def read(run):
    return median_per_statement(run, _value)
