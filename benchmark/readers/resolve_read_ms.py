"""Session / planner: what resolving the statement's file-backed views
costs, schema inference included (today it reads a file's data)."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("resolve.read_source")


def read(run):
    return median_per_statement(run, _value)
