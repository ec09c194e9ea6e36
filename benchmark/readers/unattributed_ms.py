"""Session / planner: time inside the query span that no child span
covers; large means a layer boundary has no span yet."""

from span_metrics import median_per_statement


def _value(p):
    return p.self_ms("query")


def read(run):
    return median_per_statement(run, _value)
