"""Session / planner: the planner's own time: resolve and optimize minus
what their child spans cover, plus parse where the statement has one."""

from span_metrics import median_per_statement


def _value(p):
    return p.self_ms("resolve") + p.self_ms("optimize") + p.phases.get("parse", 0.0)


def read(run):
    return median_per_statement(run, _value)
