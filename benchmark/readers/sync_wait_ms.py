"""Local executor: time the host spent blocked in those fetches."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("sync", under="execute")


def read(run):
    return median_per_statement(run, _value)
