"""Local executor (streamed scan): time the consumer waited on the
prefetch queue for the host's decode."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("scan.wait")


def read(run):
    return median_per_statement(run, _value)
