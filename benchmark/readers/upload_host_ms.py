"""Local executor: host time padding tables to their capacity bucket and
putting them on the device."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("upload")


def read(run):
    return median_per_statement(run, _value)
