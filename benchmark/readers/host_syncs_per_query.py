"""Local executor: blocking device-to-host fetches inside execute."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_count("sync", under="execute")


def read(run):
    return median_per_statement(run, _value)
