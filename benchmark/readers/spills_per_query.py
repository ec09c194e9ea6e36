"""Local executor: joins and sorts of a statement that left the device."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_count("spill", under="execute")


def read(run):
    return median_per_statement(run, _value)
