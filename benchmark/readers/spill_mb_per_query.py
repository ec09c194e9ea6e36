"""Local executor: bytes a statement's out-of-core paths wrote to disk."""

from span_metrics import median_per_statement


def _value(p):
    return p.spill_bytes / 1e6


def read(run):
    return median_per_statement(run, _value)
