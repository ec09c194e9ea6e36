"""Session / planner: what the join reorder costs a statement, the
footers' distinct-count bounds included. 0 where the program opens no
``optimize.join_reorder`` span (a statement without a join; a program
from before the span)."""

from span_metrics import median_per_statement


def _value(p):
    return p.self_ms("optimize.join_reorder")


def read(run):
    return median_per_statement(run, _value)
