"""Local executor: host time of the eager join expansion, the
join.expand spans under execute (exec/local.py LocalExecutor._join_expand:
its gathers run outside any named program, so no dispatch span covers
them). 0 where the tree has none: a statement whose joins keep unique
build keys, a program from before the span."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("join.expand", under="execute")


def read(run):
    return median_per_statement(run, _value)
