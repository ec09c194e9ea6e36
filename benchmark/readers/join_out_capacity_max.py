"""Local executor: the widest join output of a statement, in rows of
allocated capacity: what a join order that expands costs every operator
above it. 0 where no ``op.JoinExec`` span carries ``out_capacity`` (a
statement without a join; a program from before the attribute)."""

from span_metrics import median_per_statement


def _value(p):
    return max((s.attributes.get("out_capacity", 0) for s in p.spans
                if s.name == "op.JoinExec"), default=0)


def read(run):
    return median_per_statement(run, _value)
