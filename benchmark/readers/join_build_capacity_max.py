"""Local executor: the widest build side of a statement's joins, in rows
of allocated capacity: what ``build_side``'s sort and the merge's two
run at, whatever the live rows. 0 where no ``op.JoinExec`` span carries
``build_capacity`` (a statement without a join; a program from before
the attribute)."""

from span_metrics import median_per_statement


def _value(p):
    return max((s.attributes.get("build_capacity", 0) for s in p.spans
                if s.name == "op.JoinExec"), default=0)


def read(run):
    return median_per_statement(run, _value)
