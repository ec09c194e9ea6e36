"""Local executor: how near a statement's joins came to leaving the
device: the largest working set as a share of the budget ``out_of_core``
held it to; over 100 the join spilled. 0 where no ``op.JoinExec`` span
carries ``budget_bytes`` (a program from before the attribute; a platform
that reports no memory, as the CPU; a spill row count set)."""

from span_metrics import median_per_statement


def _value(p):
    return max((100.0 * s.attributes.get("working_set_bytes", 0)
                / s.attributes["budget_bytes"] for s in p.spans
                if s.name == "op.JoinExec"
                and s.attributes.get("budget_bytes")), default=0)


def read(run):
    return median_per_statement(run, _value)
