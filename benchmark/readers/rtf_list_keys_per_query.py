"""Local executor: the join keys a statement's runtime filters pushed into
scans as exact lists, the ``rtf_list_keys`` of its ``op.JoinExec`` spans
(exec/local.py LocalExecutor._rtf_prepare) summed. 0 where no span
carries the attribute: only bounds went out, no filter was built, a
program from before the attribute."""

from span_metrics import median_per_statement


def _value(p):
    return sum(s.attributes.get("rtf_list_keys", 0) for s in p.spans
               if s.name == "op.JoinExec")


def read(run):
    return median_per_statement(run, _value)
