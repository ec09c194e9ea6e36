"""Local executor: rows the runtime filters' pushed conjuncts kept out of
a statement's scans (``QueryProfile.rtf_rows_pruned``). 0 where nothing
pushed pruned a measured scan: bounds the column's whole range
satisfies, or a key list too long to push."""

from span_metrics import median_per_statement


def _value(p):
    return getattr(p, "rtf_rows_pruned", 0)


def read(run):
    return median_per_statement(run, _value)
