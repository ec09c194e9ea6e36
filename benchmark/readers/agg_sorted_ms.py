"""Kernels: time of the aggregates that group by sorting, the
``op.AggregateExec`` and ``op.FusedAggregate`` spans whose ``path`` is
``sorted`` (exec/local.py LocalExecutor._agg_with_chain), each without
the operators beneath it: the program's dispatch and the ``agg.n_groups``
sync that waits for it. 0 where no span carries the attribute: a
statement whose aggregates bin or have no key, a program from before the
attribute."""

from span_metrics import median_per_statement

AGGREGATES = ("op.AggregateExec", "op.FusedAggregate")


def _own_ms(span, children):
    """The span's duration less what its child operator spans on the
    same thread cover."""
    covered, at = 0, span.start_ns
    for c in sorted((c for c in children.get(span.span_id, ())
                     if c.name.startswith("op.")
                     and c.thread_id == span.thread_id),
                    key=lambda c: c.start_ns):
        lo, hi = max(c.start_ns, at), min(c.end_ns, span.end_ns)
        if hi > lo:
            covered += hi - lo
            at = hi
    return (span.end_ns - span.start_ns - covered) / 1e6


def _value(p):
    children = {}
    for s in p.spans:
        children.setdefault(s.parent_id, []).append(s)
    return sum(_own_ms(s, children) for s in p.spans
               if s.name in AGGREGATES
               and s.attributes.get("path") == "sorted")


def read(run):
    return median_per_statement(run, _value)
