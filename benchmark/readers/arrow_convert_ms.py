"""Local executor: host time of Arrow-to-host column conversion, the
arrow.convert spans under execute (columnar/arrow_interop.py from_arrow:
decimal to scaled int64, strings dictionary-encoded, validity; padding
and device_put are the upload span beside it). 0 where the tree has
none: a resident scan answered from the fragment cache, a program from
before the span."""

from span_metrics import median_per_statement


def _value(p):
    return p.span_ms("arrow.convert", under="execute")


def read(run):
    return median_per_statement(run, _value)
