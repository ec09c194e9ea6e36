"""Local executor: what a statement's scans hold on the device, decoded
now or served from the fragment cache, in 1e9 bytes. 0 where no
``op.ScanExec`` span carries ``bytes`` (a program from before the
attribute)."""

from span_metrics import median_per_statement


def _value(p):
    return sum(s.attributes.get("bytes", 0) for s in p.spans
               if s.name == "op.ScanExec") / 1e9


def read(run):
    return median_per_statement(run, _value)
