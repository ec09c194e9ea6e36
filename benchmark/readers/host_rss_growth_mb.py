"""Local executor: growth of the server's resident set over a statement."""

from span_metrics import median_per_statement


def _value(p):
    roots = [s for s in p.spans if s.name == "query"]
    if not roots:
        return 0.0
    attrs = roots[0].attributes
    return attrs.get("rss_mb_end", 0.0) - attrs.get("rss_mb_start", 0.0)


def read(run):
    return median_per_statement(run, _value)
