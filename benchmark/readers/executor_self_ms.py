"""Local executor: the executor's own host time: the execute span and the
op.<PlanNode> spans beneath it, minus what their children on the same
thread cover (dispatch, compile, sync, upload, scan.wait)."""

from span_metrics import median_per_statement


def _value(p):
    return p.self_ms("execute") + sum(
        p.self_ms(name) for name in {s.name for s in p.spans
                                     if s.name.startswith("op.")})


def read(run):
    return median_per_statement(run, _value)
