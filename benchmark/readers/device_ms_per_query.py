"""Kernels: device-busy time per statement, from the traced window.

The window's busy seconds over the statements that RAN in it, each
counted by the share of its own time the window holds (failed ones used
the device too). With one closed-loop stream that is the busy share
times the mean statement's time, so it cannot pass the statement."""

from span_metrics import shares_in_window


def read(run):
    if not run.trace:
        return None
    ran = sum(s for _st, s in shares_in_window(run, run.statements))
    if ran <= 0:
        return None
    return run.trace["busy_s"] * 1000.0 / ran
