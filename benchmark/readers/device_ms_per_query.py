"""Kernels: device-busy time per statement, from the traced window."""


def read(run):
    trace = run.trace
    if not trace or not trace["calls_in_window"]:
        return None
    return trace["busy_s"] * 1000.0 / trace["calls_in_window"]
