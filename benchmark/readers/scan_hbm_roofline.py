"""Kernels: the least time the chip's memory could feed the statements'
scans, as a share of the time the device was busy.

Needed bytes are ``needed_bytes.needed_bytes`` of each answered
statement that ran in the traced window, times the share of its own
time the window holds. Bound by bandwidth, not operations: a scan and
an aggregate do a handful of operations per byte."""

from needed_bytes import needed_bytes
from span_metrics import shares_in_window


def read(run):
    trace = run.trace
    if not trace or not run.peaks or trace["busy_s"] <= 0:
        return None
    needed = sum(needed_bytes(run.queries[st.query], run.config) * s
                 for st, s in shares_in_window(run, run.done))
    if needed <= 0:
        return None
    least_s = needed / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
