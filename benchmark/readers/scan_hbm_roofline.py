"""Kernels: the least time the chip's memory could feed the statements'
scans, as a share of the time the device was busy.

Needed bytes are the configuration's ``needed_bytes`` per statement:
rows scanned x logical column widths, read once — the same work
whatever implements it. Bound by bandwidth, not operations: a scan and
an aggregate do a handful of operations per byte."""


def read(run):
    trace = run.trace
    if not trace or not run.peaks or trace["busy_s"] <= 0:
        return None
    t0 = trace["wall"][0]
    t1 = trace["wall"][1]
    ended = [st for st in run.done if t0 <= st.wall1 <= t1]
    if not ended:
        return None
    needed = sum(run.config["needed_bytes"][st.query] for st in ended)
    least_s = needed / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]

