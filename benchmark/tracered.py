"""Reduction of a ``jax.profiler`` trace to the device's busy time, its
idle gaps and what the host was doing in them.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists
({plane: {line: [[name, start_ns, duration_ns], ...]}}), which is also
the form of the small recorded trace the tests keep; everything else
works on that form and imports nothing of JAX.

A device plane is one named ``/device:TPU:<n>``. Its ``XLA Ops`` line
holds one event per executed HLO operation; the union of those
intervals, clipped to the window, is the time the device was busy.
The window is the benchmark's own ``bench:window`` annotation on the
host plane, and ``bench:call:<stream>:<query>`` annotations (one per
client call) label the gaps.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench:window"
CALL_SPAN = "bench:call:"
OPS_LINE = "XLA Ops"
#: lines of a device plane that repeat what ``XLA Ops`` holds at a
#: coarser grain; used only where a plane has no ``XLA Ops`` line
COARSE_LINES = ("XLA Modules", "Steps")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns)])
    return planes


def device_planes(planes: dict) -> list:
    return sorted(p for p in planes if p.startswith("/device:TPU:"))


def host_spans(planes: dict, prefix: str) -> list:
    """[(name, start_ns, end_ns)] of the host annotations whose name
    starts with ``prefix``, from every non-device plane."""
    out = []
    for plane, lines in planes.items():
        if plane.startswith("/device:"):
            continue
        for events in lines.values():
            out.extend((n, s, s + d) for n, s, d in events
                       if n.startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def op_events(lines: dict) -> list:
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    for name in COARSE_LINES:
        if name in lines:
            return lines[name]
    return []


def short_op(name: str) -> str:
    """XLA prints a whole HLO instruction as an op's name; keep its
    result name and opcode (and a custom call's target)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    opcode = re.search(r"(?:^|\s)([A-Za-z][\w.\-]*)\(", rhs)
    out = f"{lhs.strip()} {opcode.group(1) if opcode else ''}".strip()
    marker = 'custom_call_target="'
    if marker in rhs:
        out += ":" + rhs.split(marker, 1)[1].split('"', 1)[0]
    return out[:80]


def name_ops(lines: dict) -> list:
    """The plane's op events as [name, start, duration] with the name
    shortened and prefixed by the XLA module (the jitted program) that
    was running: op names repeat from program to program."""
    import bisect
    events = op_events(lines)
    if events is not lines.get(OPS_LINE):
        return events
    modules = sorted(lines.get("XLA Modules", []), key=lambda e: e[1])
    starts = [m[1] for m in modules]
    out = []
    for name, start, dur in events:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < modules[i][1] + modules[i][2]
        prefix = modules[i][0] + "/" if inside else ""
        out.append([prefix + short_op(name), start, dur])
    return out


def clip(events: list, t0: float, t1: float) -> list:
    """[(name, start, end)] of the events' parts inside [t0, t1]."""
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e))
    return out


def union(intervals: list) -> list:
    """Merged [(start, end)] of [(name, start, end)]."""
    merged = []
    for _n, s, e in sorted(intervals, key=lambda i: i[1]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def gaps(merged: list, t0: float, t1: float) -> list:
    """[(start, end)] of the window not covered by ``merged``."""
    out, at = [], t0
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def label_gap(mid_ns: float, calls: list, phase_at=None) -> str:
    """What the host was doing at ``mid_ns``: the client calls in
    flight, and for each the profile phase that covered the moment
    (``phase_at(call name, ns)``, where the caller can tell)."""
    live = [n for n, s, e in calls if s <= mid_ns <= e]
    if not live:
        return "no call in flight"
    parts = []
    for name in live:
        phase = phase_at(name, mid_ns) if phase_at else None
        short = name[len(CALL_SPAN):]
        parts.append(f"{short}/{phase}" if phase else short)
    return "+".join(sorted(parts))


def reduce_trace(planes: dict, phase_at=None, top: int = 10,
                 calls: list = None) -> dict:
    """{"window_s", "busy_s", "device_ops", "idle_gaps", "window_ns"};
    busy time is averaged over the device planes.
    ``calls`` = [(name, start_ns, end_ns)] on the trace's clock stands
    in for the trace's own call annotations: a call that began before
    the trace did has no annotation in it. Raises where the trace holds
    no window annotation or no device."""
    windows = host_spans(planes, WINDOW_SPAN)
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} annotation")
    _n, t0, t1 = windows[0]
    devices = device_planes(planes)
    if not devices:
        raise ValueError("the trace holds no /device:TPU: plane; planes: "
                         + ", ".join(sorted(planes)))
    if calls is None:
        calls = host_spans(planes, CALL_SPAN)
    busy_ns, op_ns, gap_ns = 0.0, {}, {}
    for plane in devices:
        inside = clip(name_ops(planes[plane]), t0, t1)
        merged = union(inside)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, e in inside:
            op_ns[name] = op_ns.get(name, 0.0) + (e - s)
        for s, e in gaps(merged, t0, t1):
            label = label_gap((s + e) / 2, calls, phase_at)
            gap_ns[label] = gap_ns.get(label, 0.0) + (e - s)
    n = len(devices)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": busy_ns / n / 1e9,
            "device_ops": ranked(op_ns),
            "idle_gaps": ranked(gap_ns),
            "window_ns": [t0, t1]}


def shrink(planes: dict, keep_events: int = 400) -> dict:
    """A cut of a loaded trace small enough to keep with the tests: the
    host annotations of the benchmark and the first ``keep_events`` of
    every device line."""
    out = {}
    for plane, lines in planes.items():
        if plane.startswith("/device:"):
            out[plane] = {ln: ev[:keep_events] for ln, ev in lines.items()}
        else:
            kept = {ln: [e for e in ev if e[0].startswith("bench:")]
                    for ln, ev in lines.items()}
            kept = {ln: ev for ln, ev in kept.items() if ev}
            if kept:
                out[plane] = kept
    return out
