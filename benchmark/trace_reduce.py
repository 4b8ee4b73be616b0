"""Reduces a JAX profiler trace (``.xplane.pb``) to the device numbers
the benchmark reports: busy seconds (the union of the intervals in
which an operation ran on a device, averaged over the devices), the
traced window, the operations that took most time, and the longest
idle gaps, each named by the benchmark's own host span that was open
at its middle.

The window is the host span named ``bench.traced`` (the benchmark opens
one around the traced stretch); device intervals are clipped to it.
A gap met while the benchmark's thread sleeps in ``bench.wait`` is
named for the program's host loop, which has no spans of its own yet.
On a TPU the operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. A test on the CPU passes the CPU client's
plane and line instead.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

TPU_PLANE = "/device:TPU:"
TPU_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
#: the benchmark's own polling sleep: a gap inside it is the program's
#: host loop at work, which has no spans of its own yet
IDLE_SPAN = "bench.wait"
UNSPANNED = "program host loop (no span)"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def short_name(op: str) -> str:
    """An XLA op event's name up to its HLO text: ``%while.93 = (...)``
    becomes ``%while.93``."""
    return op.split(" = ", 1)[0]


def _events(plane, line_prefix):
    for line in plane.lines:
        if line.name.startswith(line_prefix):
            for ev in line.events:
                yield ev


def reduce(path: str, device_plane: str = TPU_PLANE,
           op_line: str = TPU_OP_LINE, window_span: str = WINDOW_SPAN,
           top: int = 10) -> dict:
    """The numbers of one trace file; raises where the trace holds no
    window span or no device operation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns
                        span = (s, s + ev.duration_ns, ev.name)
                        if ev.name == window_span:
                            window = span
                        elif ev.name != IDLE_SPAN:
                            spans.append(span)
        if plane.name.startswith(device_plane):
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    lo, hi = window[0], window[1]
    busy_ns, op_ns, gaps = [], defaultdict(float), []
    for plane in devices:
        ops = []
        for ev in _events(plane, op_line):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                ops.append((s, e))
                op_ns[short_name(ev.name)] += min(e, hi) - max(s, lo)
        busy = union(clip(ops, lo, hi))
        if not busy:
            continue
        busy_ns.append(sum(e - s for s, e in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_ns:
        raise ValueError(f"no device operation inside the window in {path}")
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    def label(gap):
        mid = (gap[0] + gap[1]) / 2
        return next((n for a, b, n in spans if a <= mid <= b), UNSPANNED)

    by_label = defaultdict(float)
    for gap in gaps:
        by_label[label(gap)] += (gap[1] - gap[0]) / 1e9
    gap_list = [[label(g), (g[1] - g[0]) / 1e9]
                for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top]]
    ops_list = sorted(([n, t / 1e9] for n, t in op_ns.items()),
                      key=lambda x: -x[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "devices": len(busy_ns), "device_ops": ops_list,
            "idle_gaps": gap_list, "idle_by_span": dict(by_label)}
