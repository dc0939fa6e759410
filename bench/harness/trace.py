"""Reduce a JAX profiler trace to busy time, top device ops and idle gaps.

The traced window is the host span named :data:`WINDOW_SPAN`, which the
runner opens around the measured loop.  On each device plane the events of
the ``XLA Ops`` line are the device's operations; an op that holds others
(a ``while`` around its body) is charged only its self time in the top list.
An op is labelled by its program (the ``XLA Modules`` event around it), its
HLO name and, where it has one, its result shape: ``jit_run:fusion.1
f32[32768,8]``.  Busy time is the union of the ops' intervals clipped to the
window, so nested or overlapping ops count once; idle time is the rest of
the window.  Each idle gap is named by the innermost host span (the shortest
one on any host thread) that holds the gap's midpoint: what the host was
doing while the device waited.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import heapq
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHAPE = re.compile(r"^([a-z]+[0-9]*\[[0-9,]*\])")
HOST_PLANE = "/host:CPU"
NO_HOST_SPAN = "(no host span)"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the device planes
    devices: int
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[host span, seconds]]

    @property
    def idle_pct(self):
        if self.devices == 0 or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(logdir: str) -> str:
    """The newest ``*.xplane.pb`` under a ``jax.profiler.trace`` logdir."""
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi):
    """The parts of [lo, hi] not covered by the disjoint sorted ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def name_gaps(gap_list, host_events):
    """Pair each gap with the innermost host event holding its midpoint.

    ``host_events`` are (start, end, name).  One sweep over midpoints in
    time order, with a heap of open events keyed by duration: an event that
    ended before the current midpoint ends before every later one too.
    """
    events = sorted(host_events)
    order = sorted(range(len(gap_list)),
                   key=lambda i: (gap_list[i][0] + gap_list[i][1]) / 2)
    names = [NO_HOST_SPAN] * len(gap_list)
    heap, k = [], 0
    for i in order:
        mid = (gap_list[i][0] + gap_list[i][1]) / 2
        while k < len(events) and events[k][0] <= mid:
            s, e, name = events[k]
            heapq.heappush(heap, (e - s, e, name))
            k += 1
        while heap and heap[0][1] < mid:
            heapq.heappop(heap)
        if heap:
            names[i] = heap[0][2]
    return names


def op_label(hlo: str) -> str:
    """``%fusion.1 = f32[512,8]{1,0:T(8,128)} fusion(...)`` -> ``fusion.1
    f32[512,8]``: the HLO name and, for an array result, its shape."""
    head, _, rest = hlo.partition(" = ")
    m = SHAPE.match(rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def module_label(name: str) -> str:
    """``jit_loop_tol(12345)`` -> ``jit_loop_tol``."""
    return name.split("(", 1)[0]


def self_times(events):
    """(name, self ns) of each (start, end, name) event: its duration less
    that of the events nested directly inside it."""
    out, stack = [], []  # stack of [end, name, self]
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, nm, own = stack.pop()
            out.append((nm, own))
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    out.extend((nm, own) for _, nm, own in stack)
    return out


def top(pairs, n=10):
    """Sum seconds per name; the ``n`` largest as [[name, seconds], ...]."""
    acc = {}
    for name, sec in pairs:
        acc[name] = acc.get(name, 0.0) + sec
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def read_planes(path: str):
    """(device ops per device plane, host events) from an xplane file (or a
    gzipped one), all as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((ev.start_ns, ev.end_ns, module_label(ev.name))
                             for ev in lines.get(MODULES_LINE, []))
            ops = []
            for ev in sorted(lines.get(OPS_LINE, []),
                             key=lambda ev: ev.start_ns):
                ops.append((ev.start_ns, ev.end_ns,
                            _in_module(modules, ev.start_ns)
                            + op_label(ev.name)))
            devices.append(ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((ev.start_ns, ev.end_ns, ev.name)
                            for ev in line.events)
    return devices, host


def _in_module(modules, t) -> str:
    """``"<module>:"`` of the module event holding time t, else ``""``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2] + ":"
    return ""


def reduce_events(devices, host) -> TraceSummary:
    """Summarise device ops and host events (ns) over the window span."""
    windows = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    host_spans = [(s, e, name) for s, e, name in host
                  if name != WINDOW_SPAN and e > lo and s < hi]
    busy_total, op_pairs, gap_pairs = 0.0, [], []
    for ops in devices:
        clipped = [(max(s, lo), min(e, hi), name) for s, e, name in ops
                   if e > lo and s < hi]
        busy = union([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in busy) * 1e-9
        op_pairs.extend((name, own * 1e-9) for name, own in
                        self_times(clipped))
        idle = gaps(busy, lo, hi)
        for (s, e), name in zip(idle, name_gaps(idle, host_spans)):
            gap_pairs.append((name, (e - s) * 1e-9))
    n = len(devices)
    scale = 1.0 / n if n else 0.0
    return TraceSummary(
        window_s=window_s,
        busy_s=busy_total * scale,
        devices=n,
        device_ops=[[k, v * scale] for k, v in top(op_pairs)],
        idle_gaps=[[k, v * scale] for k, v in top(gap_pairs)],
    )


def reduce_trace(path: str) -> TraceSummary:
    return reduce_events(*read_planes(path))
