"""Program spans and kernel names from a JAX profiler trace.

The program names its phases and kernels (``repro.obs.phase`` and
``jax.named_scope``): a phase is a host event of the profiler on the same
clock as the device's ops (``spmv.solve.place``, ``spmv.solve.loop``,
``spmv.solve.fetch``), and a device op carries its name stack in the
``tf_op`` stat of its event metadata
(``jit(loop_tol)/while/body/iterate.step/.../spmv.coo.xla/scatter-add``).
``jax.profiler.ProfileData`` does not expose that stat, so this module reads
the ``.xplane.pb`` itself with a small reader of the protobuf wire format;
it needs no generated classes.

Over the window span (:data:`bench.harness.trace.WINDOW_SPAN`) it returns,
averaged over the device planes:

  * the device self time of the ops whose name stack holds a ``spmv.``
    scope under ``iterate.step``: the solver's SpMV kernel;
  * the self time of the other ops of the loop programs (the programs in
    which some op sits under ``iterate.step``) outside any ``spmv.`` scope:
    the solver's vector ops and loop control;
  * the device-idle time, each part of a gap charged to the innermost
    ``spmv.solve`` program span it overlaps, or to none: a gap that opens
    in ``spmv.solve.place`` and closes in ``spmv.solve.loop`` is split at
    the phase boundary, so the parts sum to the window's idle time.

A parse is cached per file, so several metric readers parse it once.  A
trace of a program that names nothing yields zero kernel time, no loop
program and no sessions, and the readers then report nothing.
"""
from __future__ import annotations

import bisect
import functools
import gzip
import os
from dataclasses import dataclass, field
from typing import Optional

from .runner import TRACE_DIR
from .trace import (DEVICE_PLANE, HOST_PLANE, MODULES_LINE, NO_HOST_SPAN,
                    OPS_LINE, WINDOW_SPAN, find_xplane, gaps, module_label,
                    name_gaps, self_times, union)

STEP_SCOPE = "iterate.step"
KERNEL_PREFIX = "spmv."
SESSION_SPAN = "spmv.solve"
TF_OP = "tf_op"

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 3, 4, 5
LINE_NAME, LINE_TIMESTAMP_NS, LINE_EVENTS = 2, 3, 4
EVENT_METADATA_ID, EVENT_OFFSET_PS, EVENT_DURATION_PS = 1, 2, 3
META_ID, META_NAME, META_STATS = 1, 2, 5
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7
MAP_VALUE = 2
VARINT, FIXED64, LENGTH, FIXED32 = 0, 1, 2, 5


# ------------------------------------------------------------ wire format


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of each field in ``buf[lo:hi]``: an int for a
    varint, a (start, end) slice for a length-delimited field; fixed-width
    fields are skipped.

    Raises:
      ValueError: on a wire type the format does not define.
    """
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == VARINT:
            value, i = _varint(buf, i)
            yield number, value
        elif kind == LENGTH:
            n, i = _varint(buf, i)
            yield number, (i, i + n)
            i += n
        elif kind == FIXED64:
            i += 8
        elif kind == FIXED32:
            i += 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value slice of a map<int64, message> entry."""
    for number, value in fields(buf, *span):
        if number == MAP_VALUE:
            return value
    return None


def _stat_names(buf, plane_fields) -> dict:
    names = {}
    for number, span in plane_fields:
        if number != PLANE_STAT_METADATA:
            continue
        value = _map_values(buf, span)
        if value is None:
            continue
        sid, name = 0, ""
        for k, v in fields(buf, *value):
            if k == META_ID:
                sid = v
            elif k == META_NAME:
                name = _text(buf, v)
        names[sid] = name
    return names


def _event_metadata(buf, plane_fields, stat_names, with_stack: bool) -> dict:
    """{metadata id: (name, name stack)}; the stack from the ``tf_op`` stat,
    without its ``:<op type>`` suffix, or "" where there is none."""
    tf_op = {sid for sid, name in stat_names.items() if name == TF_OP}
    out = {}
    for number, span in plane_fields:
        if number != PLANE_EVENT_METADATA:
            continue
        value = _map_values(buf, span)
        if value is None:
            continue
        mid, name, stack = 0, "", ""
        for k, v in fields(buf, *value):
            if k == META_ID:
                mid = v
            elif k == META_NAME:
                name = _text(buf, v)
            elif k == META_STATS and with_stack and tf_op:
                stack = _stat_str(buf, v, tf_op, stat_names) or stack
        out[mid] = (name, stack.rpartition(":")[0] if ":" in stack else stack)
    return out


def _stat_str(buf, span, wanted: set, stat_names: dict) -> Optional[str]:
    sid, text = None, None
    for k, v in fields(buf, *span):
        if k == STAT_METADATA_ID:
            sid = v
        elif k == STAT_STR:
            text = _text(buf, v)
        elif k == STAT_REF:
            text = stat_names.get(v)
    return text if sid in wanted else None


def _lines(buf, plane_fields, wanted=None):
    """(line name, [(start_ps, end_ps, metadata id)]) per line."""
    for number, span in plane_fields:
        if number != PLANE_LINES:
            continue
        name, ts_ns, events = "", 0, []
        for k, v in fields(buf, *span):
            if k == LINE_NAME:
                name = _text(buf, v)
            elif k == LINE_TIMESTAMP_NS:
                ts_ns = v
            elif k == LINE_EVENTS:
                events.append(v)
        if wanted is not None and name not in wanted:
            continue
        out = []
        for ev in events:
            mid = offset = duration = 0
            for k, v in fields(buf, *ev):
                if k == EVENT_METADATA_ID:
                    mid = v
                elif k == EVENT_OFFSET_PS:
                    offset = v
                elif k == EVENT_DURATION_PS:
                    duration = v
            start = ts_ns * 1000 + offset
            out.append((start, start + duration, mid))
        yield name, out


@dataclass
class Planes:
    """A trace's device ops and host events, times in ps."""

    devices: list = field(default_factory=list)  # [[(s, e, module, stack)]]
    host: list = field(default_factory=list)  # [(s, e, name)]


def parse(data: bytes) -> Planes:
    """The device planes' ops and the host plane's events of an XSpace."""
    buf = memoryview(data)
    out = Planes()
    for number, span in fields(buf):
        if number != SPACE_PLANES:
            continue
        plane = list(fields(buf, *span))
        name = next((_text(buf, v) for k, v in plane if k == PLANE_NAME), "")
        is_device = bool(DEVICE_PLANE.match(name))
        if not is_device and name != HOST_PLANE:
            continue
        stat_names = _stat_names(buf, plane)
        meta = _event_metadata(buf, plane, stat_names, with_stack=is_device)
        if not is_device:
            for _, events in _lines(buf, plane):
                out.host.extend((s, e, meta.get(m, ("", ""))[0])
                                for s, e, m in events)
            continue
        lines = dict(_lines(buf, plane, (OPS_LINE, MODULES_LINE)))
        modules = sorted((s, e, module_label(meta.get(m, ("", ""))[0]))
                         for s, e, m in lines.get(MODULES_LINE, []))
        ops = []
        for s, e, m in sorted(lines.get(OPS_LINE, [])):
            ops.append((s, e, _module_at(modules, s), meta.get(m, ("", ""))[1]))
        out.devices.append(ops)
    return out


def _module_at(modules, t) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t <= modules[i][1]:
        return modules[i][2]
    return ""


# ------------------------------------------------------------ name stacks


def scopes(stack: str) -> list:
    return stack.split("/") if stack else []


def in_kernel(stack: str) -> bool:
    """A ``spmv.`` scope under ``iterate.step``: the solver's SpMV."""
    parts = scopes(stack)
    if STEP_SCOPE not in parts:
        return False
    return any(p.startswith(KERNEL_PREFIX)
               for p in parts[parts.index(STEP_SCOPE) + 1:])


def in_any_kernel(stack: str) -> bool:
    return any(p.startswith(KERNEL_PREFIX) for p in scopes(stack))


# ------------------------------------------------------------ the window


def phase_pieces(phases, lo: int, hi: int) -> list:
    """[lo, hi] cut at every boundary of a (start, end, name) phase: no
    boundary falls inside a piece, so the innermost phase that holds a
    piece's midpoint holds all of it."""
    cuts = {lo, hi}
    cuts.update(t for s, e, _ in phases for t in (s, e) if lo < t < hi)
    cuts = sorted(cuts)
    return list(zip(cuts, cuts[1:]))


def charge(idle, pieces, names):
    """(name, ps) of each overlap of the sorted disjoint ``idle`` gaps with
    the sorted disjoint ``pieces``, named as ``names``."""
    i = 0
    for (s, e), name in zip(pieces, names):
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            yield name, min(e, idle[j][1]) - max(s, idle[j][0])
            j += 1


@dataclass
class SpanSummary:
    window_s: float
    devices: int
    kernel_s: float  # self time of spmv. ops under iterate.step
    loop_other_s: Optional[float]  # loop programs' other ops; None: no loop
    idle_by_phase: dict  # {innermost spmv.solve span or NO_HOST_SPAN: s};
    # the values sum to the window's idle time
    sessions: int  # spmv.solve spans wholly inside the window


def summarize_planes(planes: Planes) -> SpanSummary:
    """Reduce a trace's ops and host events to :class:`SpanSummary`.

    Raises:
      ValueError: the trace holds no window span.
    """
    windows = [(s, e) for s, e, name in planes.host if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    lo, hi = windows[0]
    phases = [(s, e, name) for s, e, name in planes.host
              if name.startswith(SESSION_SPAN) and e > lo and s < hi]
    sessions = sum(1 for s, e, name in phases
                   if name == SESSION_SPAN and lo <= s and e <= hi)
    pieces = phase_pieces(phases, lo, hi)
    names = name_gaps(pieces, phases)
    kernel = other = 0
    idle = {}
    loops = False
    for ops in planes.devices:
        loop_modules = {m for _, _, m, stack in ops
                        if STEP_SCOPE in scopes(stack)}
        loops = loops or bool(loop_modules)
        clipped = [(max(s, lo), min(e, hi), (m, stack))
                   for s, e, m, stack in ops if e > lo and s < hi]
        for (module, stack), own in self_times(clipped):
            if in_kernel(stack):
                kernel += own
            elif module in loop_modules and not in_any_kernel(stack):
                other += own
        busy = union([(s, e) for s, e, _ in clipped])
        for name, t in charge(gaps(busy, lo, hi), pieces, names):
            idle[name] = idle.get(name, 0) + t
    n = len(planes.devices)
    scale = 1e-12 / n if n else 0.0
    return SpanSummary(
        window_s=(hi - lo) * 1e-12,
        devices=n,
        kernel_s=kernel * scale,
        loop_other_s=other * scale if loops else None,
        idle_by_phase={k: v * scale for k, v in idle.items()},
        sessions=sessions,
    )


def read_planes(path: str) -> Planes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return parse(f.read())


@functools.lru_cache(maxsize=4)
def _summarize_cached(path: str, mtime_ns: int, size: int) -> SpanSummary:
    return summarize_planes(read_planes(path))


def summarize(path: str) -> SpanSummary:
    """:func:`summarize_planes` of the trace at ``path``, parsed once per
    version of the file."""
    st = os.stat(path)
    return _summarize_cached(path, st.st_mtime_ns, st.st_size)


def of_run(run) -> Optional[SpanSummary]:
    """The summary of a run's traced window, or None for an untraced run."""
    if run.trace is None:
        return None
    return summarize(find_xplane(str(TRACE_DIR)))


def idle_ms_per_session(run, span: str) -> Optional[float]:
    """Device-idle ms charged to program span ``span`` per session of a
    run's traced window; None for an untraced run, one with no device plane
    or one with no session."""
    s = of_run(run)
    if s is None or not s.devices or not s.sessions:
        return None
    return 1e3 * s.idle_by_phase.get(span, 0.0) / s.sessions


__all__ = ["NO_HOST_SPAN", "Planes", "SpanSummary", "fields", "idle_ms_per_session",
           "in_kernel", "of_run", "parse", "read_planes", "summarize",
           "summarize_planes"]
