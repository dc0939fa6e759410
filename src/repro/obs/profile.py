"""Program phases, written once to both sinks: ``repro.obs`` and the profiler.

:class:`phase` is the one context manager the program times its phases
with.  It stamps a completed :class:`~repro.obs.tracing.Span` on the
caller's :class:`~repro.obs.tracing.Trace`, if any (the shared
:data:`NULL_TRACE` records nothing) and, where annotations are enabled,
opens a ``jax.profiler.TraceAnnotation`` of the phase's name around the
same interval.
The profiler records that interval itself, so in a captured device profile
(``jax.profiler.trace(logdir)``) the program's phases sit on the device
trace's clock, beside the ops they issued.  Callers read the phase's
``seconds`` from the same boundaries.

Names are stable and carry no matrix name (that rides as an annotation
argument), so a trace reader can find them.  On a request's ``Trace`` the
serving phases are stamped under their request-lifecycle names
(``load`` / ``kernel`` / ``retrieve``, :data:`repro.obs.PHASES`), which
the SLO attribution is defined over:

  * ``spmv.solve`` > ``spmv.solve.place`` / ``.loop`` / ``.fetch``
  * ``spmv.multiply`` > ``spmv.multiply.place`` / ``.run`` / ``.fetch``
  * ``spmv.register`` > ``spmv.register.stats`` / ``.fingerprint`` /
    ``.plan`` / ``.partition`` / ``.place`` / ``.compile``
  * ``spmv.compile``: trace and compile a plan's program

Annotations degrade to a shared no-op wherever ``jax.profiler`` lacks
``TraceAnnotation``, after ``set_enabled(False)``, or under
``REPRO_OBS_PROFILE=0``; the phase is still timed.
"""
from __future__ import annotations

import os
from typing import Optional

from .tracing import NULL_TRACE, clock

__all__ = ["annotate", "phase", "set_enabled"]

try:  # profiler-less builds (or a stripped jax) must not break serving
    from jax.profiler import TraceAnnotation as _TraceAnnotation

    _AVAILABLE = True
except Exception:  # pragma: no cover - exercised only on stripped installs
    _TraceAnnotation = None
    _AVAILABLE = False

_enabled = _AVAILABLE and os.environ.get("REPRO_OBS_PROFILE", "1") != "0"


class _NullAnnotation:
    """Shared no-op annotation (never allocated per call)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullAnnotation()


def set_enabled(on: bool) -> bool:
    """Toggle annotation emission; returns the effective state (stays off
    when the profiler is unavailable)."""
    global _enabled
    _enabled = bool(on) and _AVAILABLE
    return _enabled


def annotate(name: str, **kwargs):
    """A ``TraceAnnotation(name, **kwargs)``, or the shared no-op when
    disabled."""
    if not _enabled:
        return _NULL
    return _TraceAnnotation(name, **kwargs)


class phase:
    """Time one program phase and write it to both sinks.

    ``with phase("spmv.solve.place", trace, span="load", n=8) as p: ...``
    records the span ``load`` (``span``, by default the phase's name) with
    ``args`` on ``trace`` (a Trace, a sequence of them or None) and, while
    annotations are on, a profiler annotation ``spmv.solve.place``
    carrying ``args``; ``p.start_s``, ``p.end_s`` and ``p.seconds`` are on
    :data:`repro.obs.tracing.clock`.  The annotation opens before the clock
    starts and closes after it stops, so the profiler's interval holds the
    span's.  The span is recorded even when the block raises.
    """

    __slots__ = ("name", "traces", "span", "args", "start_s", "end_s",
                 "_annotation")

    def __init__(self, name: str, trace=NULL_TRACE, *,
                 span: Optional[str] = None, **args):
        self.name = name
        self.traces = (() if trace is None else
                       trace if isinstance(trace, (list, tuple)) else (trace,))
        self.span = name if span is None else span
        self.args = args
        self.start_s = self.end_s = 0.0

    def __enter__(self) -> "phase":
        self._annotation = annotate(self.name, **self.args)
        self._annotation.__enter__()
        self.start_s = clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_s = clock()
        self._annotation.__exit__(*exc)
        for trace in self.traces:
            trace.add(self.span, self.start_s, self.end_s, **self.args)
        return False

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s

