"""repro.obs — end-to-end observability for the serving stack.

SparseP's method is phase decomposition (load / kernel / retrieve, Figs. 4
and 17-24): you only understand a partitioning scheme by seeing where its
time goes.  This package applies the same discipline to the whole serving
path, so a single trace shows where a request's deadline went:

  * :mod:`tracing` — ``Span`` / ``Tracer``: zero-dep, monotonic-clock,
    thread-safe, ring-buffered request-lifecycle tracing
    (``admit -> queue_wait -> batch_form -> load -> kernel -> retrieve ->
    deliver``), with Chrome/Perfetto trace export (``chrome_trace``) and
    per-request rollups (``trace_summary``).
  * :mod:`metrics` — ``MetricsRegistry``: counters, gauges and windowed
    p50/p95/p99 histograms for queue depth, batch width, tokens remaining,
    cache hit/miss, shed-by-reason and per-phase latency series.
  * :mod:`profile` — ``phase``: the one context manager the program
    times its phases with (``spmv.solve.place``, ``spmv.multiply.run``,
    ...), written both as a span on a ``Trace`` and as a ``jax.profiler``
    annotation of the same name, so a captured device profile shows the
    program's phases on the device's clock; annotations degrade to no-ops
    wherever the profiler is absent.

Wiring: `repro.serve.AsyncSpmvService` owns a ``Tracer`` + ``MetricsRegistry``
and threads a per-request trace through `repro.engine.MicroBatcher` into
`repro.engine.SpmvEngine.multiply`; `repro.serve.replay` folds the spans
into the SLO report's per-phase attribution, and ``tools/trace_dump.py``
renders a replay as one Perfetto-loadable artifact.  See
``docs/observability.md``.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .profile import annotate, phase, set_enabled
from .tracing import (
    NULL_TRACE,
    PHASES,
    NullTrace,
    Span,
    Trace,
    Tracer,
    chrome_trace,
    merge_chrome_traces,
    trace_summary,
)

__all__ = [
    "PHASES",
    "Span",
    "Trace",
    "NullTrace",
    "NULL_TRACE",
    "Tracer",
    "chrome_trace",
    "merge_chrome_traces",
    "trace_summary",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "annotate",
    "phase",
    "set_enabled",
]
