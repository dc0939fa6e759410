"""Least time of the window's SpMV steps at peak HBM bandwidth over the
device self time of the kernel's own ops: those under a ``spmv.`` name scope
inside the solver's ``iterate.step`` (``bench/harness/spans.py``).  Bytes
are ``spmv_roofline``'s; unlike it, the solver's vector ops and CG's extra
r0 product are left out of the time."""
from bench.harness import spans
from bench.harness.roofline import roofline_pct, spmv_bytes

SOURCE = "device_trace"


def read(run):
    calls = run.window.get("spmv_calls")
    s = spans.of_run(run)
    if s is None or not calls or s.kernel_s <= 0:
        return None
    moved = spmv_bytes(run.nnz, run.shape, run.itemsize) * calls
    return roofline_pct(moved, s.kernel_s, run.device_kind)
