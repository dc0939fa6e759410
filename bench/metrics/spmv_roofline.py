"""Least time of the window's SpMV steps at peak HBM bandwidth over the
device's busy time.  Bytes are from nnz and the shape alone
(``bench/harness/roofline.py``); busy time holds the solver's vector ops
too, so this is a lower bound on the kernel's own share."""
from bench.harness.roofline import roofline_pct, spmv_bytes

SOURCE = "device_trace"


def read(run):
    calls = run.window.get("spmv_calls")
    if run.trace is None or not calls:
        return None
    moved = spmv_bytes(run.nnz, run.shape, run.itemsize) * calls
    return roofline_pct(moved, run.trace.busy_s, run.device_kind)
