"""1 - union of device-op intervals over the traced window, in a solver cell
(the cells that report ``solve_ms``)."""
SOURCE = "device_trace"


def read(run):
    return None if run.trace is None else run.trace.idle_pct
