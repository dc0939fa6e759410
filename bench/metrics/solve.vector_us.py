"""Device self time of the solver loop programs' ops outside any ``spmv.``
name scope (vector ops, residual checks, loop control), per SpMV step of the
window (``bench/harness/spans.py``)."""
from bench.harness import spans

SOURCE = "device_trace"


def read(run):
    calls = run.window.get("spmv_calls")
    s = spans.of_run(run)
    if s is None or not calls or s.loop_other_s is None:
        return None
    return 1e6 * s.loop_other_s / calls
