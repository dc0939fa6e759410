"""Device-idle ms under the program span ``spmv.solve.fetch`` (the step
count, the residual and x back to the host), per solver session wholly
inside the traced window (``bench/harness/spans.py``)."""
from bench.harness import spans

SOURCE = "program_span"


def read(run):
    return spans.idle_ms_per_session(run, "spmv.solve.fetch")
