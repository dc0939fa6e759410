"""Device-idle ms under the program span ``spmv.solve.place`` (placing b,
x0 and the scalars), per solver session wholly inside the traced window
(``bench/harness/spans.py``)."""
from bench.harness import spans

SOURCE = "program_span"


def read(run):
    return spans.idle_ms_per_session(run, "spmv.solve.place")
