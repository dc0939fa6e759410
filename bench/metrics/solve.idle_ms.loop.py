"""Device-idle ms under the program span ``spmv.solve.loop`` (the loop's
dispatch before its first op and the host's return after its last), per
solver session wholly inside the traced window
(``bench/harness/spans.py``)."""
from bench.harness import spans

SOURCE = "program_span"


def read(run):
    return spans.idle_ms_per_session(run, "spmv.solve.loop")
