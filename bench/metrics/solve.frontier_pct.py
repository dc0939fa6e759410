"""The session mean of the frontier's share of a step: each session's
frontier total (``IterateResult.frontier_total``, the sizes of the frontiers
its steps were fed) over its steps times the vertices, in percent.  What a
kernel that read only the frontier's columns would still gather of each
dense (min, +) step."""
SOURCE = "program_counter"


def read(run):
    sessions = [(s, f) for s, f in run.window.get("frontier", ())
                if s and f is not None]
    if not sessions:
        return None
    n = run.shape[1]
    return 100.0 * sum(f / (s * n) for s, f in sessions) / len(sessions)
