"""Mean ``IterateResult.steps`` per solver session in the window."""
SOURCE = "program_counter"


def read(run):
    steps = run.window.get("steps")
    if not steps:
        return None
    return sum(steps) / len(steps)
