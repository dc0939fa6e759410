"""The measured window over the solver sessions completed in it."""
SOURCE = "host_clock"


def read(run):
    w = run.window
    if not w["units"]:
        return None
    return 1e3 * w["seconds"] / w["units"]
