"""The benchmark's span around the warm-up calls: compile or cache load."""
SOURCE = "host_clock"


def read(run):
    return run.setup.get("warm_s")
