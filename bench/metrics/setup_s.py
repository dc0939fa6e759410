"""Set-up: process start to the first timed call (JAX start, data, register,
warm-up or compile-cache load)."""
SOURCE = "host_clock"


def read(run):
    return run.setup["setup_s"]
