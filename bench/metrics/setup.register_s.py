"""The benchmark's span around ``register``: statistics, fingerprint,
containers, partition and placement."""
SOURCE = "host_clock"


def read(run):
    return run.setup.get("register_s")
