"""Run a cell of BENCHMARK.json at a small size on the CPU, for the tests.

Every step of a run is the benchmark's own except the look for a chip: the
sizes shrink, the persistent compilation cache stays off, and the window is
short.  The small sizes are the ``small`` object of the cell's
configuration file (``bench/configs/<config>.json``), which overrides keys
of its ``params``; the benchmark's own runs never read it.
"""
import argparse
import io
import time

from bench.harness import runner


def run_small(cell: str, seed: int = 2**33 + 11, seconds: float = 0.3,
              trace: int = 0, dtype=None) -> dict:
    from bench.harness import manifest as M

    small = M.resolve(cell).config["small"]
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    return runner.run(args, time.perf_counter(), require_chip=False,
                      config_params=small, dtype=dtype,
                      compile_cache=False, out=io.StringIO(),
                      err=io.StringIO())
