"""Run a cell of BENCHMARK.json at a small size on the CPU, for the tests.

Every step of a run is the benchmark's own except the look for a chip: the
sizes shrink, the persistent compilation cache stays off, and the window is
short.
"""
import argparse
import io
import time

from bench.harness import runner

SMALL = {
    "hpcg-27pt-32": {"nx": 6, "ny": 6, "nz": 6},
    "graph500-kron-s15": {"scale": 8},
}


def run_small(cell: str, seed: int = 2**33 + 11, seconds: float = 0.3,
              trace: int = 0, dtype=None) -> dict:
    from bench.harness import manifest as M

    config = {w["name"]: w["config"] for w in M.manifest()["workloads"]}[cell]
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    return runner.run(args, time.perf_counter(), require_chip=False,
                      config_params=SMALL[config], dtype=dtype,
                      compile_cache=False, out=io.StringIO(),
                      err=io.StringIO())
