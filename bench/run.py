"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout; see ``bench/harness/runner.py``.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache lives at a fixed path in this checkout
# (the program's cache helper takes the directory this names)
CACHE_DIR = ROOT / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

if __name__ == "__main__":
    try:
        import repro.api
    except ImportError as e:
        print(f"the program under test is missing: {e}", file=sys.stderr)
        sys.exit(2)
    if Path(repro.api.__file__).resolve().parents[2] != ROOT / "src":
        print(f"repro imported from {repro.api.__file__}, not from this "
              "checkout", file=sys.stderr)
        sys.exit(2)
    CACHE_DIR.mkdir(exist_ok=True)
    from bench.harness.runner import main

    sys.exit(main(t_start=T_START))
