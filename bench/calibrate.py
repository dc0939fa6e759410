"""Readings for a cell's correctness limits: many seeds in one process.

    python3 bench/calibrate.py --workload hpcg32.cg --seeds 11,12,13 \\
        --seconds 10 [--dtype bfloat16]

Runs the cell once per seed, as ``bench/run.py`` does, and prints one JSON
line per run with the compared numbers, then the largest reading of each.
``--dtype`` runs the program in another precision than the configuration
states: the control, which must come out not correct.  Exits non-zero
without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
(ROOT / ".jax_cache").mkdir(exist_ok=True)
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--dtype", default=None)
    args = ap.parse_args(argv)

    from bench.harness import runner

    worst = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        run_args = argparse.Namespace(workload=args.workload, seed=seed,
                                      seconds=args.seconds, trace=0)
        buf, errs = io.StringIO(), io.StringIO()
        t0 = time.perf_counter()
        try:
            res = runner.run(run_args, t0, dtype=args.dtype, out=buf,
                             err=errs)
        except runner.NoChip as e:
            print(f"no chip: {e}", file=sys.stderr)
            return 3
        lines = [json.loads(s) for s in buf.getvalue().splitlines()]
        info = next(x["window"] for x in lines if "window" in x)
        setup = next(x["setup"] for x in lines if "setup" in x)
        for k, c in res["checks"].items():
            worst[k] = max(worst.get(k, 0.0), c["value"])
        print(json.dumps({
            "seed": seed, "dtype": args.dtype, "correct": res["correct"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "metrics": {k: m["value"] for k, m in res["metrics"].items()},
            "setup": setup, "window": info,
            "errors": errs.getvalue().splitlines()[:3]}), flush=True)
    print(json.dumps({"worst": worst, "dtype": args.dtype}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
