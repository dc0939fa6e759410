"""Iterative-solver benchmark — on-device ``iterate`` vs host-stepped loop.

    PYTHONPATH=src python -m benchmarks.solver_bench [--smoke] [--json PATH]

Measures the solver tier's existence claim: :meth:`Executor.iterate` keeps
the iterate **on device** across SpMVs — one dispatch and one host
round-trip per *session* — so a k-step solve must beat the same k steps
issued as host round-trip multiplies (``engine.multiply`` + a numpy
normalize per step, the loop every caller wrote before the tier existed).
Power iteration at ``--steps`` (default 64) is the timed pair; both sides
are checked against each other element-wise before any timing is trusted.

Emits the usual ``name,us_per_call,derived`` CSV rows.  ``--json PATH``
**merges** its rows into an existing benchmark JSON instead of overwriting
it: CI runs this right after ``benchmarks.run --smoke --json
bench_out.json``, so the single ``tools/check_bench.py`` gate sees the
figure rows and the ``solve.*`` rows in one document (any stale ``solve.*``
rows in the target are replaced, everything else is preserved).  The same
merge updates the committed ``BENCH_smoke.json`` baseline in place.

Exit status 1 when the on-device loop fails to beat the host loop by
``--min-speedup`` (default 2.0x) at 64 steps — the acceptance floor — or
when the two loops disagree numerically.  A CG convergence row
(``kind: "count"``: iteration counts are exact, not wall-clock) rides
along so the trajectory records solver behaviour, not just speed.
"""
import argparse
import json
import os
import sys
import time

import numpy as np


def _time_best(fn, repeats: int) -> float:
    """Best-of-``repeats`` wall seconds (min: the least-noise estimator for
    a quiet CPU box; medians over few repeats still carry scheduler spikes).
    """
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for the CI perf job")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="merge the rows into this benchmark JSON "
                         "(created if missing; existing solve.* rows are "
                         "replaced, all other rows preserved)")
    ap.add_argument("--steps", type=int, default=64,
                    help="session length for the timed power-iteration pair")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timing repeats; best-of is reported")
    ap.add_argument("--min-speedup", type=float, default=2.0,
                    help="fail below this iterate-vs-host-loop ratio")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    from repro.data.matrices import scale_free_matrix
    from repro.engine import SpmvEngine

    n = 192 if args.smoke else 1024
    # integer values: float32 SpMV over small integers is exact in any
    # summation order, so the iterate-vs-host-loop check can be strict
    a = np.round(scale_free_matrix(n, n, n * 8, seed=args.seed) * 2.0)
    rng = np.random.default_rng(args.seed + 1)
    x0 = rng.integers(-2, 3, size=n).astype(np.float32)

    engine = SpmvEngine(cache_capacity=8)
    engine.register("graph", a)

    k = args.steps
    # warm both paths: the session loop compiles once per (combine, mode),
    # the multiply path traces once per vector shape
    engine.solve("graph", x0, steps=k, combine="power")
    engine.multiply("graph", x0)

    def host_loop(x):
        for _ in range(k):
            y = engine.multiply("graph", x)
            x = (y / max(np.linalg.norm(y), 1e-30)).astype(np.float32)
        return x

    # both loops implement the same recurrence — disagreement means the
    # on-device combine drifted from the host reference, and no timing of
    # a wrong answer is worth recording
    x_dev = np.asarray(engine.solve("graph", x0, steps=k, combine="power").x)
    x_host = host_loop(x0)
    err = float(np.max(np.abs(x_dev.astype(np.float64)
                              - x_host.astype(np.float64))))
    if not np.isfinite(err) or err > 1e-5:
        print(f"FAIL: iterate and host loop disagree (max |err| {err:.2e})",
              file=sys.stderr)
        return 1

    it_s = _time_best(
        lambda: engine.solve("graph", x0, steps=k, combine="power"),
        args.repeats,
    )
    host_s = _time_best(lambda: host_loop(x0), args.repeats)
    speedup = host_s / it_s if it_s > 0 else float("inf")

    print("name,us_per_call,derived")
    print(f"# --- solve: on-device iterate vs host loop "
          f"({k} steps, n={n}, best of {args.repeats})")
    rows = []

    def row(name: str, us: float, extra: str = "", kind: str = None,
            gate_factor: float = None) -> None:
        r = {"name": name, "us_per_call": round(us, 1), "derived": extra}
        if kind is not None:
            r["kind"] = kind  # count rows are exempt from the perf gate
        if gate_factor is not None:
            r["gate_factor"] = gate_factor  # baseline-side per-row gate
        rows.append(r)
        print(f"{name},{us:.1f},{extra}")

    derived = f"steps={k} n={n} max_err={err:.1e}"
    # gate_factor 4.0: per-step microseconds on tiny CPU shapes are
    # dispatch-dominated — gate catastrophic regressions (a retrace per
    # step), not runner-generation drift
    row("solve.power.iterate.us_per_step", it_s / k * 1e6, derived,
        gate_factor=4.0)
    row("solve.power.host_loop.us_per_step", host_s / k * 1e6, derived,
        gate_factor=4.0)
    row("solve.power.speedup_x", speedup,
        f"host_loop/iterate at {k} steps (floor {args.min_speedup}x)",
        kind="count")

    # CG on the SPD 1D Laplacian: exact, machine-independent iteration
    # count — the convergence regression the trajectory tracks
    m = 64
    lap = (4.0 * np.eye(m) - np.eye(m, k=1) - np.eye(m, k=-1)).astype(
        np.float32)
    b = rng.integers(-2, 3, size=m).astype(np.float32)
    engine.register("laplacian", lap)
    res = engine.solve("laplacian", np.zeros(m, dtype=np.float32),
                       tol=1e-5, combine="cg", b=b, max_steps=200,
                       check_every=1)
    x_ref = np.linalg.solve(lap.astype(np.float64), b.astype(np.float64))
    cg_err = float(np.max(np.abs(np.asarray(res.x, dtype=np.float64)
                                 - x_ref)))
    row("solve.cg.laplacian.iters", float(res.steps),
        f"tol=1e-5 converged={res.converged} max_err={cg_err:.1e}",
        kind="count")

    if args.json:
        doc = {"version": 1,
               "mode": "solver-smoke" if args.smoke else "solver",
               "rows": []}
        if os.path.exists(args.json):
            with open(args.json, encoding="utf-8") as fh:
                doc = json.load(fh)
            if not isinstance(doc, dict):  # bare row-list documents
                doc = {"version": 1, "rows": doc}
        kept = [r for r in doc.get("rows", [])
                if not str(r.get("name", "")).startswith("solve.")]
        doc["rows"] = kept + rows
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
        print(f"# merged {len(rows)} solve.* rows into {args.json} "
              f"({len(doc['rows'])} total)", file=sys.stderr)

    fails = []
    if speedup < args.min_speedup:
        fails.append(f"iterate only {speedup:.2f}x vs host loop at {k} "
                     f"steps (floor {args.min_speedup}x)")
    if not res.converged:
        fails.append(f"CG failed to converge on the SPD Laplacian "
                     f"(residual {res.residual:.2e} after {res.steps} steps)")
    if cg_err > 1e-3:
        fails.append(f"CG solution off by {cg_err:.2e} vs dense solve")
    if fails:
        print(f"FAIL: {'; '.join(fails)}", file=sys.stderr)
        return 1
    print(f"# solve OK: speedup {speedup:.1f}x, CG {res.steps} iters")
    return 0


if __name__ == "__main__":
    sys.exit(main())
